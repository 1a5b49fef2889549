"""Build the native C++ components on first import (cached by mtime).

The reference builds its native core via bazel (``src/ray/BUILD``); here the
native surface is small enough that a direct g++ invocation with an mtime
cache is simpler and hermetic (no generated build files in-tree).
"""

from __future__ import annotations

import os
import subprocess
import threading

_SRC_DIR = os.path.join(os.path.dirname(__file__), "src")
_LIB_DIR = os.path.join(os.path.dirname(__file__), "lib")
_LOCK = threading.Lock()

_LIBS = {
    # lib name -> source files
    "shm_store": ["shm_store.cc"],
}


def lib_path(name: str) -> str:
    return os.path.join(_LIB_DIR, f"lib{name}.so")


def _compile(out: str, sources: list, flags: list) -> str:
    """mtime-cached g++ compile to ``out`` (atomic tmp+rename)."""
    with _LOCK:
        if os.path.exists(out):
            # Headers count: every .cc includes headers from src/, and a
            # protocol change in e.g. rpc_channel.h must invalidate cached
            # binaries or old workers would fail the new handshake.
            headers = [
                os.path.join(_SRC_DIR, f)
                for f in os.listdir(_SRC_DIR) if f.endswith(".h")
            ]
            src_mtime = max(os.path.getmtime(s) for s in sources + headers)
            if os.path.getmtime(out) >= src_mtime:
                return out
        os.makedirs(_LIB_DIR, exist_ok=True)
        tmp = out + f".tmp.{os.getpid()}"
        cmd = ["g++", *flags, "-std=c++17", "-pthread", "-o", tmp, *sources]
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, out)  # atomic w.r.t. concurrent builders
    return out


def ensure_built(name: str, force: bool = False) -> str:
    """Compile lib<name>.so if missing or stale; return its path.
    ``force`` discards the cached binary first — the dlopen self-heal
    path for a checked-out .so built against an incompatible glibc."""
    out = lib_path(name)
    if force:
        with _LOCK:
            try:
                os.unlink(out)
            except OSError:
                pass
    sources = [os.path.join(_SRC_DIR, s) for s in _LIBS[name]]
    return _compile(out, sources, ["-O2", "-g", "-fPIC", "-shared"])


def build_cpp_worker() -> str:
    """Build the sample C++ worker/driver binary (the native worker API's
    reference executable — ``cpp/`` worker parity). Also usable as a
    template: user worker binaries compile their own functions against
    raytpu.h + raytpu_runtime.cc the same way."""
    sources = [
        os.path.join(_SRC_DIR, "sample_worker.cc"),
        os.path.join(_SRC_DIR, "raytpu_runtime.cc"),
        os.path.join(_SRC_DIR, "shm_store.cc"),
    ]
    return _compile(
        os.path.join(_LIB_DIR, "raytpu_sample_worker"), sources, ["-O2", "-g"])


def build_stress_binary(sanitize: str | None = None) -> str:
    """Build the multithreaded store stress driver (store_stress.cc +
    shm_store.cc in one binary), optionally under a sanitizer
    ("address" / "thread" / "undefined") — SURVEY §5.2 race detection.
    Cached by mtime per sanitizer flavor."""
    tag = sanitize or "plain"
    sources = [
        os.path.join(_SRC_DIR, "store_stress.cc"),
        os.path.join(_SRC_DIR, "shm_store.cc"),
    ]
    flags = ["-O1", "-g"]
    if sanitize:
        flags += [f"-fsanitize={sanitize}", "-fno-omit-frame-pointer"]
    return _compile(
        os.path.join(_LIB_DIR, f"store_stress_{tag}"), sources, flags)
