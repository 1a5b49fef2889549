"""Static cost accounting for compiled step functions (the MFU ground
truth).

Every MFU number this repo committed before round-19 was hand-derived:
``measure.py`` multiplies ``gpt2_flops_per_token`` (the PaLM appendix-B
estimate) by tok/s. That formula silently diverges from what XLA
actually compiled — fused ops, remat, optimizer FLOPs, padding — so the
step anatomy plane computes cost from the compiled HLO instead:
``jitted.lower(*args).compile().cost_analysis()`` gives FLOPs and bytes
accessed for the exact program the device runs, ``memory_analysis()``
the argument/output/temp footprint. From those, arithmetic intensity
and the roofline position against the ``measure.py`` per-device-kind
peak table (plus the HBM-bandwidth table below) decide compute- vs
memory-bound *before* any step is timed; MFU then divides measured
step FLOP/s by the same peak the roofline used.

Off-jax discipline (the ``device_telemetry`` idiom): this module NEVER
imports jax itself — a node agent must not initialize a backend and
steal the chip from its workers. Every entry point degrades to a stub
with ``available=False`` when jax is not already loaded or the cost
query fails, so callers can ship the dict unconditionally.
"""

from __future__ import annotations

import sys
from typing import Any, Dict, Optional

# Peak HBM GB/s per chip by device kind substring (roofline ridge
# denominators; same substring-match protocol as measure.PEAK_TFLOPS).
PEAK_HBM_GBPS = {
    "v5 lite": 819.0,
    "v5litepod": 819.0,
    "v5e": 819.0,
    "v4": 1228.0,
    "v5p": 2765.0,
    "v6 lite": 1640.0,
    "v6e": 1640.0,
    "cpu": 50.0,  # nominal DDR: tier-1's step-anatomy tests run off-TPU
}


def jax_loaded() -> bool:
    """Has something in this process already imported jax? (We piggyback
    on their import; we never trigger one.)"""
    return "jax" in sys.modules


def peak_hbm_bytes_per_s(device_kind: str) -> float:
    """Peak HBM bytes/s of one chip of this kind; an unknown kind is an
    error, never a default (same rule as ``peak_flops_per_chip``)."""
    kind = (device_kind or "").lower()
    for key, gbps in PEAK_HBM_GBPS.items():
        if key in kind:
            return gbps * 1e9
    raise ValueError(
        f"no peak HBM bandwidth known for device kind {device_kind!r}; "
        f"add it to PEAK_HBM_GBPS with its source")


def stub(reason: str = "jax not loaded") -> Dict[str, Any]:
    """The off-jax / on-failure shape: same keys a caller branches on,
    ``available=False`` so nothing downstream mistakes it for a cost."""
    return {"available": False, "reason": reason}


def _device_kind() -> str:
    if not jax_loaded():
        return ""
    try:
        import jax

        d = jax.devices()[0]
        return getattr(d, "device_kind", "") or d.platform
    except Exception:
        return ""


def _merge_cost_analysis(cost: Any) -> Dict[str, float]:
    """``compiled.cost_analysis()`` returns a list of per-program dicts
    on jax>=0.4 (one per partition; usually length 1) or a bare dict on
    older versions. Sum the numeric keys we account for."""
    if cost is None:
        return {}
    entries = cost if isinstance(cost, (list, tuple)) else [cost]
    out = {"flops": 0.0, "bytes accessed": 0.0}
    seen = False
    for entry in entries:
        if not isinstance(entry, dict):
            continue
        seen = True
        for key in out:
            try:
                out[key] += float(entry.get(key, 0.0) or 0.0)
            except (TypeError, ValueError):
                pass
    return out if seen else {}


def analyze_compiled(compiled: Any,
                     device_kind: Optional[str] = None) -> Dict[str, Any]:
    """Cost-account an already-compiled executable (the output of
    ``jitted.lower(*args).compile()``)."""
    try:
        merged = _merge_cost_analysis(compiled.cost_analysis())
    except Exception as exc:  # backend without cost_analysis support
        return stub(f"cost_analysis failed: {exc!r}")
    if not merged:
        return stub("cost_analysis returned no per-program entries")
    flops = merged.get("flops", 0.0)
    bytes_accessed = merged.get("bytes accessed", 0.0)
    kind = device_kind if device_kind is not None else _device_kind()
    # Lazy import: scripts.measure owns the peak-FLOPs table (the MFU
    # denominators the committed evidence already uses) and is
    # dependency-free, but util must not import scripts at module load.
    from ray_tpu.scripts.measure import peak_flops_per_chip

    try:
        peak_flops = peak_flops_per_chip(kind)
        peak_bw = peak_hbm_bytes_per_s(kind)
    except ValueError as exc:
        # No roofline against a guessed peak: callers ship the stub.
        return stub(str(exc))
    intensity = flops / bytes_accessed if bytes_accessed > 0 else 0.0
    ridge = peak_flops / peak_bw if peak_bw > 0 else 0.0
    out: Dict[str, Any] = {
        "available": True,
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "intensity_flops_per_byte": round(intensity, 3),
        "device_kind": kind,
        "peak_flops": peak_flops,
        "peak_hbm_bytes_per_s": peak_bw,
        "ridge_flops_per_byte": round(ridge, 3),
        "roofline": "compute-bound" if intensity >= ridge
        else "memory-bound",
        "roofline_frac": round(intensity / ridge, 4) if ridge > 0 else 0.0,
    }
    try:
        mem = compiled.memory_analysis()
        out["memory"] = {
            "argument_bytes": int(getattr(
                mem, "argument_size_in_bytes", 0) or 0),
            "output_bytes": int(getattr(
                mem, "output_size_in_bytes", 0) or 0),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0) or 0),
            "generated_code_bytes": int(getattr(
                mem, "generated_code_size_in_bytes", 0) or 0),
        }
    except Exception:
        out["memory"] = {}
    return out


def step_cost(step_fn: Any, *args: Any,
              device_kind: Optional[str] = None,
              **kwargs: Any) -> Dict[str, Any]:
    """Cost-account a jitted step function against example arguments.

    ``step_fn`` must be a ``jax.jit`` product (anything with
    ``.lower``); the lowering traces with the example args' shapes —
    the same specialization the training loop will execute — and the
    compile hits jax's in-process executable cache when the loop
    already compiled this shape."""
    if not jax_loaded():
        return stub()
    if not hasattr(step_fn, "lower"):
        return stub("step_fn has no .lower (not a jax.jit product)")
    try:
        compiled = step_fn.lower(*args, **kwargs).compile()
    except Exception as exc:
        return stub(f"lower/compile failed: {exc!r}")
    return analyze_compiled(compiled, device_kind=device_kind)


def mfu_percent(flops_per_step: float, step_seconds: float,
                device_kind: Optional[str] = None,
                n_devices: int = 1) -> float:
    """Measured model-FLOPs utilization: cost-model FLOPs per step over
    measured step seconds, against the device peak (one chip's peak x
    device count) — the same denominator ``measure.py`` uses, so the
    HLO-derived number is directly comparable to the formula-derived
    one."""
    if step_seconds <= 0 or flops_per_step <= 0:
        return 0.0
    from ray_tpu.scripts.measure import peak_flops_per_chip

    kind = device_kind if device_kind is not None else _device_kind()
    peak = peak_flops_per_chip(kind) * max(1, n_devices)
    if peak <= 0:
        return 0.0
    return flops_per_step / step_seconds / peak * 100.0
