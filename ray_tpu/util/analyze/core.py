"""Analyzer core: findings, pass registry, runner, baseline, diff mode.

Every pass is a function ``(module: ParsedModule) -> Iterable[Finding]``
registered under a rule-family name via :func:`analysis_pass`. The
runner parses each target file once (stdlib ``ast`` — no new deps) and
hands the same :class:`ParsedModule` to every selected pass.

Findings carry a *stable key* (rule : relpath : scope : detail — no
line numbers, so unrelated edits don't churn the allowlist) matched
against the committed ``ANALYZE_BASELINE.json``: only findings whose
key is absent from the baseline fail the run. Baseline entries map the
key to a one-line justification; an entry whose key no longer matches
any finding is reported as stale so the allowlist can only shrink.
"""

from __future__ import annotations

import ast
import json
import os
import re
import subprocess
from typing import Callable, Dict, Iterable, List, Optional, Sequence

BASELINE_FILENAME = "ANALYZE_BASELINE.json"

# Line pragma: `# analyze: ignore[LO001]` or `# analyze: ignore` —
# suppresses findings anchored on that source line.
_IGNORE_RE = re.compile(r"#\s*analyze:\s*ignore(?:\[([A-Z0-9, ]+)\])?")


class Finding:
    """One rule violation at a source location."""

    __slots__ = ("rule", "path", "line", "scope", "detail", "message",
                 "hint")

    def __init__(self, rule: str, path: str, line: int, scope: str,
                 detail: str, message: str, hint: str = ""):
        self.rule = rule
        self.path = path  # repo-relative
        self.line = line
        self.scope = scope  # enclosing class.method (or <module>)
        self.detail = detail  # rule-specific stable discriminator
        self.message = message
        self.hint = hint

    @property
    def key(self) -> str:
        """Baseline identity: stable across line-number churn."""
        return f"{self.rule}:{self.path}:{self.scope}:{self.detail}"

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"<Finding {self.rule} {self.path}:{self.line} {self.detail}>"

    def to_dict(self) -> dict:
        return {
            "rule": self.rule, "file": self.path, "line": self.line,
            "scope": self.scope, "detail": self.detail, "key": self.key,
            "message": self.message, "hint": self.hint,
        }

    def format(self) -> str:
        out = (f"{self.path}:{self.line}: {self.rule} [{self.scope}] "
               f"{self.message}")
        if self.hint:
            out += f"\n    fix: {self.hint}"
        return out


class ParsedModule:
    """One target file, parsed once and shared by every pass."""

    def __init__(self, path: str, relpath: str, source: str,
                 tree: ast.Module):
        self.path = path
        self.relpath = relpath
        self.source = source
        self.lines = source.splitlines()
        self.tree = tree
        self._ignores: Optional[Dict[int, Optional[set]]] = None
        self._model = None

    def model(self):
        """The module's lock/alias model, built once and shared by
        every pass (the resolver walk is the expensive part)."""
        if self._model is None:
            from ray_tpu.util.analyze.resolver import ModuleModel

            self._model = ModuleModel(self.tree, self.lines)
        return self._model

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1]
        return ""

    def ignored(self, rule: str, lineno: int) -> bool:
        """True when the line carries `# analyze: ignore[...]` for this
        rule (or a bare ignore covering every rule)."""
        if self._ignores is None:
            table: Dict[int, Optional[set]] = {}
            for i, text in enumerate(self.lines, 1):
                m = _IGNORE_RE.search(text)
                if m:
                    rules = m.group(1)
                    table[i] = (set(r.strip() for r in rules.split(","))
                                if rules else None)
            self._ignores = table
        rules = self._ignores.get(lineno, False)
        if rules is False:
            return False
        return rules is None or rule in rules


class FindingSink:
    """Deduping finding collector shared by the passes: one emit
    helper, one identity rule (rule, line, scope, detail)."""

    def __init__(self, relpath: str):
        self.relpath = relpath
        self.findings: List[Finding] = []
        self._seen: set = set()

    def emit(self, rule: str, line: int, scope: str, detail: str,
             message: str, hint: str = "") -> None:
        ident = (rule, line, scope, detail)
        if ident in self._seen:
            return
        self._seen.add(ident)
        self.findings.append(Finding(rule, self.relpath, line, scope,
                                     detail, message, hint))


# rule-family name -> pass callable
PASSES: "Dict[str, Callable[[ParsedModule], Iterable[Finding]]]" = {}

# rule-family name -> cross-module checker run only on FULL scans (the
# whole tree must be in view: stale failpoint sites, gauge families
# emitted in one module and retracted in another). Keyed by the same
# family name as the per-module pass so --rule selection covers both.
CROSS_PASSES: "Dict[str, Callable[[Sequence[ParsedModule]], Iterable[Finding]]]" = {}


def analysis_pass(name: str):
    """Register a pass under a ``--rule`` family name."""

    def deco(fn):
        PASSES[name] = fn
        return fn

    return deco


def cross_pass(name: str):
    """Register a full-scan cross-module checker for a rule family."""

    def deco(fn):
        CROSS_PASSES[name] = fn
        return fn

    return deco


def repo_root() -> str:
    """The checkout root (this file lives in ray_tpu/util/analyze/)."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))


def default_paths() -> List[str]:
    """The product tree the repo-wide run covers: every .py under the
    ray_tpu package (tests hold intentional-violation fixtures and the
    scripts are covered too — they ride the package)."""
    root = repo_root()
    pkg = os.path.join(root, "ray_tpu")
    out = []
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames
                       if d not in ("__pycache__", "_native")]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                out.append(os.path.join(dirpath, fn))
    return out


def parse_file(path: str, root: Optional[str] = None) -> Optional[ParsedModule]:
    root = root or repo_root()
    try:
        with open(path, encoding="utf-8") as f:
            source = f.read()
        tree = ast.parse(source, filename=path)
    except (OSError, SyntaxError):
        return None
    rel = os.path.relpath(os.path.abspath(path), root)
    return ParsedModule(path, rel.replace(os.sep, "/"), source, tree)


def _select_passes(rules: Optional[Sequence[str]]):
    if not rules:
        return dict(PASSES)
    unknown = [r for r in rules if r not in PASSES]
    if unknown:
        raise ValueError(
            f"unknown rule(s) {unknown}; have {sorted(PASSES)}")
    return {r: PASSES[r] for r in rules}


def run_modules(modules: Sequence[ParsedModule],
                rules: Optional[Sequence[str]] = None) -> List[Finding]:
    selected = _select_passes(rules)
    findings: List[Finding] = []
    for mod in modules:
        for fn in selected.values():
            for f in fn(mod):
                if not mod.ignored(f.rule, f.line):
                    findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.rule, f.detail))
    return findings


def run_paths(paths: Sequence[str],
              rules: Optional[Sequence[str]] = None,
              root: Optional[str] = None) -> List[Finding]:
    """Run the selected passes over the target files; returns findings
    sorted by location. Unknown rule names raise ValueError (a typo'd
    --rule must not silently pass)."""
    root = root or repo_root()
    modules = [m for m in (parse_file(p, root) for p in paths)
               if m is not None]
    return run_modules(modules, rules)


def baseline_path(root: Optional[str] = None) -> str:
    return os.path.join(root or repo_root(), BASELINE_FILENAME)


def load_baseline(path: Optional[str] = None) -> Dict[str, str]:
    """{finding key: one-line justification}. Missing file = empty."""
    path = path or baseline_path()
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError):
        return {}
    entries = data.get("entries", data) if isinstance(data, dict) else {}
    return {str(k): str(v) for k, v in entries.items()
            if not str(k).startswith("_")}


def apply_baseline(findings: Sequence[Finding],
                   baseline: Dict[str, str]):
    """Split findings into (new, allowlisted) and report stale baseline
    keys that matched nothing (the allowlist must only shrink)."""
    new: List[Finding] = []
    allowed: List[Finding] = []
    seen: set = set()
    for f in findings:
        if f.key in baseline:
            allowed.append(f)
            seen.add(f.key)
        else:
            new.append(f)
    stale = sorted(k for k in baseline if k not in seen)
    return new, allowed, stale


def changed_lines(rev: str,
                  root: Optional[str] = None) -> Dict[str, Optional[set]]:
    """{repo-relative path: set of changed/added line numbers} since
    ``rev``, from a cheap ``git diff -U0`` parse (the ``--diff`` mode:
    a PR sees findings on the lines it touched, not the whole repo).
    Brand-new UNTRACKED .py files — which ``git diff`` omits entirely —
    map to ``None``, meaning every line counts as changed (a new module
    is 100%% the PR's lines; silently skipping it would false-pass the
    exact violations the PR introduced)."""
    root = root or repo_root()
    try:
        out = subprocess.run(
            ["git", "diff", "-U0", rev, "--", "*.py"],
            cwd=root, capture_output=True, text=True, timeout=60)
        untracked = subprocess.run(
            ["git", "ls-files", "--others", "--exclude-standard",
             "--", "*.py"],
            cwd=root, capture_output=True, text=True, timeout=60)
    except (OSError, subprocess.TimeoutExpired) as e:
        raise RuntimeError(f"git diff against {rev!r} failed: {e}")
    if out.returncode != 0:
        raise RuntimeError(
            f"git diff against {rev!r} failed: {out.stderr.strip()}")
    changed: Dict[str, Optional[set]] = {}
    if untracked.returncode == 0:
        for path in untracked.stdout.splitlines():
            if path.strip():
                changed[path.strip()] = None  # all lines are new
    current: Optional[str] = None
    for line in out.stdout.splitlines():
        if line.startswith("+++ "):
            target = line[4:].strip()
            current = None if target == "/dev/null" else \
                target[2:] if target.startswith("b/") else target
        elif line.startswith("@@") and current is not None:
            m = re.search(r"\+(\d+)(?:,(\d+))?", line)
            if not m:
                continue
            start = int(m.group(1))
            count = int(m.group(2)) if m.group(2) is not None else 1
            if count <= 0:
                # Pure-deletion hunk (`+N,0`): no line in the new file
                # was touched — marking N "changed" would pin someone
                # else's finding on a deletion-only PR.
                continue
            changed.setdefault(current, set()).update(
                range(start, start + count))
    return changed


def filter_to_diff(findings: Sequence[Finding],
                   changed: Dict[str, Optional[set]]) -> List[Finding]:
    out = []
    for f in findings:
        lines = changed.get(f.path, ())
        if lines is None or f.line in lines:  # None = whole file is new
            out.append(f)
    return out


def rule_counts(findings: Sequence[Finding]) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for f in findings:
        counts[f.rule] = counts.get(f.rule, 0) + 1
    return dict(sorted(counts.items()))


def _cross_ignored(modules: Sequence[ParsedModule], f: Finding) -> bool:
    """Honor line pragmas for cross-module findings too: the module the
    finding anchors to is in view on a full scan by construction."""
    for m in modules:
        if m.relpath == f.path:
            return m.ignored(f.rule, f.line)
    return False


def run(paths: Optional[Sequence[str]] = None,
        rules: Optional[Sequence[str]] = None,
        use_baseline: bool = True,
        baseline_file: Optional[str] = None,
        diff_rev: Optional[str] = None,
        root: Optional[str] = None) -> dict:
    """One-call API (the CLI and the tier-1 test share it).

    Returns ``{findings, new, allowed, stale_baseline, rule_counts,
    ok}`` where ``ok`` means zero unbaselined findings (stale baseline
    keys are reported but don't fail — a fix must not break the gate)."""
    root = root or repo_root()
    full_scan = not paths
    paths = list(paths) if paths else default_paths()
    modules = [m for m in (parse_file(p, root) for p in paths)
               if m is not None]
    findings = run_modules(modules, rules)
    if full_scan:
        # Cross-module checks: they need the whole tree in view, so
        # they only run on full scans (a path-restricted run would
        # report every site it didn't happen to look at as stale).
        for name, fn in CROSS_PASSES.items():
            if not rules or name in rules:
                findings.extend(f for f in fn(modules)
                                if not _cross_ignored(modules, f))
        findings.sort(key=lambda f: (f.path, f.line, f.rule, f.detail))
    if diff_rev:
        findings = filter_to_diff(findings, changed_lines(diff_rev, root))
    baseline = load_baseline(baseline_file or baseline_path(root)) \
        if use_baseline else {}
    new, allowed, stale = apply_baseline(findings, baseline)
    # Stale-key reporting is only meaningful when the run could have
    # matched the key: a diff- or rule-restricted run hides findings by
    # design, and a path-restricted run never saw other files — advising
    # "remove it" there would delete still-needed justifications.
    if diff_rev or rules:
        stale = []
    elif not full_scan:
        scanned = {m.relpath for m in modules}
        stale = [k for k in stale
                 if ":" in k and k.split(":")[1] in scanned]
    return {
        "findings": findings,
        "new": new,
        "allowed": allowed,
        "stale_baseline": stale,
        "rule_counts": rule_counts(findings),
        "new_rule_counts": rule_counts(new),
        "n_files": len(paths),
        "ok": not new,
    }
