"""``detlint`` — the determinism / bit-exactness linter for ``src/repro``.

Every check below encodes a trap this repo actually hit (see
``CHANGES.md``); each check's docstring cites the PR where the trap was
found by hand so the rule's provenance is reviewable.  The linter is
purely syntactic (one ``ast`` parse per file, no imports of the linted
code), deterministic, and fast enough to run as a hard CI gate.

Suppression syntax — intentional exceptions must be visible in review::

    jax.jit(step, donate_argnums=(0, 1))  # detlint: ignore[det-donate-argnums] training step; no serving state

A suppression comment applies to the findings on its own line, or — when
the comment stands alone on a line — to the next line.  Only
suppressions that actually silenced a finding are recorded in the
report; the reason text after the bracket is carried verbatim.
"""
from __future__ import annotations

import ast
import dataclasses
import re
from pathlib import Path
from typing import Any, Callable, Iterable

from repro.obs.invariants import CONSERVED_SCHED, CONSERVED_WORKLOAD
from repro.obs.phases import PHASES
from .report import Finding, Suppression

#: Path prefixes (relative to the linted root) where wall-clock reads
#: would contaminate state, snapshots, serialized artifacts, or numerics.
#: Training-side telemetry (train/, launch/) is out of scope by design.
STATE_PATHS = ("serve/", "deploy/", "compress/", "obs/", "core/", "data/",
               "kernels/")

#: Paths where iteration order feeds fused dispatch or stats output.
ORDERED_PATHS = ("serve/", "obs/")

#: Receiver names that identify tracer objects at span call sites.
_TRACER_NAMES = ("tr", "tracer", "_tracer")
_SPAN_OPENERS = ("open", "open_count")

_SUPPRESS_RE = re.compile(
    r"#\s*detlint:\s*ignore\[([a-z0-9\-, ]+)\]\s*(.*)$")

_WALLCLOCK_CALLS = {
    ("time", "time"), ("time", "time_ns"), ("time", "localtime"),
    ("time", "ctime"), ("time", "asctime"), ("time", "strftime"),
    ("datetime", "now"), ("datetime", "utcnow"), ("datetime", "today"),
    ("date", "today"),
}


def _dotted(node: ast.AST) -> tuple[str, ...]:
    """('a', 'b', 'c') for ``a.b.c``; () when not a plain dotted name."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return tuple(reversed(parts))
    return ()


def _recv_name(call_func: ast.Attribute) -> str:
    """Last component of the receiver of a method call (``self._tracer``
    -> '_tracer', ``tr`` -> 'tr')."""
    v = call_func.value
    if isinstance(v, ast.Attribute):
        return v.attr
    if isinstance(v, ast.Name):
        return v.id
    return ""


def _is_tracer_recv(call_func: ast.AST) -> bool:
    return (isinstance(call_func, ast.Attribute)
            and _recv_name(call_func) in _TRACER_NAMES)


def _mentions_jit(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id == "jit":
            return True
        if isinstance(sub, ast.Attribute) and sub.attr == "jit":
            return True
    return False


@dataclasses.dataclass(frozen=True)
class Check:
    name: str
    doc: str                                   # one line, cites the trap
    scope: Callable[[str], bool]               # relpath -> lint this file?
    run: Callable[[ast.AST, str], Iterable[tuple[int, str]]]


def _everywhere(path: str) -> bool:
    return True


def _state_paths(path: str) -> bool:
    return path.startswith(STATE_PATHS)


def _ordered_paths(path: str) -> bool:
    return path.startswith(ORDERED_PATHS)


def _span_paths(path: str) -> bool:
    # obs/trace.py implements the primitive (its _Span adapter forwards a
    # caller-supplied phase); consumers everywhere else are in scope.
    return path != "obs/trace.py"


# ---------------------------------------------------------------------------
# Check bodies
# ---------------------------------------------------------------------------

def _check_builtin_hash(tree: ast.AST, path: str):
    """PR 1: synthetic HAPT was seeded via ``hash(split)`` — randomized
    per process by PYTHONHASHSEED, so two runs produced different
    datasets.  Fixed to crc32; ``hash()`` stays banned in ``src/repro``."""
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "hash"):
            yield (node.lineno,
                   "builtin hash() is PYTHONHASHSEED-randomized; use "
                   "zlib.crc32 (see data/hapt.py) for stable seeding")


def _check_wallclock(tree: ast.AST, path: str):
    """PR 1 / PR 7: wall-clock reads in state, snapshot, or serialized
    paths break byte-identical replay (the metrics snapshot explicitly
    strips wallclock-tagged fields to stay byte-stable).  Monotonic
    ``perf_counter`` timing for telemetry is allowed."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func)
        if len(d) >= 2 and (d[-2], d[-1]) in _WALLCLOCK_CALLS:
            yield (node.lineno,
                   f"wall-clock call {'.'.join(d)}() in a state/snapshot "
                   f"path; deterministic outputs must not read the clock")


def _check_donate_argnums(tree: ast.AST, path: str):
    """PR 8: ``donate_argnums`` made the XLA CPU executable ~3x slower
    for the resident step AND shifted its fusion by ~1 ulp, breaking the
    host-vs-device bit-identity contract.  Donation anywhere near the
    serving path needs an explicit, visible exception."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            for kw in node.keywords:
                if kw.arg in ("donate_argnums", "donate_argnames"):
                    yield (kw.value.lineno,
                           f"{kw.arg} changes XLA fusion (~1 ulp) and was "
                           f"measured 3x slower on CPU (PR 8); donation "
                           f"must be an explicit suppressed exception")


def _check_jit_pallas(tree: ast.AST, path: str):
    """PR 8: wrapping an interpret-mode pallas call in ``jax.jit`` fuses
    the pad/slice into the trace and makes the result batch-shape
    unstable (~1 ulp between a 16-row dispatch and two 8-row ones) —
    the resident pallas wrapper runs its pads eagerly for exactly this
    reason (kernels/fastgrnn_cell/ops.py::_build_pallas_resident)."""
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        jit_decs = [d for d in node.decorator_list if _mentions_jit(d)]
        if not jit_decs:
            continue
        calls_pallas = any(
            isinstance(sub, ast.Call) and (
                (isinstance(sub.func, ast.Attribute)
                 and sub.func.attr == "pallas_call")
                or (isinstance(sub.func, ast.Name)
                    and sub.func.id == "pallas_call"))
            for sub in ast.walk(node))
        if calls_pallas:
            yield (jit_decs[0].lineno,
                   f"jax.jit wraps pallas_call in {node.name}(): "
                   f"interpret-mode pallas under jit is batch-shape "
                   f"unstable (~1 ulp, PR 8)")


def _check_set_iteration(tree: ast.AST, path: str):
    """PR 5/7 hygiene: fused-dispatch grouping and stats assembly must
    not iterate containers with unspecified order; a ``set`` iterated
    into a dispatch order or a stats list makes output
    machine-dependent.  Sort first (``sorted(set(...))`` is fine)."""
    def is_unordered(it: ast.AST) -> bool:
        if isinstance(it, (ast.Set, ast.SetComp)):
            return True
        return (isinstance(it, ast.Call) and isinstance(it.func, ast.Name)
                and it.func.id in ("set", "frozenset"))

    for node in ast.walk(tree):
        iters: list[ast.AST] = []
        if isinstance(node, (ast.For, ast.AsyncFor)):
            iters.append(node.iter)
        elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                               ast.GeneratorExp)):
            iters.extend(g.iter for g in node.generators)
        for it in iters:
            if is_unordered(it):
                yield (it.lineno,
                       "iteration over a set has unspecified order in a "
                       "dispatch/stats path; wrap in sorted(...)")


def _function_scopes(tree: ast.AST):
    """Yield (function node, direct statements excluding nested defs)."""
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _walk_own(fn: ast.AST):
    """Walk a function body without descending into nested functions."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _check_span_pairing(tree: ast.AST, path: str):
    """PR 7: spans are recorded as a ``tok = tracer.open(phase)`` /
    ``tracer.close(tok)`` pair (``open_count`` likewise).  A token that
    is never passed to ``close`` is a dropped span (latency silently
    missing from the phase breakdown), and a non-literal phase defeats
    the static registry check."""
    for fn in _function_scopes(tree):
        starts: dict[str, int] = {}
        consumed: set[str] = set()
        for node in _walk_own(fn):
            if (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)
                    and isinstance(node.value.func, ast.Attribute)
                    and node.value.func.attr in _SPAN_OPENERS
                    and _is_tracer_recv(node.value.func)):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        starts[tgt.id] = node.lineno
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and _is_tracer_recv(node.func)):
                args = node.args
                if (node.func.attr in _SPAN_OPENERS + ("span",)
                        and not (args and isinstance(args[0], ast.Constant)
                                 and isinstance(args[0].value, str))):
                    yield (node.lineno,
                           f"span phase passed to .{node.func.attr}() must "
                           f"be a string literal (registry-checkable)")
                if (node.func.attr == "close" and args
                        and isinstance(args[0], ast.Name)):
                    consumed.add(args[0].id)
        for name, line in sorted(starts.items()):
            if name not in consumed:
                yield (line,
                       f"span token {name} = tracer.open(...) is never "
                       f"passed to tracer.close(...) in {fn.name}() — "
                       f"dropped span")


def _check_span_registry(tree: ast.AST, path: str):
    """PR 7: every recorded phase must be in
    ``repro.obs.phases.PHASES`` — a typo'd phase silently interns a new
    ring and splits the latency history for that phase."""
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _SPAN_OPENERS + ("span",)
                and _is_tracer_recv(node.func)
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            phase = node.args[0].value
            if phase not in PHASES:
                yield (node.lineno,
                       f"span phase {phase!r} is not registered in "
                       f"repro.obs.phases.PHASES")


def _dict_keys_of(node: ast.AST) -> set[str] | None:
    """String keys of a dict literal or a ``{k: 0 for k in (...)}``
    comprehension over a literal tuple/list; None when not static."""
    if isinstance(node, ast.Dict):
        keys = set()
        for k in node.keys:
            if not (isinstance(k, ast.Constant) and isinstance(k.value, str)):
                return None
            keys.add(k.value)
        return keys
    if isinstance(node, ast.DictComp):
        it = node.generators[0].iter if node.generators else None
        if isinstance(it, (ast.Tuple, ast.List)):
            keys = set()
            for e in it.elts:
                if not (isinstance(e, ast.Constant)
                        and isinstance(e.value, str)):
                    return None
                keys.add(e.value)
            return keys
    return None


def _check_conserved_counters(tree: ast.AST, path: str):
    """PR 6/7: fleet totals obey the conservation law live + retired ==
    total (``repro.obs.invariants``).  The retired accumulators in
    ``FleetEngine`` and the conservation sets must name the same
    counters, or a crash/rebuild silently loses (or double-counts) a
    counter the invariant no longer covers."""
    expected = {"_retired": set(CONSERVED_WORKLOAD),
                "_retired_sched": set(CONSERVED_SCHED)}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        for tgt in node.targets:
            if not (isinstance(tgt, ast.Attribute)
                    and tgt.attr in expected):
                continue
            keys = _dict_keys_of(node.value)
            if keys is None:
                continue
            want = expected[tgt.attr]
            missing, extra = sorted(want - keys), sorted(keys - want)
            if missing or extra:
                detail = []
                if missing:
                    detail.append(f"missing {missing}")
                if extra:
                    detail.append(f"unregistered {extra}")
                yield (node.lineno,
                       f"self.{tgt.attr} keys drift from the "
                       f"obs.invariants conservation sets: "
                       f"{'; '.join(detail)}")


def _engine_path(path: str) -> bool:
    return path.endswith("serve/fleet/engine.py")


#: The check registry.  Order is the report order.
CHECKS: tuple[Check, ...] = (
    Check("det-builtin-hash",
          "no PYTHONHASHSEED-randomized hash() (PR 1: hash-seeded HAPT)",
          _everywhere, _check_builtin_hash),
    Check("det-wallclock",
          "no wall-clock reads in state/snapshot paths (PR 1/7)",
          _state_paths, _check_wallclock),
    Check("det-donate-argnums",
          "no donate_argnums (PR 8: 3x slower + 1 ulp fusion shift)",
          _everywhere, _check_donate_argnums),
    Check("det-jit-pallas",
          "no jax.jit around interpret-mode pallas_call (PR 8: "
          "batch-shape unstable)",
          _everywhere, _check_jit_pallas),
    Check("det-set-iteration",
          "no unordered set iteration in dispatch/stats paths (PR 5/7)",
          _ordered_paths, _check_set_iteration),
    Check("det-span-pairing",
          "open()/close() spans paired, phases literal (PR 7)",
          _span_paths, _check_span_pairing),
    Check("det-span-registry",
          "span phases drawn from repro.obs.phases.PHASES (PR 7)",
          _span_paths, _check_span_registry),
    Check("det-conserved-counters",
          "retired counters match obs.invariants conservation sets "
          "(PR 6/7)",
          _engine_path, _check_conserved_counters),
)

CHECK_IDS = tuple(c.name for c in CHECKS)


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def _suppressions_by_line(src: str) -> dict[int, tuple[set[str], str]]:
    """line number -> (suppressed check ids, reason).  A comment-only
    line's suppression shifts to the following line."""
    out: dict[int, tuple[set[str], str]] = {}
    lines = src.splitlines()
    for i, line in enumerate(lines, start=1):
        m = _SUPPRESS_RE.search(line)
        if not m:
            continue
        checks = {c.strip() for c in m.group(1).split(",") if c.strip()}
        reason = m.group(2).strip()
        target = i + 1 if line.lstrip().startswith("#") else i
        if target in out:
            prev_checks, prev_reason = out[target]
            checks |= prev_checks
            reason = reason or prev_reason
        out[target] = (checks, reason)
    return out


def lint_source(src: str, relpath: str
                ) -> tuple[list[Finding], list[Suppression]]:
    """Lint one file's source.  ``relpath`` is the path relative to the
    linted root (posix separators) — it drives check scoping."""
    tree = ast.parse(src, filename=relpath)
    suppress = _suppressions_by_line(src)
    findings: list[Finding] = []
    suppressions: list[Suppression] = []
    for check in CHECKS:
        if not check.scope(relpath):
            continue
        for line, message in check.run(tree, relpath):
            where = f"{relpath}:{line}"
            sup = suppress.get(line)
            if sup and check.name in sup[0]:
                suppressions.append(Suppression(
                    check=check.name, where=where, reason=sup[1]))
            else:
                findings.append(Finding(
                    check=check.name, where=where, message=message))
    return findings, suppressions


def default_root() -> Path:
    """The ``src/repro`` tree this module itself lives in."""
    return Path(__file__).resolve().parent.parent


def lint_tree(root: Path | str | None = None) -> dict[str, Any]:
    """Lint every ``*.py`` under ``root`` (default: the live
    ``src/repro``).  Returns the detlint block of the analysis report."""
    root = Path(root) if root is not None else default_root()
    findings: list[Finding] = []
    suppressions: list[Suppression] = []
    files = sorted(p for p in root.rglob("*.py"))
    for p in files:
        rel = p.relative_to(root).as_posix()
        f, s = lint_source(p.read_text(), rel)
        findings.extend(f)
        suppressions.extend(s)
    return {
        "root": root.name,
        "files": len(files),
        "checks": list(CHECK_IDS),
        "check_docs": {c.name: c.doc for c in CHECKS},
        "findings": [f.to_dict() for f in findings],
        "suppressions": [s.to_dict() for s in suppressions],
    }
