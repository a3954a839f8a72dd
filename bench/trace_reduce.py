"""Reduce a profiler trace (``.xplane.pb``) to device busy time, per-op
device time and idle gaps named by what the host was doing.

* Device planes are those named ``/device:<accelerator>:<n>``; their
  ``XLA Ops`` line holds one event per operation run on the device.
* The benchmark's own host spans (``jax.profiler.TraceAnnotation``, names
  starting ``bench.``) lie on the host plane on the same clock.  The
  ``bench.window`` span bounds the measured window; events are clipped to it.
* Busy time is the union of a device's op intervals in the window, averaged
  over the devices.  An idle gap is a stretch of the window with no op on
  a device; it is named by the innermost ``bench.`` span (other than the
  window) that covers its midpoint, or ``none``.
"""
from __future__ import annotations

import bisect
import glob
import os

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"


def find_xplane(log_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def _is_device(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith("/device:CPU")


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


def reduce(path: str, *, top: int = 10) -> dict:
    """``{"devices", "window_s", "busy_s", "ops": {name: s}, "gaps": {name: s},
    "op_text": {name: str}, "top_ops", "top_gaps"}`` of the trace at
    ``path``; ``op_text`` holds the string stats of an op's first event
    (its long name, its HLO category), for readers that match an op by
    more than its short name.  Device fields are 0 or empty when the trace
    holds no device plane (a CPU run)."""
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, top=top)


def reduce_planes(planes, *, top: int = 10) -> dict:
    """:func:`reduce` of planes as ``ProfileData`` gives them: each with a
    ``name`` and ``lines``, each line with a ``name`` and ``events``, each
    event with a ``name``, ``start_ns`` and ``duration_ns``."""
    spans, device_lines = [], []
    for plane in planes:
        if _is_device(plane.name):
            device_lines += [ln for ln in plane.lines if ln.name == OPS_LINE]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [e for e in _events(ln) if e[0].startswith("bench.")]
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if windows:
        w0, w1 = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        ends = [e for ln in device_lines for e in _events(ln)]
        w0 = min((s for _, s, _ in ends), default=0.0)
        w1 = max((e for _, _, e in ends), default=0.0)
    inner = sorted(((s, e, n) for n, s, e in spans if n != WINDOW_SPAN),
                   key=lambda t: (t[0], -t[1]))
    ops: dict = {}
    gaps: dict = {}
    op_text: dict = {}
    busy = 0.0
    for ln in device_lines:
        ivs = []
        for ev in ln.events:
            if ev.name not in op_text:
                op_text[ev.name] = " ".join(
                    str(v) for _, v in getattr(ev, "stats", ()) if isinstance(v, str))
        for name, s, e in _events(ln):
            s, e = max(s, w0), min(e, w1)
            if e > s:
                ops[name] = ops.get(name, 0.0) + (e - s) / 1e9
                ivs.append((s, e))
        merged = _union(ivs)
        busy += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                label = _label(inner, (gs + ge) / 2)
                gaps[label] = gaps.get(label, 0.0) + (ge - gs) / 1e9
    n = len(device_lines)
    by_time = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"devices": n, "window_s": (w1 - w0) / 1e9,
            "busy_s": busy / n / 1e9 if n else 0.0,
            "ops": ops, "gaps": gaps, "op_text": op_text,
            "top_ops": by_time(ops), "top_gaps": by_time(gaps)}


def _label(spans: list, t: float) -> str:
    """The latest-starting span that covers time t (the benchmark's spans
    other than the window follow one another, so that is the innermost)."""
    i = bisect.bisect_right(spans, (t, float("inf"), "")) - 1
    while i >= 0:
        s, e, name = spans[i]
        if e >= t:
            return name
        if i and spans[i - 1][1] < s:
            break            # earlier spans ended before this one began
        i -= 1
    return "none"
