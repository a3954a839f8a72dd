"""Tick-phase tracer: fixed-size span rings for the serving hot path.

The paper's headline is a *latency* claim — 9.21 ms/sample against a
20 ms 50 Hz tick budget — so the serving stack needs to answer "where
does a tick spend its time?" without perturbing the thing it measures.
This tracer is built around three constraints:

* **The phase is named where the span opens.**  A span is recorded with
  two calls — ``tok = tracer.open(phase, shard)`` before the work and
  ``tracer.close(tok)`` after — so the name is known while the work
  runs.  The token is the span's depth in a stack of open spans; closing
  a span also drops any span opened inside it and never closed.
* **No allocation on the hot path.**  Closed spans are written into
  preallocated rings (plain lists: a list store costs a fraction of a
  NumPy element store) through an integer cursor.  Phase names are
  interned to integer ids on first use; the steady state is one dict hit
  plus a handful of list stores.
* **Zero cost when disabled.**  :data:`NULL_TRACER` (the engines'
  default) implements the same surface as no-ops: ``open`` returns the
  cached small int ``0`` and ``close`` returns immediately, so the
  bit-exact fast path stays untouched (gated by the zero-allocation
  test in ``tests/test_obs.py`` and the <2 % overhead budget in
  ``benchmarks/obs_bench.py``).

Spans in the profiler trace: while a profiler session is active
(``jax.profiler.start_trace``), every span opened with :meth:`Tracer.open`
is also written into the profiler's trace as a
:class:`jax.profiler.TraceAnnotation` of the phase's name (with a
``shard`` stat when it has one), on the host plane beside the device's
ops and on the same clock.  :meth:`Tracer.open_count` is for per-stream
call sites (``fleet.feed``, thousands a tick): its spans reach the
phase's statistics only — neither the flight ring nor the profiler.

Two views of the recorded spans:

* **Per-phase duration rings** — ``phase_stats()`` folds the last
  ``capacity`` durations of every phase into count / total / p50 / p99 /
  max (the latency-breakdown surface ``BENCH_obs.json`` publishes).
* **The flight ring** — one chronological ring over the spans opened
  with ``open`` (sequence number, fleet tick, phase, shard, start,
  duration), in the order they closed.  ``flight()`` returns its tail:
  the exact pre-crash phase history the
  :class:`repro.obs.flight.FlightRecorder` dumps on ``crash_shard``.

Wall-clock fields (``t0_us`` / ``dur_us``) are intrinsically
nondeterministic; every exporter that promises byte-stable output
(``flight(deterministic=True)``, the metrics snapshot) strips them and
keeps the deterministic skeleton (seq, tick, phase, shard).
"""
from __future__ import annotations

import time
from typing import Any

import numpy as np
from jax.profiler import TraceAnnotation

_now = time.perf_counter_ns

#: The shard value of a span opened with ``open_count``: phase
#: statistics only, no flight-ring record and no profiler annotation.
_COUNT_ONLY = -2


class _NullSpan:
    """Reusable no-op context manager (one shared instance, no alloc)."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Disabled tracer: the engines' default.  Every method is a no-op
    cheap enough for the fused-tick hot path (no timestamps taken, no
    objects allocated)."""
    enabled = False
    __slots__ = ()

    def open(self, phase: str, shard: int = -1) -> int:
        return 0

    def open_count(self, phase: str) -> int:
        return 0

    def close(self, tok: int) -> int:
        return 0

    def set_tick(self, tick: int) -> None:
        pass

    def span(self, phase: str, shard: int = -1):
        return _NULL_SPAN

    def phase_stats(self) -> dict:
        return {}

    def flight(self, last: int | None = None,
               deterministic: bool = False) -> list:
        return []

    def totals_s(self) -> dict:
        return {}


NULL_TRACER = NullTracer()


class _Span:
    """Context-manager adapter over the ``open()``/``close()`` pair, for
    call sites that are not allocation-sensitive (harnesses,
    ``deploy.verify``).  Exposes the recorded duration as ``.dur_ns``
    after exit."""
    __slots__ = ("_tracer", "_phase", "_shard", "_tok", "dur_ns")

    def __init__(self, tracer: "Tracer", phase: str, shard: int):
        self._tracer = tracer
        self._phase = phase
        self._shard = shard
        self.dur_ns = 0

    def __enter__(self):
        self._tok = self._tracer.open(self._phase, self._shard)
        return self

    def __exit__(self, *exc):
        self.dur_ns = self._tracer.close(self._tok)
        return False


class Tracer:
    """Span recorder with fixed-size rings (see module docstring).

    ``capacity`` bounds both the chronological flight ring and each
    phase's duration ring; recording wraps, it never grows."""
    enabled = True

    def __init__(self, capacity: int = 4096):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self._epoch = _now()
        self._tick = 0
        # phase interning
        self._phase_ids: dict[str, int] = {}
        self._phase_names: list[str] = []
        # per-phase duration rings + monotonic totals
        self._durs: list[list[int]] = []
        self._cursors: list[int] = []
        self._counts: list[int] = []
        self._total_ns: list[int] = []
        # the stack of open spans, indexed by token (= depth)
        self._depth = 0
        self._o_pid: list[int] = []
        self._o_shard: list[int] = []
        self._o_t0: list[int] = []
        self._o_note: list[TraceAnnotation | None] = []
        # chronological flight ring
        self._seq = 0
        self._fl_seq = [-1] * capacity
        self._fl_tick = [0] * capacity
        self._fl_phase = [-1] * capacity
        self._fl_shard = [-1] * capacity
        self._fl_t0 = [0] * capacity      # ns since epoch
        self._fl_dur = [0] * capacity     # ns

    # ------------------------------------------------------------------
    # Hot-path surface
    # ------------------------------------------------------------------
    def set_tick(self, tick: int) -> None:
        """Tag subsequent spans with the current fleet tick (flight-ring
        context; called once per tick, not per span)."""
        self._tick = tick

    def open(self, phase: str, shard: int = -1) -> int:
        """Open a span of ``phase``; returns the token :meth:`close`
        takes.  While a profiler session is active the span is also
        annotated in the profiler's trace."""
        tok = self._push(phase, shard)
        self._o_note[tok] = (
            None if not TraceAnnotation.is_enabled()
            else TraceAnnotation(phase, shard=shard) if shard >= 0
            else TraceAnnotation(phase))
        self._o_t0[tok] = _now()
        return tok

    def open_count(self, phase: str) -> int:
        """Open a span that reaches ``phase``'s statistics only (count,
        total, duration ring): for call sites that run per stream, whose
        spans would flood the flight ring and the profiler's trace."""
        tok = self._push(phase, _COUNT_ONLY)
        self._o_note[tok] = None
        self._o_t0[tok] = _now()
        return tok

    def close(self, tok: int) -> int:
        """Close the span opened as ``tok`` (and drop any span opened
        inside it and left open).  Returns the span's duration in ns
        (callers layer deadline accounting on top without a second clock
        read)."""
        t1 = _now()
        self._depth = tok
        note = self._o_note[tok]
        if note is not None:
            note.__exit__(None, None, None)
        t0 = self._o_t0[tok]
        dur = t1 - t0
        pid = self._o_pid[tok]
        # per-phase duration ring
        cur = self._cursors[pid]
        self._durs[pid][cur] = dur
        self._cursors[pid] = (cur + 1) % self.capacity
        self._counts[pid] += 1
        self._total_ns[pid] += dur
        shard = self._o_shard[tok]
        if shard == _COUNT_ONLY:
            return dur
        # chronological flight ring
        i = self._seq % self.capacity
        self._fl_seq[i] = self._seq
        self._fl_tick[i] = self._tick
        self._fl_phase[i] = pid
        self._fl_shard[i] = shard
        self._fl_t0[i] = t0 - self._epoch
        self._fl_dur[i] = dur
        self._seq += 1
        return dur

    def span(self, phase: str, shard: int = -1) -> _Span:
        """Context-manager convenience for cold call sites."""
        return _Span(self, phase, shard)

    def _push(self, phase: str, shard: int) -> int:
        tok = self._depth
        self._depth = tok + 1
        if tok == len(self._o_pid):      # deepest nesting so far
            self._o_pid.append(0)
            self._o_shard.append(0)
            self._o_t0.append(0)
            self._o_note.append(None)
        pid = self._phase_ids.get(phase)
        if pid is None:
            pid = self._intern(phase)
        self._o_pid[tok] = pid
        self._o_shard[tok] = shard
        return tok

    # ------------------------------------------------------------------
    # Views
    # ------------------------------------------------------------------
    def phase_stats(self) -> dict[str, dict[str, Any]]:
        """Per-phase latency breakdown over each phase's retained ring:
        ``{phase: {count, total_us, p50_us, p99_us, max_us}}`` (count and
        total are monotonic over the tracer's whole lifetime; the
        percentiles cover the last ``capacity`` spans).  Phases sort by
        name so the snapshot is structurally deterministic."""
        out: dict[str, dict[str, Any]] = {}
        for name in sorted(self._phase_ids):
            pid = self._phase_ids[name]
            if not self._counts[pid]:        # opened, never closed
                continue
            n = min(self._counts[pid], self.capacity)
            us = np.asarray(self._durs[pid][:n], np.int64) / 1e3
            out[name] = {
                "count": int(self._counts[pid]),
                "total_us": round(self._total_ns[pid] / 1e3, 3),
                "p50_us": round(float(np.percentile(us, 50)), 3),
                "p99_us": round(float(np.percentile(us, 99)), 3),
                "max_us": round(float(us.max()), 3),
            }
        return out

    def totals_s(self) -> dict[str, float]:
        """Total recorded seconds per phase (the ``deploy.verify`` timing
        surface: one span per protocol section, summed)."""
        return {name: self._total_ns[pid] / 1e9
                for name, pid in sorted(self._phase_ids.items())
                if self._counts[pid]}

    def flight(self, last: int | None = None,
               deterministic: bool = False) -> list[dict[str, Any]]:
        """Chronological tail of the flight ring (oldest first), each
        span as a dict.  ``deterministic=True`` strips the wall-clock
        fields (``t0_us`` / ``dur_us``) so two identical runs produce
        byte-identical dumps — the flight-recorder stability contract."""
        n = min(self._seq, self.capacity)
        if last is not None:
            n = min(n, last)
        out = []
        for k in range(self._seq - n, self._seq):
            i = k % self.capacity
            rec: dict[str, Any] = {
                "seq": self._fl_seq[i],
                "tick": int(self._fl_tick[i]),
                "phase": self._phase_names[self._fl_phase[i]],
                "shard": int(self._fl_shard[i]),
            }
            if not deterministic:
                rec["t0_us"] = round(self._fl_t0[i] / 1e3, 3)
                rec["dur_us"] = round(self._fl_dur[i] / 1e3, 3)
            out.append(rec)
        return out

    # ------------------------------------------------------------------
    def _intern(self, phase: str) -> int:
        pid = len(self._phase_names)
        self._phase_ids[phase] = pid
        self._phase_names.append(phase)
        self._durs.append([0] * self.capacity)
        self._cursors.append(0)
        self._counts.append(0)
        self._total_ns.append(0)
        return pid
