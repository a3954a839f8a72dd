"""Observability subsystem gate (repro.obs): tracer, metrics registry,
flight recorder, conservation invariant, and their engine integrations.

Contracts locked in here:

* **NullTracer is free** — zero allocations per hot-path call, so the
  default (untraced) serving path is untouched to the byte.
* **Histogram bucket edges** — the fixed log2 ladder is exact at edges
  (``searchsorted side="left"``: an observation equal to an edge lands
  in that edge's bucket).
* **Deterministic snapshots** — ``snapshot(deterministic=True)`` /
  ``FlightRecorder.dumps(deterministic=True)`` are byte-identical across
  identical runs (wall-clock fields stripped), including under the full
  phase x shard ``crash_matrix`` fault schedule.
* **Conservation invariant** — the shared production implementation
  (``obs.invariants``) both powers the fault-harness assertion and
  trips loudly in debug-mode ``FleetEngine.stats()``.
* **O(shards) stats** — ``FleetEngine.stats()`` never walks per-stream
  containers (regression test poisons them).
"""
import glob
import json
import os
import tempfile
import tracemalloc

import jax
import numpy as np
import pytest

from faultharness import make_streams, run_crash_schedule
from repro.core import fastgrnn as fg
from repro.core.quantization import QuantConfig, quantize_params
from repro.obs import (BUCKET_EDGES_US, NULL_OBS, NULL_TRACER, PHASES,
                       FlightRecorder, Histogram, MetricsRegistry,
                       Observability, Tracer, check_conservation,
                       merge_histogram_counts, validate_snapshot)
from repro.serve.fleet import FleetConfig, FleetEngine, crash_matrix
from repro.serve.streaming import StreamingConfig, StreamingEngine


@pytest.fixture(scope="module")
def qp():
    cfg = fg.FastGRNNConfig(rank_w=2, rank_u=8)
    return quantize_params(fg.init_params(cfg, jax.random.PRNGKey(0)),
                           QuantConfig())


@pytest.fixture(scope="module")
def input_dim(qp):
    return StreamingEngine(qp, StreamingConfig(max_slots=1)).kernel.input_dim


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

def test_tracer_records_spans_and_phase_stats():
    tr = Tracer(capacity=16)
    tr.set_tick(3)
    for _ in range(5):
        t0 = tr.open("phase.a", shard=1)
        tr.close(t0)
    t0 = tr.open("phase.b")
    tr.close(t0)
    st = tr.phase_stats()
    assert set(st) == {"phase.a", "phase.b"}
    assert st["phase.a"]["count"] == 5
    assert st["phase.b"]["count"] == 1
    for s in st.values():
        assert s["p50_us"] >= 0 and s["p99_us"] >= s["p50_us"] >= 0
    fl = tr.flight()
    assert len(fl) == 6
    assert fl[0]["phase"] == "phase.a" and fl[0]["shard"] == 1
    assert all(rec["tick"] == 3 for rec in fl)
    assert [rec["seq"] for rec in fl] == list(range(6))


def test_tracer_ring_wraps_without_growth():
    tr = Tracer(capacity=8)
    for i in range(50):
        tr.close(tr.open("p"))
    assert len(tr.flight()) == 8                      # bounded
    assert [r["seq"] for r in tr.flight()] == list(range(42, 50))
    assert tr.phase_stats()["p"]["count"] == 50       # monotonic total


def test_tracer_deterministic_flight_strips_wallclock():
    tr = Tracer(capacity=8)
    tr.close(tr.open("p", shard=2))
    det = tr.flight(deterministic=True)[0]
    assert set(det) == {"seq", "tick", "phase", "shard"}
    full = tr.flight()[0]
    assert "t0_us" in full and "dur_us" in full


def test_tracer_span_context_manager():
    tr = Tracer()
    with tr.span("ctx.phase", shard=4) as sp:
        pass
    assert sp.dur_ns > 0
    assert tr.flight()[-1]["phase"] == "ctx.phase"
    assert tr.flight()[-1]["shard"] == 4
    assert tr.totals_s()["ctx.phase"] > 0


def test_tracer_close_drops_unclosed_children_and_counts_quietly():
    tr = Tracer(capacity=16)
    outer = tr.open("phase.outer")
    tr.open("phase.left_open", shard=1)          # never closed
    tr.close(outer)
    again = tr.open("phase.outer")
    assert again == outer                        # the stack unwound
    tr.close(again)
    for _ in range(3):
        tr.close(tr.open_count("phase.quiet"))
    st = tr.phase_stats()
    assert st["phase.quiet"]["count"] == 3
    assert st["phase.outer"]["count"] == 2
    assert "phase.left_open" not in {r["phase"] for r in tr.flight()}
    assert [r["phase"] for r in tr.flight()] == ["phase.outer"] * 2


def _null_open_close(tr):
    t0 = tr.open("engine.tick", 3)
    tr.close(t0)


def _null_open_count(tr):
    t0 = tr.open_count("fleet.feed")
    tr.close(t0)


def _null_span_tick(tr):
    with tr.span("engine.tick", 3):
        tr.set_tick(7)


@pytest.mark.parametrize("call", [_null_open_close, _null_open_count,
                                  _null_span_tick],
                         ids=["open-close", "open_count", "span"])
def test_null_tracer_is_allocation_free(call):
    """The disabled path must not allocate: this is what keeps the
    bit-exact fast path untouched when obs is off.  One case per span
    form of the API."""
    tr = NULL_TRACER
    # warm up (interned small ints, method caches)
    for _ in range(10):
        call(tr)

    def burst(n):
        for _ in range(n):
            call(tr)

    def leaked_by(n):
        before, _ = tracemalloc.get_traced_memory()
        burst(n)
        after, _ = tracemalloc.get_traced_memory()
        return after - before

    tracemalloc.start()
    try:
        burst(100)                            # warm tracemalloc itself
        small, big = leaked_by(1000), leaked_by(10000)
    finally:
        tracemalloc.stop()
    # a constant few-bytes residue (interpreter internals) is tolerated;
    # what is forbidden is growth proportional to the number of calls
    assert big <= small + 64, (
        f"NullTracer allocates per call: {small}B/1k vs {big}B/10k calls")


# ---------------------------------------------------------------------------
# Metrics: histogram edges, registry, snapshots, exporters
# ---------------------------------------------------------------------------

def test_histogram_bucket_edges_exact():
    h = Histogram("t")
    # an observation exactly on an edge lands in that edge's bucket
    for k, edge in enumerate(BUCKET_EDGES_US):
        h2 = Histogram("e")
        h2.observe_us(edge)
        assert h2.counts[k] == 1, f"edge {edge} fell in bucket {np.argmax(h2.counts)}"
    # just above an edge -> next bucket; overflow -> +inf bucket
    h.observe_us(BUCKET_EDGES_US[0] + 0.5)
    assert h.counts[1] == 1
    h.observe_us(BUCKET_EDGES_US[-1] * 10)
    assert h.counts[-1] == 1
    # 0 lands in the first bucket
    h.observe_us(0.0)
    assert h.counts[0] == 1


def test_histogram_vectorized_matches_scalar():
    rng = np.random.default_rng(0)
    vals = rng.uniform(0, 4e6, 500)
    a, b = Histogram("a"), Histogram("b")
    for v in vals:
        a.observe_us(float(v))
    b.observe_many_us(vals)
    np.testing.assert_array_equal(a.counts, b.counts)
    assert a.count == b.count == 500
    assert abs(a.sum_us - b.sum_us) < 1e-6 * a.sum_us


def test_histogram_quantiles_bucket_resolution():
    h = Histogram("q")
    h.observe_many_us(np.full(99, 3.0))       # bucket edge 4
    h.observe_us(5e6)                         # overflow
    assert h.quantile_us(0.5) == 4.0
    assert h.quantile_us(0.99) == 4.0
    assert h.quantile_us(1.0) == float(BUCKET_EDGES_US[-1] * 2)


def test_registry_get_or_create_and_kind_clash():
    reg = MetricsRegistry()
    c = reg.counter("a.count")
    assert reg.counter("a.count") is c
    with pytest.raises(TypeError):
        reg.gauge("a.count")
    c.inc()
    c.inc(5)
    reg.gauge("a.g").set(2.5)
    reg.histogram("a.h").observe_us(100)
    assert "a.count" in reg and reg.names() == ["a.count", "a.g", "a.h"]


def test_snapshot_schema_validates_and_roundtrips():
    reg = MetricsRegistry()
    reg.counter("c").inc(3)
    reg.gauge("g").set(1.25)
    reg.histogram("h").observe_many_us(np.array([1.0, 100.0, 1e7]))
    snap = reg.snapshot()
    assert validate_snapshot(snap) == []
    assert validate_snapshot(json.loads(json.dumps(snap))) == []
    assert snap["counters"]["c"] == 3
    assert snap["histograms"]["h"]["count"] == 3
    # broken snapshots are rejected
    bad = json.loads(json.dumps(snap))
    bad["histograms"]["h"]["counts"][0] += 1
    assert any("counts sum" in e for e in validate_snapshot(bad))
    assert any("missing top-level" in e
               for e in validate_snapshot({"benchmark": "metrics_snapshot"}))


def test_deterministic_snapshot_bytes_stable_across_runs():
    def run():
        reg = MetricsRegistry()
        reg.counter("steps").inc(128)
        reg.histogram("warm", wallclock=False).observe_many_us(
            np.arange(1, 65, dtype=np.float64))
        reg.histogram("tick_us", wallclock=True).observe_us(
            float(np.random.default_rng().uniform(1, 1e5)))  # wall-clock noise
        reg.counter("missed", wallclock=True).inc(
            int(np.random.default_rng().integers(1, 100)))
        return reg.dumps(deterministic=True)
    a, b = run(), run()
    assert a == b
    snap = json.loads(a)
    assert "tick_us" not in snap["histograms"]      # wallclock dropped
    assert "missed" not in snap["counters"]
    assert "warm" in snap["histograms"]             # deterministic kept


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("fleet.ticks", "total ticks").inc(7)
    reg.gauge("fleet.occupancy").set(0.5)
    h = reg.histogram("fleet.tick_us")
    h.observe_us(3.0)
    h.observe_us(1e9)
    text = reg.prometheus()
    assert "# TYPE fleet_ticks counter\nfleet_ticks 7" in text
    assert "fleet_occupancy 0.5" in text
    assert 'fleet_tick_us_bucket{le="4"} 1' in text
    assert 'fleet_tick_us_bucket{le="+Inf"} 2' in text
    assert "fleet_tick_us_count 2" in text
    # cumulative buckets are monotone
    cums = [int(l.rsplit(" ", 1)[1]) for l in text.splitlines()
            if l.startswith("fleet_tick_us_bucket")]
    assert cums == sorted(cums)


def test_prometheus_exposition_conformance():
    """Exporter conformance beyond the happy path: metric names must
    match ``[a-zA-Z_:][a-zA-Z0-9_:]*``, HELP text must escape backslash
    and newline, and output ordering must be stable (sorted by source
    name) so scrapes diff cleanly."""
    import re
    reg = MetricsRegistry()
    reg.counter("50hz.deadline-miss", "misses @ 50Hz").inc(1)
    reg.gauge("numerics.drift.h", 'help with \\ backslash\nand newline')
    reg.counter("fleet.shard0.ticks", "plain").inc(2)
    reg.gauge("weird~name!", "").set(1.0)
    text = reg.prometheus()
    name_re = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*$")
    for line in text.splitlines():
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            name = line.split(" ", 3)[2]
        else:
            name = line.split("{", 1)[0].split(" ", 1)[0]
        assert name_re.match(name), f"bad metric name {name!r} in {line!r}"
    # leading digit gets a prefix instead of producing an invalid name
    assert "_50hz_deadline_miss 1" in text
    # HELP payload is single-line with escaped backslash / newline
    help_line = next(l for l in text.splitlines()
                     if l.startswith("# HELP numerics_drift_h"))
    assert help_line == \
        "# HELP numerics_drift_h help with \\\\ backslash\\nand newline"
    # stable ordering: families appear in sorted source-name order
    fams = [l.split(" ", 3)[2] for l in text.splitlines()
            if l.startswith("# TYPE ")]
    assert fams == [_prom_name_ref(n) for n in sorted(
        ("50hz.deadline-miss", "numerics.drift.h", "fleet.shard0.ticks",
         "weird~name!"))]
    # two identical registries render byte-identically
    reg2 = MetricsRegistry()
    reg2.counter("50hz.deadline-miss", "misses @ 50Hz").inc(1)
    reg2.gauge("numerics.drift.h", 'help with \\ backslash\nand newline')
    reg2.counter("fleet.shard0.ticks", "plain").inc(2)
    reg2.gauge("weird~name!", "").set(1.0)
    assert reg2.prometheus() == text


def _prom_name_ref(name: str) -> str:
    from repro.obs.metrics import _prom_name
    return _prom_name(name)


def test_merge_histogram_counts():
    a, b = Histogram("a"), Histogram("b")
    a.observe_many_us(np.array([1.0, 5.0]))
    b.observe_many_us(np.array([5.0, 1e9]))
    merged = merge_histogram_counts([a.counts, b.counts])
    assert sum(merged) == 4
    with pytest.raises(ValueError):
        merge_histogram_counts([[1, 2, 3]])


# ---------------------------------------------------------------------------
# Conservation invariant (shared test/production implementation)
# ---------------------------------------------------------------------------

def _toy_stats():
    shard = {"active": 1, "pending": 0, "completed": 2, "stream_steps": 10,
             "ring_spills": 0, "replay_suppressed": 0,
             "scheduler": {"admissions": 3, "recycles": 1, "spills": 0,
                           "completed": 2, "cancelled": 0, "evictions": 0,
                           "ticks": 5}}
    retired = {"completed": 1, "stream_steps": 4, "ring_spills": 0,
               "replay_suppressed": 0,
               "scheduler": {"admissions": 1, "recycles": 0, "spills": 0,
                             "completed": 1, "cancelled": 0, "evictions": 0,
                             "ticks": 2}}
    return {"active": 1, "pending": 0, "completed": 3, "stream_steps": 14,
            "ring_spills": 0, "replay_suppressed": 0,
            "scheduler": {"admissions": 4, "recycles": 1, "spills": 0,
                          "completed": 3, "cancelled": 0, "evictions": 0,
                          "ticks": 7},
            "per_shard": [shard], "retired": retired}


def test_check_conservation_passes_and_catches_drift():
    assert check_conservation(_toy_stats()) == []
    broken = _toy_stats()
    broken["completed"] += 1
    errs = check_conservation(broken)
    assert len(errs) == 1 and "completed" in errs[0]
    broken2 = _toy_stats()
    broken2["scheduler"]["ticks"] -= 1
    assert any("scheduler.ticks" in e for e in check_conservation(broken2))
    broken3 = _toy_stats()
    broken3["active"] += 1                        # gauge absorbed retired
    assert any("gauge" in e for e in check_conservation(broken3))


def test_debug_mode_stats_asserts_conservation(qp, input_dim,
                                               monkeypatch):
    """``debug=True`` routes every ``stats()`` roll-up through the shared
    conservation checker (guarding the accumulation-pass keys against
    refactoring drift); ``debug=False`` never pays for it."""
    import repro.serve.fleet.engine as fleet_mod
    checked = []
    monkeypatch.setattr(
        fleet_mod, "assert_conservation",
        lambda stats: checked.append(stats["completed"]))
    streams = make_streams(4, 40, input_dim)

    def run(debug):
        fleet = FleetEngine(qp, FleetConfig(
            shards=2, stream=StreamingConfig(max_slots=4)),
            obs=Observability(debug=debug))
        for sid, w in streams.items():
            fleet.attach(sid, w, total_steps=len(w))
        fleet.drain()
        return fleet.stats()

    st = run(debug=True)
    assert checked == [st["completed"] == 4 and 4]
    run(debug=False)
    assert len(checked) == 1                   # not called off the debug path
    # and the real checker passes on a genuine roll-up
    assert check_conservation(st) == []


# ---------------------------------------------------------------------------
# Engine integration: spans, metrics, deadline + warm-up accounting
# ---------------------------------------------------------------------------

def _profiled(fn):
    """``fn()`` under a profiler session: its result and the host planes'
    events as (name, start_ns, end_ns, thread line)."""
    from jax.profiler import ProfileData, ProfileOptions
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            out = fn()
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        events = [(e.name, e.start_ns, e.start_ns + e.duration_ns, ln.name)
                  for plane in ProfileData.from_file(path).planes
                  if plane.name.startswith("/host:")
                  for ln in plane.lines for e in ln.events]
    return out, events


@pytest.mark.parametrize("profiling", [False, True],
                         ids=["no-profiler", "profiler-on"])
def test_fleet_traced_run_bit_identical_to_untraced(qp, input_dim,
                                                    profiling):
    """Full instrumentation must not perturb a single output bit, also
    while a profiler session records the spans."""
    streams = make_streams(12, 150, input_dim, seed=3)

    def run(obs):
        fleet = FleetEngine(qp, FleetConfig(
            shards=2, stream=StreamingConfig(max_slots=8)), obs=obs)
        for sid, w in streams.items():
            fleet.attach(sid, w, total_steps=len(w))
        from faultharness import collect_log
        return collect_log(fleet.drain())

    traced = lambda: run(Observability.full(debug=True))
    got = _profiled(traced)[0] if profiling else traced()
    assert run(NULL_OBS) == got


def _resident_fleet(qp, obs=None):
    """Two shards on one device, resident h, 8-sample windows."""
    return FleetEngine(qp, FleetConfig(
        shards=2, placement="host", stream=StreamingConfig(
            max_slots=4, window=8, backend="jit")),
        obs=obs)


def test_fleet_spans_nest_in_the_profiler_trace(qp, input_dim):
    """Under a profiler session the fleet's spans are TraceMe events on
    the host plane, nested by interval as the code nests them."""
    fleet = _resident_fleet(qp, Observability(tracer=Tracer()))
    for sid, w in make_streams(8, 24, input_dim).items():
        fleet.attach(sid, w)
    for _ in range(9):                  # compile, admit, one emission
        fleet.step()

    def emitting_ticks():
        for _ in range(8):              # the next window ends in here
            fleet.step()

    _, events = _profiled(emitting_ticks)
    by = lambda name: [e for e in events if e[0] == name]
    inside = lambda a, b: b[1] <= a[1] and a[2] <= b[2] and a[3] == b[3]
    chain = ("engine.emit_wait", "engine.emit", "fleet.finish", "fleet.tick")
    waits = by(chain[0])
    assert waits, sorted({e[0] for e in events})
    for wait in waits:
        inner = wait
        for parent in chain[1:]:
            outers = [e for e in by(parent) if inside(inner, e)]
            assert len(outers) == 1, (inner, parent)
            inner = outers[0]


def test_null_tracer_writes_no_span_into_the_profiler_trace(qp, input_dim):
    fleet = _resident_fleet(qp)
    for sid, w in make_streams(8, 24, input_dim).items():
        fleet.attach(sid, w)
    fleet.step()
    _, events = _profiled(lambda: [fleet.step() for _ in range(10)])
    assert events                       # the session recorded something
    assert not {e[0] for e in events} & PHASES


def test_fleet_feed_counted_outside_the_flight_ring(qp, input_dim):
    """``fleet.feed`` counts every call in the phase statistics and
    stays out of the flight ring, whose deterministic dump is byte-stable
    across identical runs."""
    streams = make_streams(6, 40, input_dim, seed=5)

    def run():
        obs = Observability.full()
        fleet = FleetEngine(qp, FleetConfig(
            shards=2, stream=StreamingConfig(max_slots=8, window=16)),
            obs=obs)
        for sid in streams:
            fleet.attach(sid)
        feeds = 0
        for k in range(0, 40, 10):
            for sid, w in streams.items():
                fleet.feed(sid, w[k:k + 10])
                feeds += 1
            for _ in range(10):
                fleet.step()
        return obs, feeds

    (obs, feeds), (obs2, _) = run(), run()
    assert obs.tracer.phase_stats()["fleet.feed"]["count"] == feeds == 24
    flight = obs.tracer.flight(deterministic=True)
    assert "fleet.feed" not in {r["phase"] for r in flight}
    assert json.dumps(flight) == json.dumps(
        obs2.tracer.flight(deterministic=True))
    assert (obs.recorder.dumps(deterministic=True)
            == obs2.recorder.dumps(deterministic=True))


def test_resident_tick_books_its_crossings(qp, input_dim):
    """A steady resident tick copies x and the active mask to the device
    and pulls nothing; an emitting tick adds, once for the device group,
    the row indices and the window-reset mask up and the rows down."""
    fleet = _resident_fleet(qp)
    for sid, w in make_streams(8, 24, input_dim).items():
        fleet.attach(sid, w)
    fleet.step()                         # admission
    crossings = []
    for _ in range(7):                   # ticks 2-8: the window ends at 8
        before = fleet.stats()["transfers"]
        fleet.step()
        after = fleet.stats()["transfers"]
        crossings.append((after["h2d_count"] - before["h2d_count"],
                          after["d2h_count"] - before["d2h_count"]))
    assert crossings == [(2, 0)] * 6 + [(2 + 2, 1)]


def test_resident_tick_after_emission_adopts_the_fused_state(qp, input_dim,
                                                             monkeypatch):
    """The window reset's output is the group's fused state, so the tick
    after an emitting tick steps it as it is: no device concatenate."""
    fleet = _resident_fleet(qp)
    for sid, w in make_streams(8, 24, input_dim).items():
        fleet.attach(sid, w)
    g, = fleet._group_list
    concats = []
    real = g.kernel.concat_device
    monkeypatch.setattr(g.kernel, "concat_device",
                        lambda parts: concats.append(len(parts)) or real(parts))
    fleet.step()                         # admission: the one concatenate
    assert concats == [2]
    for tick in range(2, 18):            # windows end at ticks 8 and 16
        fleet.step()
        assert concats == [2], tick


def test_fleet_tick_phases_traced(qp, input_dim):
    obs = Observability.full()
    fleet = FleetEngine(qp, FleetConfig(
        shards=2, stream=StreamingConfig(max_slots=8)), obs=obs)
    for sid, w in make_streams(8, 140, input_dim).items():
        fleet.attach(sid, w, total_steps=len(w))
    fleet.drain()
    st = obs.tracer.phase_stats()
    # fused fleet ticks: the kernel dispatch is the fleet.dispatch span
    # (one fused call for all shards); engine.kernel appears only on the
    # single-engine/unfused path, asserted separately below
    for phase in ("fleet.tick", "fleet.begin", "fleet.dispatch",
                  "fleet.finish", "fleet.deliver", "engine.gather",
                  "engine.emit", "engine.finish", "sched.admit",
                  "sched.release"):
        assert phase in st, f"missing phase {phase}: have {sorted(st)}"
    # the tick envelope dominates its parts
    assert st["fleet.tick"]["total_us"] >= st["fleet.dispatch"]["total_us"]
    # engine.emit's four children tile it
    kids = ("engine.emit_pull", "engine.emit_wait", "engine.emit_head",
            "engine.emit_reset")
    assert all(st[k]["count"] == st["engine.emit"]["count"] for k in kids)
    assert sum(st[k]["total_us"] for k in kids) <= \
        st["engine.emit"]["total_us"]
    # spans are tagged with real shard indices
    shards = {r["shard"] for r in obs.tracer.flight()
              if r["phase"] == "engine.gather"}
    assert shards <= {0, 1} and shards
    # the fused resident fleet emits once per device group: its two
    # shards' emitting ticks open engine.emit and each child once
    obs = Observability.full()
    fleet = _resident_fleet(qp, obs)
    for sid, w in make_streams(8, 24, input_dim).items():
        fleet.attach(sid, w)
    for _ in range(17):                  # windows end at ticks 8 and 16
        fleet.step()
    st = obs.tracer.phase_stats()
    assert st["engine.finish"]["count"] == 2 * 17
    assert all(st[k]["count"] == 2 for k in ("engine.emit",) + kids)
    assert sum(st[k]["total_us"] for k in kids) <= \
        st["engine.emit"]["total_us"]


def test_single_engine_kernel_span_and_tick(qp, input_dim):
    obs = Observability.full()
    eng = StreamingEngine(qp, StreamingConfig(max_slots=4), obs=obs)
    for sid, w in make_streams(4, 140, input_dim).items():
        eng.attach(sid, w, total_steps=len(w))
    eng.drain()
    st = obs.tracer.phase_stats()
    for phase in ("engine.tick", "engine.kernel", "engine.gather",
                  "engine.finish", "sched.admit"):
        assert phase in st, f"missing phase {phase}: have {sorted(st)}"
    assert "engine.tick_us" in obs.metrics.snapshot()["histograms"]


def test_fleet_metrics_counters_and_warmup(qp, input_dim):
    obs = Observability.full()
    fleet = FleetEngine(qp, FleetConfig(
        shards=2, stream=StreamingConfig(max_slots=8, warmup_samples=64)),
        obs=obs)
    n, steps = 8, 140
    for sid, w in make_streams(n, steps, input_dim).items():
        fleet.attach(sid, w, total_steps=steps)
    fleet.drain()
    snap = obs.metrics.snapshot()
    assert validate_snapshot(snap) == []
    assert snap["counters"]["fleet.ticks"] == fleet.stats()["ticks"]
    # every stream crosses warm-up exactly once, at its first emission
    # (window=128 >= warmup=64), so the histogram has n observations of
    # 128 samples each
    wh = snap["histograms"]["stream.warmup_samples"]
    assert wh["count"] == n and wh["sum_us"] == n * 128
    assert snap["counters"]["stream.warm_emissions"] == n * 2  # window+final
    assert snap["counters"]["stream.cold_emissions"] == 0
    # occupancy gauges drained to zero
    assert snap["gauges"]["fleet.active"] == 0
    assert snap["gauges"]["fleet.occupancy"] == 0


def test_deadline_miss_accounting(qp, input_dim):
    # deadline_ms=0: every productive tick misses, counted in stream-ticks
    obs = Observability.full(deadline_ms=0.0)
    fleet = FleetEngine(qp, FleetConfig(
        shards=2, stream=StreamingConfig(max_slots=4)), obs=obs)
    for sid, w in make_streams(4, 50, input_dim).items():
        fleet.attach(sid, w, total_steps=50)
    fleet.drain()
    snap = obs.metrics.snapshot()
    st = fleet.stats()
    assert snap["counters"]["fleet.deadline_miss_ticks"] == st["ticks"]
    assert snap["counters"]["fleet.deadline_miss_stream_ticks"] == \
        st["stream_steps"]
    per_shard = sum(snap["counters"][f"fleet.shard{i}."
                                     "deadline_miss_stream_ticks"]
                    for i in range(2))
    assert per_shard == st["stream_steps"]
    # default deadline (50 Hz -> 20 ms) on the same tiny workload: ticks
    # run in far under 20 ms, so no misses
    obs2 = Observability.full()
    fleet2 = FleetEngine(qp, FleetConfig(
        shards=2, stream=StreamingConfig(max_slots=4)), obs=obs2)
    for sid, w in make_streams(4, 50, input_dim).items():
        fleet2.attach(sid, w, total_steps=50)
    fleet2.drain()
    assert obs2.metrics.snapshot()["counters"][
        "fleet.deadline_miss_ticks"] == 0


def test_warmup_histogram_survives_migration(qp, input_dim):
    obs = Observability.full()
    fleet = FleetEngine(qp, FleetConfig(
        shards=2, stream=StreamingConfig(max_slots=4, warmup_samples=64)),
        obs=obs)
    streams = make_streams(2, 150, input_dim)
    for sid, w in streams.items():
        fleet.attach(sid, w, total_steps=150)
    for _ in range(40):                       # pre-warm-up (< 64 steps)
        fleet.step()
    sid0 = next(iter(streams))
    fleet.migrate(sid0, (fleet.shard_of(sid0) + 1) % 2)
    fleet.drain()
    wh = obs.metrics.snapshot()["histograms"]["stream.warmup_samples"]
    assert wh["count"] == 2                   # once per stream, not re-counted
    assert wh["sum_us"] == 2 * 128


# ---------------------------------------------------------------------------
# Flight recorder + crash matrix byte-stability
# ---------------------------------------------------------------------------

def test_flight_recorder_truncates_event_tail():
    tr = Tracer(capacity=8)
    rec = FlightRecorder(tr, events_per_shard=4)
    rec.note_events(0, tick=1, summaries=[(f"s{i}", "window", i)
                                          for i in range(10)])
    rec.note_events(0, tick=2, summaries=[("x", "final", 99)], total=500)
    dump = rec.record_crash({"shard": 0, "phase": "pre_tick"}, tick=3)
    ev = dump["recent_events"]["0"]
    assert ev["total_events"] == 510          # true count, not tail length
    assert len(ev["tail"]) == 4               # bounded
    assert ev["tail"][-1] == {"tick": 2, "stream": "x", "kind": "final",
                              "step": 99}


def test_flight_recorder_dump_on_crash(qp, input_dim):
    obs = Observability.full()
    fleet = FleetEngine(qp, FleetConfig(
        shards=2, snapshot_every=16,
        stream=StreamingConfig(max_slots=8)), obs=obs)
    for sid, w in make_streams(8, 200, input_dim).items():
        fleet.attach(sid, w, total_steps=200)
    for _ in range(140):                      # past first window emission
        fleet.step()
    fleet.crash_shard(1)
    assert obs.recorder.n_crashes == 1
    d = obs.recorder.last()
    assert d["artifact"] == "flight_record" and d["shard"] == 1
    assert d["recovery"]["streams_recovered"] > 0
    assert d["counters"]["failovers"] == 1
    # the span tail captures the exact pre-crash tick phases, in order,
    # and nothing from after the crash tick
    phases_seen = {r["phase"] for r in d["trace"]}
    assert {"fleet.tick", "fleet.begin", "fleet.dispatch",
            "fleet.finish"} <= phases_seen
    assert all(r["tick"] <= d["tick"] for r in d["trace"])
    assert [r["seq"] for r in d["trace"]] == sorted(
        r["seq"] for r in d["trace"])
    assert any(ev["total_events"] > 0 for ev in d["recent_events"].values())
    fleet.drain()


@pytest.mark.parametrize("shards", [2, 4])
def test_crash_matrix_flight_dumps_byte_stable(qp, input_dim, shards):
    """Identical runs under the full phase x shard crash matrix produce
    byte-identical deterministic flight-recorder dumps."""
    streams = make_streams(12, 300, input_dim, seed=5)

    def run():
        obs = Observability.full()
        log, stats = run_crash_schedule(
            qp, streams, shards=shards, slots_per_shard=8,
            injector=crash_matrix(shards), obs=obs)
        return obs, log, stats

    obs_a, log_a, stats_a = run()
    obs_b, log_b, stats_b = run()
    assert obs_a.recorder.n_crashes == 3 * shards      # every phase x shard
    dump_a = obs_a.recorder.dumps(deterministic=True)
    assert dump_a == obs_b.recorder.dumps(deterministic=True)
    assert log_a == log_b
    # nondeterministic dumps still parse and carry wall-clock spans
    full = json.loads(obs_a.recorder.dumps())
    assert any("dur_us" in r for c in full["crashes"] for r in c["trace"])


# ---------------------------------------------------------------------------
# O(shards) stats regression
# ---------------------------------------------------------------------------

class _PoisonDict(dict):
    """Raises if anybody iterates it — the O(streams) tripwire."""

    def __iter__(self):
        raise AssertionError("stats() iterated a per-stream container")

    def keys(self):
        raise AssertionError("stats() iterated a per-stream container")

    def values(self):
        raise AssertionError("stats() iterated a per-stream container")

    def items(self):
        raise AssertionError("stats() iterated a per-stream container")


def test_fleet_stats_is_o_shards_not_o_streams(qp, input_dim):
    fleet = FleetEngine(qp, FleetConfig(
        shards=4, stream=StreamingConfig(max_slots=8)))
    for sid, w in make_streams(16, 60, input_dim).items():
        fleet.attach(sid, w, total_steps=60)
    for _ in range(10):
        fleet.step()
    # poison every stream-keyed container: owner map, replay cursors,
    # failover stores, per-shard session maps
    saved = (fleet._owner, fleet._cursor, fleet._snapshots, fleet._journal,
             [sh._sessions for sh in fleet.shards])
    fleet._owner = _PoisonDict(fleet._owner)
    fleet._cursor = _PoisonDict(fleet._cursor)
    fleet._snapshots = _PoisonDict(fleet._snapshots)
    fleet._journal = _PoisonDict(fleet._journal)
    for sh in fleet.shards:
        sh._sessions = _PoisonDict(sh._sessions)
    calls = {"n": 0}
    orig = type(fleet.shards[0]).stats

    def counting_stats(self):
        calls["n"] += 1
        return orig(self)

    try:
        type(fleet.shards[0]).stats = counting_stats
        st = fleet.stats()
    finally:
        type(fleet.shards[0]).stats = orig
        fleet._owner, fleet._cursor, fleet._snapshots, fleet._journal, \
            sessions = saved
        for sh, sess in zip(fleet.shards, sessions):
            sh._sessions = sess
    assert calls["n"] == 4                    # exactly one call per shard
    assert st["active"] == 16
    fleet.drain()


# ---------------------------------------------------------------------------
# LM engine spans
# ---------------------------------------------------------------------------

def test_lm_engine_obs_spans():
    import repro.configs as C
    from repro.models import transformer as T
    from repro.serve.engine import Engine, ServeConfig
    cfg = C.reduced(C.get("deepseek-7b"), compute_dtype="float32",
                    param_dtype="float32")
    params = T.init(cfg, jax.random.PRNGKey(0))
    obs = Observability.full()
    eng = Engine(cfg, params, ServeConfig(max_len=32, max_slots=2), obs=obs)
    rng = np.random.default_rng(0)
    for i in range(3):
        eng.submit(rng.integers(0, cfg.vocab_size, 4).astype(np.int32), 4)
    eng.run()
    st = obs.tracer.phase_stats()
    assert st["lm.prefill"]["count"] == 3
    assert st["lm.decode"]["count"] >= 3
    assert "lm.tick" in st and "sched.admit" in st
    snap = obs.metrics.snapshot()
    assert snap["counters"]["lm.tokens_generated"] == \
        eng.stats()["tokens_generated"] - 3   # prefill tokens not decode-counted


# ---------------------------------------------------------------------------
# span phase-name registry (repro.obs.phases)
# ---------------------------------------------------------------------------

def test_every_serving_span_phase_is_registered():
    """Every phase literal recorded through the tracer API anywhere in
    the serving and deploy trees must be registered in
    repro.obs.phases.PHASES — a typo'd phase would silently intern a new
    ring and split that phase's latency history (det-span-registry lints
    the same property; this pins it from the runtime side)."""
    import ast
    import os
    from repro.obs.phases import PHASES

    src_root = os.path.join(os.path.dirname(__file__), "..", "src", "repro")
    used = {}
    for sub in ("serve", "deploy"):
        for dirpath, _, files in os.walk(os.path.join(src_root, sub)):
            for fn in sorted(files):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                with open(path) as f:
                    tree = ast.parse(f.read(), filename=path)
                for node in ast.walk(tree):
                    if (isinstance(node, ast.Call)
                            and isinstance(node.func, ast.Attribute)
                            and node.func.attr in ("open", "open_count",
                                                   "span")
                            and node.args
                            and isinstance(node.args[0], ast.Constant)
                            and isinstance(node.args[0].value, str)):
                        used.setdefault(node.args[0].value, []).append(
                            f"{path}:{node.lineno}")
    unregistered = {p: w for p, w in used.items() if p not in PHASES}
    assert not unregistered, unregistered
    # sanity: the scan actually sees the serving spans (an empty `used`
    # would mean the extractor broke, not that the tree is clean)
    assert {"fleet.tick", "engine.kernel", "lm.prefill"} <= set(used)


def test_phase_registry_api():
    from repro.obs import PHASES, assert_registered, registered
    assert registered("fleet.dispatch") and not registered("fleet.dispach")
    assert_registered("engine.tick")
    with pytest.raises(ValueError):
        assert_registered("engine.tick_typo")
    # registry names are unique across subsystem groups and non-empty
    from repro.obs import phases as P
    groups = (P.ENGINE_PHASES + P.FLEET_PHASES + P.LM_PHASES
              + P.SCHED_PHASES + P.VERIFY_PHASES)
    assert len(groups) == len(set(groups)) == len(PHASES)
