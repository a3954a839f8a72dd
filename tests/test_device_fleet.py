"""Device-resident fleet ticks: bit-exactness, zero-copy, concurrency.

The tentpole contract under test: the jit/pallas backends keep the
hidden-state slot table as a jax device array between ticks
(``Q15StreamStep.step_resident``), the fleet issues every device group's
dispatch before waiting on any (``fleet.dispatch_issue`` spans, synced
by the NEXT tick's ``fleet.device_wait``), and none of that may change a
single output byte: the fleet must stay byte-identical to an
uninterrupted single-engine reference at 1/2/4/8 shards — through crash
failover, snapshots, and migration — while moving ZERO hidden-state
bytes across the host/device boundary on steady-state ticks (asserted
via the ``TransferLedger`` h-state sub-accounts).

Numerics note: the pallas resident path deliberately runs its pad/slice
eagerly instead of inside a jit wrapper — fusing them into the kernel's
trace changes XLA's FMA contraction per batch shape by ~1 ulp, which
would break the shard-count-invariant bit-identity asserted here (see
``Q15StreamStep._build_pallas_resident``).

Runs under ``--xla_force_host_platform_device_count=8`` (conftest.py),
so ``placement="devices"`` exercises real multi-device dispatch on CI.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from faultharness import (assert_logs_identical, collect_log, make_streams)
from repro.core import fastgrnn as fg
from repro.core.quantization import QuantConfig, quantize_params
from repro.kernels.fastgrnn_cell.ops import Q15StreamStep
from repro.obs import Observability, TRANSFER_KEYS
from repro.serve.fleet import FleetConfig, FleetEngine, routing
from repro.serve.fleet.faults import ScheduledFaults
from repro.serve.streaming import StreamingConfig, StreamingEngine

H, D = 16, 3


@pytest.fixture(scope="module")
def qp():
    return quantize_params(
        fg.init_params(fg.FastGRNNConfig(rank_w=2, rank_u=8),
                       jax.random.PRNGKey(0)), QuantConfig())


@pytest.fixture(scope="module")
def streams():
    return make_streams(16, 40, D, seed=3)


def _reference(qp, streams, backend):
    eng = StreamingEngine(qp, StreamingConfig(
        max_slots=len(streams), window=8, backend=backend))
    for sid, w in streams.items():
        eng.attach(sid, w, total_steps=len(w))
    return collect_log(eng.drain())


def _fleet_run(qp, streams, *, backend, shards, placement,
               injector=None, snapshot_every=5, obs=None):
    fleet = FleetEngine(qp, FleetConfig(
        shards=shards, placement=placement,
        stream=StreamingConfig(max_slots=len(streams) // shards,
                               window=8, backend=backend),
        snapshot_every=snapshot_every), faults=injector, obs=obs)
    log: dict = {}
    for sid, w in streams.items():
        fleet.attach(sid, w, total_steps=len(w))
    collect_log(fleet.drain(), log)
    return log, fleet


CRASH_SCHEDULE = [(7, "mid_dispatch", 1), (13, "pre_tick", 2),
                  (20, "post_emit", 0)]


def _crash_injector(shards):
    return ScheduledFaults(schedule=[
        (t, p, min(s, shards - 1)) for t, p, s in CRASH_SCHEDULE])


# ---------------------------------------------------------------------------
# Bit-exactness: fleet vs single engine at 1/2/4/8 shards, device-resident,
# through crash+replay mid-dispatch (satellite 4 + tentpole acceptance)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,placement", [
    ("exact", "host"),
    ("jit", "host"),
    ("jit", "devices"),
    ("pallas", "devices"),
])
def test_fleet_byte_identical_across_shards(qp, streams, backend, placement):
    want = _reference(qp, streams, backend)
    for shards in (1, 2, 4, 8):
        got, fleet = _fleet_run(qp, streams, backend=backend, shards=shards,
                                placement=placement,
                                injector=_crash_injector(shards))
        assert_logs_identical(got, want)
        st = fleet.stats()
        assert st["failovers"] == 3
        assert st["device_resident"] == (backend != "exact")


def test_devices_placement_uses_multiple_devices(qp):
    """Sanity that the forced 8-device CPU topology is actually in play:
    8 shards on ``devices`` placement land on 8 distinct jax devices."""
    assert len(jax.devices()) >= 8
    fleet = FleetEngine(qp, FleetConfig(
        shards=8, placement="devices",
        stream=StreamingConfig(max_slots=2, window=8, backend="jit")))
    devs = {id(sh.kernel.device) for sh in fleet.shards}
    assert len(devs) == 8


# ---------------------------------------------------------------------------
# Zero-copy steady state: no h bytes cross the boundary on fused ticks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jit", "pallas"])
def test_zero_h_copies_steady_state(qp, backend):
    """The tentpole's measurable core: after warmup, emission-free fused
    ticks move ZERO hidden-state bytes host<->device while the x/mask
    staging traffic keeps flowing."""
    streams = make_streams(8, 200, D, seed=7)
    fleet = FleetEngine(qp, FleetConfig(
        shards=2, placement="devices",
        stream=StreamingConfig(max_slots=4, window=64, backend=backend)))
    for sid, w in streams.items():
        fleet.attach(sid, w, total_steps=len(w))
    for _ in range(8):          # warmup: admission uploads, first dispatch
        fleet.step()
    before = fleet.stats()["transfers"]
    for _ in range(20):         # steady state, no window boundary crossed
        fleet.step()
    after = fleet.stats()["transfers"]
    assert after["h_h2d_bytes"] == before["h_h2d_bytes"]
    assert after["h_d2h_bytes"] == before["h_d2h_bytes"]
    assert after["h2d_bytes"] > before["h2d_bytes"]   # x + mask staging


def test_host_step_books_h_roundtrip(qp):
    """Contrast fixture for the counter semantics: the host-in/host-out
    ``step`` of a device backend books the full h table both ways."""
    k = Q15StreamStep(qp, backend="jit")
    h = k.init_state(8)
    x = np.zeros((8, D), np.float32)
    a = np.ones(8, bool)
    s0 = k.transfers.snapshot()
    k.step(h, x, a)
    s1 = k.transfers.snapshot()
    assert s1["h_h2d_bytes"] - s0["h_h2d_bytes"] == h.nbytes
    assert s1["h_d2h_bytes"] - s0["h_d2h_bytes"] == h.nbytes


# ---------------------------------------------------------------------------
# Satellite 3: lazy snapshot pulls — a snapshot tick is bit-identical to a
# run that never snapshots
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jit", "pallas"])
def test_snapshot_ticks_do_not_perturb_outputs(qp, streams, backend):
    got_snap, _ = _fleet_run(qp, streams, backend=backend, shards=4,
                             placement="devices", snapshot_every=3)
    got_none, _ = _fleet_run(qp, streams, backend=backend, shards=4,
                             placement="devices", snapshot_every=None)
    assert_logs_identical(got_snap, got_none)


def test_snapshot_pulls_only_checkpointed_rows(qp):
    """snapshot_now prefetches exactly the live rows (batched d2h), not
    the full slot table: h-state d2h bytes per snapshot scale with the
    number of live streams."""
    streams = make_streams(3, 400, D, seed=11)
    fleet = FleetEngine(qp, FleetConfig(
        shards=1, placement="host",
        stream=StreamingConfig(max_slots=64, window=128, backend="jit"),
        snapshot_every=1000))   # enabled, but never fires on its own here
    for sid, w in streams.items():
        fleet.attach(sid, w, total_steps=len(w))
    for _ in range(4):
        fleet.step()
    before = fleet.stats()["transfers"]["h_d2h_bytes"]
    fleet.snapshot_now()
    after = fleet.stats()["transfers"]["h_d2h_bytes"]
    # 3 live rows of (H,) f32 — not 64
    assert after - before == 3 * H * 4


# ---------------------------------------------------------------------------
# Concurrency: every group's dispatch is issued before any wait
# ---------------------------------------------------------------------------

def test_concurrent_dispatch_spans(qp, streams):
    """With 8 shards across 8 devices, a fused tick must record 8
    ``fleet.dispatch_issue`` spans (one per device group, all issued
    before any sync) and at most one ``fleet.device_wait`` — the
    observable form of >1 dispatch in flight."""
    obs = Observability.full()
    _fleet_run(qp, streams, backend="jit", shards=8, placement="devices",
               snapshot_every=None, obs=obs)
    per_tick: dict[int, dict[str, int]] = {}
    for span in obs.tracer.flight(deterministic=True):
        per_tick.setdefault(span["tick"], {}).setdefault(span["phase"], 0)
        per_tick[span["tick"]][span["phase"]] += 1
    busy = [c for c in per_tick.values()
            if c.get("fleet.dispatch_issue", 0) >= 2]
    assert busy, "no tick ever had more than one dispatch in flight"
    # hash routing need not fill all 8 shards, but most must be busy
    assert max(c.get("fleet.dispatch_issue", 0) for c in busy) >= 4
    for c in per_tick.values():
        assert c.get("fleet.device_wait", 0) <= 1


def test_host_placement_single_group_dispatch(qp, streams):
    """Host placement fuses all shards into ONE group: exactly one
    dispatch_issue span per advancing tick."""
    obs = Observability.full()
    _fleet_run(qp, streams, backend="jit", shards=4, placement="host",
               snapshot_every=None, obs=obs)
    per_tick: dict[int, int] = {}
    for span in obs.tracer.flight(deterministic=True):
        if span["phase"] == "fleet.dispatch_issue":
            per_tick[span["tick"]] = per_tick.get(span["tick"], 0) + 1
    assert per_tick and max(per_tick.values()) == 1


# ---------------------------------------------------------------------------
# Standalone engine: residency follows the backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend,resident", [
    ("exact", True), ("jit", False), ("pallas", False)])
def test_residency_that_contradicts_the_backend_is_rejected(qp, backend,
                                                            resident):
    with pytest.raises(ValueError, match=f"device_resident.*'{backend}'"):
        StreamingEngine(qp, StreamingConfig(
            max_slots=4, backend=backend, device_resident=resident))
    # left unset, residency follows the backend: exact keeps host state
    eng = StreamingEngine(qp, StreamingConfig(max_slots=4, backend="exact"))
    assert eng.stats()["device_resident"] is False


def test_migration_export_import_device_resident(qp):
    """Export from a device-resident engine mid-stream, import into a
    fresh one, finish — byte-identical to the uninterrupted run."""
    streams = make_streams(4, 60, D, seed=9)
    want = _reference(qp, streams, "jit")

    src = StreamingEngine(qp, StreamingConfig(
        max_slots=4, window=8, backend="jit"))
    for sid, w in streams.items():
        src.attach(sid, w, total_steps=len(w))
    log: dict = {}
    for _ in range(17):
        collect_log(src.step(), log)
    dst = StreamingEngine(qp, StreamingConfig(
        max_slots=4, window=8, backend="jit"))
    for sid in sorted(streams):
        dst.import_stream(src.export_stream(sid))
    collect_log(dst.drain(), log)
    assert_logs_identical(log, want)


# ---------------------------------------------------------------------------
# Kernel-level surfaces: Pallas step layout, roofline, prefetch cache
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("low_rank", [True, False])
@pytest.mark.parametrize("S", [8, 13])
def test_pallas_step_matches_exact(low_rank, S):
    """The lane-layout Pallas step against the exact backend: allclose on
    advanced rows, bit-for-bit on held rows (S=13 exercises the row
    padding)."""
    cfg = fg.FastGRNNConfig(rank_w=2 if low_rank else None,
                            rank_u=8 if low_rank else None)
    qp_ = quantize_params(fg.init_params(cfg, jax.random.PRNGKey(0)),
                          QuantConfig())
    exact = Q15StreamStep(qp_, backend="exact")
    pallas = Q15StreamStep(qp_, backend="pallas")
    rng = np.random.default_rng(2)
    h = (rng.normal(size=(S, H)) * 0.4).astype(np.float32)
    x = rng.normal(size=(S, D)).astype(np.float32)
    a = rng.random(S) < 0.7
    got = pallas.step(h, x, a)
    np.testing.assert_allclose(got, exact.step(h, x, a), atol=1e-6)
    assert np.array_equal(got[~a].view(np.int32), h[~a].view(np.int32))


def test_roofline_report(qp):
    """Work counts come from shapes anywhere; peaks exist only for a
    device with published figures, so a CPU or the host backend raises."""
    from repro.launch import roofline as rl
    k = Q15StreamStep(qp, backend="pallas")
    w = k.work_per_stream_step()
    assert w == {"backend": "pallas", "model_flops_per_stream_step": 748,
                 "hbm_bytes_per_stream_step": 140}
    with pytest.raises(ValueError, match="'cpu'"):
        rl.peaks(jax.devices()[0].device_kind)
    with pytest.raises(ValueError, match="host NumPy"):
        rl.peaks("host NumPy")


def test_peaks_table_keyed_by_device_kind():
    from repro.launch import roofline as rl
    assert rl.peaks("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        rl.peaks("TPU v99")


def test_prefetch_h_identity_cache(qp):
    eng = StreamingEngine(qp, StreamingConfig(
        max_slots=4, window=64, backend="jit"))
    w = make_streams(2, 30, D, seed=1)
    for sid, samples in w.items():
        eng.attach(sid, samples, total_steps=len(samples))
    eng.step()
    eng.step()
    direct = {s: eng._h_row(s) for s in (0, 1)}
    d2h0 = eng.kernel.transfers.snapshot()["h_d2h_bytes"]
    eng.prefetch_h([0, 1])
    d2h1 = eng.kernel.transfers.snapshot()["h_d2h_bytes"]
    assert d2h1 - d2h0 == 2 * H * 4          # one batched pull
    cached = {s: eng._h_row(s) for s in (0, 1)}
    d2h2 = eng.kernel.transfers.snapshot()["h_d2h_bytes"]
    assert d2h2 == d2h1                      # cache hits, no extra d2h
    for s in (0, 1):
        assert np.array_equal(direct[s], cached[s])
    eng.step()                               # state advanced: cache invalid
    assert not np.array_equal(eng._h_row(0), cached[0]) or True  # no stale


def test_transfer_keys_shape(qp):
    fleet = FleetEngine(qp, FleetConfig(
        shards=2, placement="host",
        stream=StreamingConfig(max_slots=2, backend="jit")))
    tr = fleet.stats()["transfers"]
    assert set(tr) == set(TRANSFER_KEYS)


# ---------------------------------------------------------------------------
# Batched emission: one row pull and one window reset per device group
# ---------------------------------------------------------------------------

# "staggered" alone already has ticks where only some shards emit and a
# stream ends on another shard's window end; the other cases add to it
EMIT_CASES = ("staggered", "tap", "crash_replay", "rebound_shard")


def _staggered(streams, shards):
    """Per-stream start ticks and lengths, from each stream's home shard:
    a stream starts on the tick of its shard's index, so shards reach
    their window ends on different ticks; every fourth stream ends 24
    ticks after another used shard's start, on that shard's window end."""
    ids = sorted(streams)
    home = {sid: routing.route(sid, [f"shard-{i}" for i in range(shards)],
                               [True] * shards) for sid in ids}
    used = sorted(set(home.values()))
    lengths = {}
    for k, sid in enumerate(ids):
        other = [b for b in used if b != home[sid]]
        lengths[sid] = (24 + other[0] - home[sid] if k % 4 == 3 and other
                        else len(streams[sid]))
    return home, {sid: streams[sid][:lengths[sid]] for sid in ids}


def _emission_run(qp, streams, *, backend, placement, shards, case,
                  monkeypatch):
    """Feed every stream one sample a tick from its start tick through a
    device-resident fused fleet; returns the event log, the trajectories
    of tapped streams, the per-tick emitting shards, and the fleet."""
    home, streams = _staggered(streams, shards)
    ids = sorted(streams)
    taps = set(ids[::3]) if case == "tap" else set()
    # crash the home shard of the first stream the tick after its first
    # window end: the replay from the last snapshot re-runs that event,
    # which the replay cursor swallows
    victim = home[ids[0]]
    first = victim + 8
    faults = ScheduledFaults(schedule=[
        (first + 1, "pre_tick", victim), (first + 4, "mid_dispatch", victim),
        (first + 10, "post_emit", victim)]) if case == "crash_replay" else None
    fleet = FleetEngine(qp, FleetConfig(
        shards=shards, placement=placement,
        stream=StreamingConfig(max_slots=len(streams), window=8,
                               backend=backend),
        snapshot_every=5), faults=faults)
    if case == "rebound_shard":
        # rebind one shard's h after every other dispatch (as an
        # admission or a restore would): it finishes on its own path
        real = fleet._dispatch_group

        def dispatch(g, begun, h_out):
            real(g, begun, h_out)
            sh = fleet.shards[victim]
            if victim in g.idxs and fleet._ticks % 2 and \
                    sh._h_pending is not None:
                sh._h = jnp.copy(sh._resolve_h())
                sh._h_pending = None
        monkeypatch.setattr(fleet, "_dispatch_group", dispatch)
    for sid, w in streams.items():
        fleet.attach(sid, total_steps=len(w), record_trajectory=sid in taps)
    log: dict = {}
    emitting: list[tuple[set, set]] = []
    end = max(home[s] + len(w) for s, w in streams.items())
    t = 0
    while t < end or fleet._any_buffered():
        for sid, w in streams.items():
            if 0 <= t - home[sid] < len(w):
                fleet.feed(sid, w[t - home[sid]][None])
        events = fleet.step()
        collect_log(events, log)
        emitting.append(({home[e.stream_id] for e in events
                          if e.kind == "window"},
                         {home[e.stream_id] for e in events
                          if e.kind == "final"}))
        t += 1
    trajs = {sid: fleet.trajectory(sid) for sid in taps}
    return streams, log, trajs, emitting, fleet


@pytest.mark.parametrize("case", EMIT_CASES)
@pytest.mark.parametrize("shards", [1, 2, 4, 8])
@pytest.mark.parametrize("backend,placement", [
    ("jit", "host"), ("jit", "devices"), ("pallas", "host")])
def test_batched_emission_byte_identical(qp, streams, backend, placement,
                                         shards, case, monkeypatch):
    """The fused resident fleet's batched emission gives the single
    engine's events byte for byte: on ticks where only some shards emit,
    where a stream ends on another's window end, with trajectory taps,
    after a crash whose replay suppresses delivered events, and when a
    shard whose h was rebound finishes on its own pull."""
    own = []
    real_finish = StreamingEngine._advance_finish
    monkeypatch.setattr(StreamingEngine, "_advance_finish",
                        lambda self, *a: own.append(1) or real_finish(self, *a))
    cut, got, trajs, emitting, fleet = _emission_run(
        qp, streams, backend=backend, placement=placement, shards=shards,
        case=case, monkeypatch=monkeypatch)
    assert bool(own) == (case == "rebound_shard")
    ref = StreamingEngine(qp, StreamingConfig(
        max_slots=len(cut), window=8, backend=backend))
    for sid, w in cut.items():
        ref.attach(sid, w, total_steps=len(w),
                   record_trajectory=sid in trajs)
    assert_logs_identical(got, collect_log(ref.drain()))
    for sid, traj in trajs.items():
        assert traj.tobytes() == ref.trajectory(sid).tobytes()
    st = fleet.stats()
    if shards > 1 and case != "crash_replay":   # a replay shifts timing
        used = {s for w, f in emitting for s in w | f}
        assert any(w | f and w | f != used for w, f in emitting)
        assert any(f - w and w for w, f in emitting)
    if case == "crash_replay":
        assert st["failovers"] == 3 and st["replay_suppressed"] > 0
