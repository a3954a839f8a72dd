"""Sharded fleet serving (serve/fleet): the bit-exactness gate, routing,
migration, spillover, decommission, counter composition, and device
placement.

The load-bearing contract (ISSUE acceptance): for every stream,
``FleetEngine`` outputs — logits, warm-up flags, step counters,
trajectories — are byte-identical to the single-engine
``StreamingEngine`` reference at 1, 2, 4 and 8 shards, including across
forced mid-stream migrations.  That is what makes the fleet a serving
core rather than a demo."""
import dataclasses
import random
import re

import jax
import numpy as np
import pytest

from repro.core import fastgrnn as fg
from repro.core.qruntime import QRuntime
from repro.core.quantization import quantize_params, QuantConfig
from repro.data import hapt
from repro.serve.fleet import (FleetConfig, FleetEngine, hrw_weight,
                               rank_shards, route, shard_devices)
from repro.serve.streaming import (StreamEventBatch, StreamingConfig,
                                   StreamingEngine, classify_windows)


def _model(seed=0):
    cfg = fg.FastGRNNConfig(rank_w=2, rank_u=8)
    return quantize_params(fg.init_params(cfg, jax.random.PRNGKey(seed)),
                           QuantConfig())


@pytest.fixture(scope="module")
def qp():
    return _model()


@pytest.fixture(scope="module")
def windows():
    return hapt.load("test", n=120).windows


@pytest.fixture(scope="module")
def ref_logits(qp, windows):
    rt = QRuntime(qp)
    return np.stack([rt.run_window(w) for w in windows])


def _collect(events):
    """Map stream_id -> last event fields, expanding columnar batches."""
    out = {}
    for e in events:
        if isinstance(e, StreamEventBatch):
            for ev in e.events():
                out[ev.stream_id] = ev
        else:
            out[e.stream_id] = e
    return out


# ---------------------------------------------------------------------------
# Acceptance gate: bit-identical to the single engine at 1/2/4/8 shards
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shards", [1, 2, 4, 8])
def test_fleet_bit_identical_across_shard_counts(qp, windows, ref_logits,
                                                 shards):
    n = 64
    fleet = FleetEngine(qp, FleetConfig(
        shards=shards, stream=StreamingConfig(max_slots=16)))
    for i in range(n):
        fleet.attach(f"s{i}", windows[i], total_steps=len(windows[i]))
    by_id = _collect(fleet.drain())
    assert len(by_id) == n
    got = np.stack([by_id[f"s{i}"].logits for i in range(n)])
    np.testing.assert_array_equal(got.view(np.int32),
                                  ref_logits[:n].view(np.int32))
    for i in range(n):
        ev = by_id[f"s{i}"]
        assert ev.step == 128 and ev.warm     # counters identical too
        assert ev.prediction == int(np.argmax(ref_logits[i]))
    st = fleet.stats()
    assert st["completed"] == n and st["stream_steps"] == n * 128


@pytest.mark.parametrize("shards", [2, 4, 8])
def test_fleet_bit_identical_across_forced_migration(qp, windows,
                                                     ref_logits, shards):
    """Mid-stream migration (hidden state + buffered samples + counters
    move shards) must not perturb a single bit of any stream — migrated
    or bystander."""
    n = 32
    fleet = FleetEngine(qp, FleetConfig(
        shards=shards, stream=StreamingConfig(max_slots=16)))
    for i in range(n):
        fleet.attach(f"s{i}", windows[i], total_steps=128)
    for _ in range(37):                      # advance mid-window
        fleet.step()
    for i in range(0, n, 3):                 # force-migrate a third of them
        src = fleet.shard_of(f"s{i}")
        fleet.migrate(f"s{i}", (src + 1) % shards)
    for _ in range(20):
        fleet.step()
    fleet.migrate("s0")                      # second hop for one stream
    by_id = _collect(fleet.drain())
    got = np.stack([by_id[f"s{i}"].logits for i in range(n)])
    np.testing.assert_array_equal(got.view(np.int32),
                                  ref_logits[:n].view(np.int32))
    assert fleet.stats()["migrations"] == n // 3 + (n % 3 > 0) + 1


def test_fleet_parity_smoke_4x64(qp, windows, ref_logits):
    """The CI fleet-parity smoke: 4 shards x 64 streams vs the single
    engine, via the shared classify_windows driver (which runs unchanged
    against a fleet)."""
    fleet = FleetEngine(qp, FleetConfig(
        shards=4, stream=StreamingConfig(max_slots=16)))
    preds = classify_windows(fleet, windows[:64])
    np.testing.assert_array_equal(preds, np.argmax(ref_logits[:64], axis=1))


def test_migrated_trajectory_bit_identical(qp, windows):
    """detach-state -> migrate -> re-attach must reproduce the single
    engine's per-step hidden trajectory bit-exactly (satellite gate)."""
    fleet = FleetEngine(qp, FleetConfig(
        shards=4, stream=StreamingConfig(max_slots=8)))
    fleet.attach("t", windows[0], total_steps=128, record_trajectory=True)
    for _ in range(50):
        fleet.step()
    src = fleet.shard_of("t")
    fleet.migrate("t", (src + 2) % 4)
    fleet.drain()
    single = StreamingEngine(qp, StreamingConfig(max_slots=4))
    single.attach("t", windows[0], total_steps=128, record_trajectory=True)
    single.drain()
    np.testing.assert_array_equal(fleet.trajectory("t").view(np.int32),
                                  single.trajectory("t").view(np.int32))


# ---------------------------------------------------------------------------
# Rendezvous routing
# ---------------------------------------------------------------------------

def test_hrw_routing_deterministic_and_total():
    keys = [f"shard-{i}" for i in range(8)]
    assert hrw_weight("stream-a", "shard-0") == hrw_weight("stream-a",
                                                           "shard-0")
    homes = [route(f"stream-{i}", keys) for i in range(512)]
    assert homes == [route(f"stream-{i}", keys) for i in range(512)]
    counts = np.bincount(homes, minlength=8)
    assert (counts > 0).all()                # every shard gets traffic
    ranked = rank_shards("stream-x", keys)
    assert sorted(ranked) == list(range(8))  # a permutation
    assert ranked[0] == route("stream-x", keys)


def test_hrw_stable_under_shard_removal():
    """Removing one shard remaps ONLY that shard's streams (each to its
    next-best shard); every other stream keeps its home — the property
    drain/decommission relies on."""
    keys = [f"shard-{i}" for i in range(8)]
    sids = [f"stream-{i}" for i in range(400)]
    before = {s: route(s, keys) for s in sids}
    eligible = [i != 3 for i in range(8)]
    for s in sids:
        after = route(s, keys, eligible)
        if before[s] != 3:
            assert after == before[s]
        else:
            assert after == rank_shards(s, keys)[1]  # next-best


def test_route_requires_eligible_shard():
    with pytest.raises(ValueError):
        route("s", ["a", "b"], [False, False])


# ---------------------------------------------------------------------------
# Admission spillover + lifecycle
# ---------------------------------------------------------------------------

def test_spillover_queue_fifo_and_bit_exact(qp, windows, ref_logits):
    fleet = FleetEngine(qp, FleetConfig(
        shards=2, stream=StreamingConfig(max_slots=2),
        max_pending_per_shard=1))
    statuses = [fleet.attach(f"s{i}", windows[i], total_steps=128)
                for i in range(12)]
    assert statuses.count("spilled") >= 1    # the queue was exercised
    assert fleet.n_spilled == statuses.count("spilled")
    by_id = _collect(fleet.drain())
    got = np.stack([by_id[f"s{i}"].logits for i in range(12)])
    np.testing.assert_array_equal(got.view(np.int32),
                                  ref_logits[:12].view(np.int32))
    st = fleet.stats()
    assert st["global_spills"] == statuses.count("spilled")
    assert st["completed"] == 12 and st["spilled"] == 0


def test_feed_and_detach_on_spilled_stream(qp, windows):
    fleet = FleetEngine(qp, FleetConfig(
        shards=2, stream=StreamingConfig(max_slots=1),
        max_pending_per_shard=0))
    for i in range(2):
        fleet.attach(f"fill{i}", windows[i])          # open-ended: pin slots
    assert fleet.attach("late", windows[2][:10]) == "spilled"
    fleet.feed("late", windows[2][10:20])             # buffers while spilled
    assert fleet.shard_of("late") == -1
    assert fleet.detach("late") is None               # dequeued, no event
    with pytest.raises(KeyError):
        fleet.feed("late", windows[2])
    fleet.attach("late", windows[2], total_steps=128)  # id reusable


# each form a caller may hand ``feed``, as the calls that feed one packet
_SAMPLE_FORMS = {
    "row": lambda p: list(p),                       # (d,) a call
    "lists": lambda p: [p.tolist()],
    "float64": lambda p: [p.astype(np.float64)],
    "int": lambda p: [p.astype(np.int64)],
    "canonical": lambda p: [p],                     # float32 (k, d)
}


def _three_state_fleet(qp):
    """A journaled fleet of one one-slot shard: the first stream attached is
    resident, the second pending, the third in the fleet's spill queue."""
    return FleetEngine(qp, FleetConfig(
        shards=1, stream=StreamingConfig(max_slots=1),
        max_pending_per_shard=1, snapshot_every=10_000))


@pytest.mark.parametrize("form", list(_SAMPLE_FORMS))
def test_feed_accepts_every_sample_form_alike(qp, windows, form):
    """Every form of the same samples lands as the same float32 rows in a
    resident stream's ring, a pending stream's chunks, a spilled stream's
    queue and each stream's replay journal, and gives the same logits."""
    sids = ("res", "pend", "spill")
    data = {sid: np.rint(windows[i] * 4).astype(np.float32)   # int-valued
            for i, sid in enumerate(sids)}
    sizes = [1, 5, 25, 33, 64]
    bounds = np.cumsum([0] + sizes)
    fleet = _three_state_fleet(qp)
    assert [fleet.attach(sid, total_steps=128) for sid in sids] == \
        ["active", "pending", "spilled"]
    for sid in sids:
        for a, b in zip(bounds[:-1], bounds[1:]):
            for x in _SAMPLE_FORMS[form](data[sid][a:b]):
                fleet.feed(sid, x)
    eng = fleet.shards[0]
    np.testing.assert_array_equal(
        eng._ring[:128, eng._sessions["res"].slot], data["res"])
    np.testing.assert_array_equal(
        np.concatenate(eng._sessions["pend"].chunks), data["pend"])
    np.testing.assert_array_equal(
        np.concatenate(fleet._spilled["spill"].chunks), data["spill"])
    for sid in sids:
        chunks = fleet._journal[sid].chunks
        assert all(c.dtype == np.float32 and c.ndim == 2 for c in chunks)
        assert [len(c) for c in chunks] == \
            ([1] * 128 if form == "row" else sizes)
        np.testing.assert_array_equal(np.concatenate(chunks), data[sid])
    by_id = _collect(fleet.drain())
    rt = QRuntime(qp)
    for sid in sids:
        np.testing.assert_array_equal(
            by_id[sid].logits.view(np.int32),
            rt.run_window(data[sid]).view(np.int32))


@pytest.mark.parametrize("bad", ["wrong-width", "3-d"])
def test_feed_rejects_bad_shapes_naming_the_stream(qp, windows, bad):
    """A wrong width or rank raises ValueError naming the stream, from the
    fleet (resident, pending, spilled) and the single engine, and leaves
    the stream's buffers and journal as they were."""
    head = windows[0][:4]
    d = head.shape[1]
    x = (np.zeros((5, d + 1), np.float32) if bad == "wrong-width"
         else np.zeros((1, 5, d), np.float32))
    fleet = _three_state_fleet(qp)
    for sid in ("res", "pend", "spill"):
        fleet.attach(sid, head)
        with pytest.raises(ValueError, match=re.escape(f"stream {sid!r}")):
            fleet.feed(sid, x)
        assert len(fleet._journal[sid].chunks) == 1
    eng = fleet.shards[0]
    for sid in ("res", "pend"):
        np.testing.assert_array_equal(eng.snapshot_stream(sid).samples, head)
    np.testing.assert_array_equal(
        np.concatenate(fleet._spilled["spill"].chunks), head)
    solo = StreamingEngine(qp, StreamingConfig(max_slots=1))
    for sid in ("a", "b"):                  # resident, then pending
        solo.attach(sid, head)
        with pytest.raises(ValueError, match=re.escape(f"stream {sid!r}")):
            solo.feed(sid, x)
        np.testing.assert_array_equal(solo.snapshot_stream(sid).samples, head)


def test_stream_id_reusable_after_completion(qp, windows):
    fleet = FleetEngine(qp, FleetConfig(
        shards=2, stream=StreamingConfig(max_slots=4)))
    fleet.attach("s", windows[0], total_steps=128)
    fleet.drain()
    fleet.attach("s", windows[1], total_steps=128)    # stale owner reclaimed
    by_id = _collect(fleet.drain())
    assert by_id["s"].prediction == int(
        np.argmax(QRuntime(qp).run_window(windows[1])))


def test_duplicate_attach_rejected(qp, windows):
    fleet = FleetEngine(qp, FleetConfig(
        shards=2, stream=StreamingConfig(max_slots=4)))
    fleet.attach("s", windows[0])
    with pytest.raises(ValueError):
        fleet.attach("s", windows[1])


# ---------------------------------------------------------------------------
# Decommission / recommission
# ---------------------------------------------------------------------------

def test_decommission_drains_shard_and_preserves_results(qp, windows,
                                                         ref_logits):
    fleet = FleetEngine(qp, FleetConfig(
        shards=4, stream=StreamingConfig(max_slots=16)))
    homes = {}
    for i in range(32):
        fleet.attach(f"s{i}", windows[i], total_steps=128)
        homes[f"s{i}"] = fleet.shard_of(f"s{i}")
    for _ in range(11):
        fleet.step()
    moved = fleet.decommission(1)
    assert set(moved) == {s for s, h in homes.items() if h == 1}
    for sid, home in homes.items():
        if home != 1:
            assert fleet.shard_of(sid) == home        # bystanders untouched
        else:
            assert fleet.shard_of(sid) != 1
    assert fleet.attach("new", windows[40], total_steps=128) in ("active",
                                                                 "pending")
    assert fleet.shard_of("new") != 1                 # not routed to drained
    by_id = _collect(fleet.drain())
    got = np.stack([by_id[f"s{i}"].logits for i in range(32)])
    np.testing.assert_array_equal(got.view(np.int32),
                                  ref_logits[:32].view(np.int32))
    fleet.recommission(1)
    assert fleet.stats()["routable"] == [True] * 4


def test_migrate_refuses_decommissioned_destination(qp, windows):
    """A drained shard must stay empty until recommission — an explicit
    migrate onto it is an error, not a silent re-population."""
    fleet = FleetEngine(qp, FleetConfig(
        shards=3, stream=StreamingConfig(max_slots=4)))
    fleet.attach("s", windows[0], total_steps=128)
    src = fleet.shard_of("s")
    dead = next(i for i in range(3) if i != src)
    fleet.decommission(dead)
    with pytest.raises(ValueError, match="decommissioned"):
        fleet.migrate("s", dead)
    fleet.recommission(dead)
    assert fleet.migrate("s", dead) in ("active", "pending")


def test_migrate_without_destination_needs_another_routable_shard(qp,
                                                                  windows):
    fleet = FleetEngine(qp, FleetConfig(
        shards=1, stream=StreamingConfig(max_slots=4)))
    fleet.attach("s", windows[0], total_steps=128)
    with pytest.raises(ValueError, match="no routable destination"):
        fleet.migrate("s")


def test_cannot_decommission_last_shard(qp):
    fleet = FleetEngine(qp, FleetConfig(
        shards=2, stream=StreamingConfig(max_slots=2)))
    fleet.decommission(0)
    with pytest.raises(ValueError):
        fleet.decommission(1)


# ---------------------------------------------------------------------------
# Counter composition (satellite: fleet stats == sum of shard counters)
# ---------------------------------------------------------------------------

def test_counters_compose_under_random_lifecycle(qp, windows):
    """Property: after any random admit / feed / migrate / detach / step
    sequence, every composed counter in fleet.stats()['scheduler'] equals
    the sum over per-shard schedulers, and the workload roll-ups equal
    the per-shard sums."""
    rng = random.Random(1234)
    fleet = FleetEngine(qp, FleetConfig(
        shards=3, stream=StreamingConfig(max_slots=4),
        max_pending_per_shard=1))
    live, next_id = [], 0
    for _ in range(220):
        op = rng.random()
        if op < 0.35:
            sid = f"r{next_id}"
            next_id += 1
            k = rng.randrange(0, 64)
            total = rng.choice([None, 32, 128])
            fleet.attach(sid, windows[rng.randrange(len(windows))][:k]
                         if k else None, total_steps=total)
            live.append(sid)
        elif op < 0.5 and live:
            sid = live.pop(rng.randrange(len(live)))
            try:
                fleet.detach(sid)
            except KeyError:
                pass                      # finished on its own: stale id
        elif op < 0.6 and live:
            sid = rng.choice(live)
            try:
                fleet.migrate(sid, rng.randrange(3))
            except (KeyError, ValueError):
                pass                      # spilled / same-shard / finished
        elif op < 0.75 and live:
            fleet.feed(rng.choice(live),
                       windows[rng.randrange(len(windows))][:8])
        else:
            fleet.step()
    st = fleet.stats()
    per = [p["scheduler"] for p in st["per_shard"]]
    for key in ("admissions", "recycles", "spills", "completed",
                "cancelled", "evictions", "ticks", "active", "pending",
                "peak_active"):
        assert st["scheduler"][key] == sum(p[key] for p in per), key
    for key in ("active", "pending", "completed", "stream_steps",
                "ring_spills"):
        assert st[key] == sum(p[key] for p in st["per_shard"]), key
    total_slots = sum(p["max_slots"] for p in per)
    assert st["scheduler"]["occupancy"] == \
        st["scheduler"]["active"] / total_slots


def test_random_lifecycle_matches_reference_predictions(qp, windows):
    """Under a random admit/spill/migrate/release schedule every finished
    window still matches the scalar reference bit for bit."""
    rng = random.Random(7)
    fleet = FleetEngine(qp, FleetConfig(
        shards=4, stream=StreamingConfig(max_slots=3),
        max_pending_per_shard=2))
    events = []
    for i in range(24):
        fleet.attach(f"w{i}", windows[i], total_steps=128)
        for _ in range(rng.randrange(0, 30)):
            events.extend(fleet.step())
        if i % 5 == 0:
            try:
                fleet.migrate(f"w{rng.randrange(i + 1)}")
            except (KeyError, ValueError):
                pass
    events.extend(fleet.drain())
    by_id = _collect(events)
    rt = QRuntime(qp)
    for i in range(24):
        np.testing.assert_array_equal(
            by_id[f"w{i}"].logits.view(np.int32),
            rt.run_window(windows[i]).view(np.int32))


# ---------------------------------------------------------------------------
# Columnar event mode
# ---------------------------------------------------------------------------

def test_batch_events_carry_identical_content(qp, windows):
    cfg = FleetConfig(shards=2,
                      stream=StreamingConfig(max_slots=8, batch_events=True))
    fleet = FleetEngine(qp, cfg)
    for i in range(8):
        fleet.attach(f"s{i}", windows[i][:40], total_steps=40)
    events = fleet.drain()
    assert all(isinstance(e, StreamEventBatch) for e in events)
    by_id = _collect(events)          # expands via StreamEventBatch.events()
    single = StreamingEngine(qp, StreamingConfig(max_slots=8))
    for i in range(8):
        single.attach(f"s{i}", windows[i][:40], total_steps=40)
    ref = {e.stream_id: e for e in single.drain()}
    assert set(by_id) == set(ref)
    for sid, ev in by_id.items():
        assert (ev.kind, ev.step, ev.window_step, ev.prediction, ev.warm) \
            == (ref[sid].kind, ref[sid].step, ref[sid].window_step,
                ref[sid].prediction, ref[sid].warm)
        np.testing.assert_array_equal(ev.logits.view(np.int32),
                                      ref[sid].logits.view(np.int32))


# ---------------------------------------------------------------------------
# Device placement + fast backends
# ---------------------------------------------------------------------------

def test_shard_devices_fallbacks():
    assert shard_devices(4, "host", "jit") == [None] * 4
    assert shard_devices(4, "auto", "exact") == [None] * 4
    with pytest.raises(ValueError):
        shard_devices(2, "nope", "jit")


def test_fleet_on_distinct_devices(qp, windows, ref_logits):
    """jit shards placed on distinct fake host devices (conftest forces
    8) still produce reference predictions; stats reports the placement."""
    devs = shard_devices(4, "devices", "jit")
    assert len({str(d) for d in devs}) == 4      # genuinely distinct
    fleet = FleetEngine(qp, FleetConfig(
        shards=4, placement="devices",
        stream=StreamingConfig(max_slots=8, backend="jit")))
    st_devices = fleet.stats()["devices"]
    assert len(set(st_devices)) == 4
    preds = classify_windows(fleet, windows[:24])
    np.testing.assert_array_equal(preds, np.argmax(ref_logits[:24], axis=1))


@pytest.mark.parametrize("backend", ["jit", "pallas"])
def test_fast_backends_agree_on_predictions(qp, windows, ref_logits,
                                            backend):
    n = 24 if backend == "jit" else 12
    fleet = FleetEngine(qp, FleetConfig(
        shards=3, placement="host",
        stream=StreamingConfig(max_slots=8, backend=backend)))
    preds = classify_windows(fleet, windows[:n])
    np.testing.assert_array_equal(preds, np.argmax(ref_logits[:n], axis=1))


# ---------------------------------------------------------------------------
# Engine-level export/import (the migration primitive)
# ---------------------------------------------------------------------------

def test_export_import_resident_stream_bit_exact(qp, windows):
    a = StreamingEngine(qp, StreamingConfig(max_slots=4))
    b = StreamingEngine(qp, StreamingConfig(max_slots=4))
    a.attach("s", windows[0], total_steps=128)
    busy = [a.attach(f"b{i}", windows[i + 1], total_steps=128)
            for i in range(2)]
    assert busy == ["active"] * 2
    for _ in range(53):
        a.step()
    state = a.export_stream("s")
    assert state.steps == 53 and state.samples.shape == (75, 3)
    assert a.n_active == 2                     # slot freed, no event emitted
    assert b.import_stream(state) == "active"
    ev = [e for e in b.drain() if e.stream_id == "s"][0]
    np.testing.assert_array_equal(
        ev.logits.view(np.int32),
        QRuntime(qp).run_window(windows[0]).view(np.int32))
    sched_a = a.stats()["scheduler"]
    assert sched_a["evictions"] == 1 and sched_a["cancelled"] == 0


def test_export_pending_stream_restores_cleanly(qp, windows):
    a = StreamingEngine(qp, StreamingConfig(max_slots=1))
    a.attach("r", windows[0], total_steps=128)
    assert a.attach("p", windows[1], total_steps=128) == "pending"
    state = a.export_stream("p")
    assert state.steps == 0 and len(state.samples) == 128
    b = StreamingEngine(qp, StreamingConfig(max_slots=1))
    b.import_stream(state)
    ev = b.drain()[0]
    np.testing.assert_array_equal(
        ev.logits.view(np.int32),
        QRuntime(qp).run_window(windows[1]).view(np.int32))


def test_reexport_of_pending_migrated_stream_keeps_state(qp, windows):
    """Regression: a migrated-in stream still waiting in the destination's
    pending queue carries restored state on its session; exporting it
    AGAIN (second migration, decommission of the destination) must carry
    that state onward, not rewind the stream to zero."""
    a = StreamingEngine(qp, StreamingConfig(max_slots=2))
    a.attach("s", windows[0], total_steps=128)
    for _ in range(40):
        a.step()
    state = a.export_stream("s")
    b = StreamingEngine(qp, StreamingConfig(max_slots=1))
    b.attach("pin", windows[1])              # open stream pins the only slot
    assert b.import_stream(state) == "pending"
    state2 = b.export_stream("s")            # second hop while still pending
    assert state2.steps == 40 and len(state2.samples) == 88
    np.testing.assert_array_equal(state2.h.view(np.int32),
                                  state.h.view(np.int32))
    c = StreamingEngine(qp, StreamingConfig(max_slots=1))
    c.import_stream(state2)
    ev = [e for e in c.drain() if e.stream_id == "s"][0]
    np.testing.assert_array_equal(
        ev.logits.view(np.int32),
        QRuntime(qp).run_window(windows[0]).view(np.int32))


def test_owner_map_compacts_in_long_running_fleet(qp, windows):
    """Finished streams must not grow the fleet's owner map forever:
    compaction drops finished ids but keeps live streams and tapped
    (trajectory-recorded) ones, so post-completion ``trajectory()``
    still resolves.  (step() invokes it automatically once the stale
    entries outnumber 2x live + 1024.)"""
    fleet = FleetEngine(qp, FleetConfig(
        shards=2, stream=StreamingConfig(max_slots=8)))
    for g in range(40):
        fleet.attach(f"g{g}", windows[g % len(windows)][:8], total_steps=8)
    fleet.attach("tapped", windows[0][:8], total_steps=8,
                 record_trajectory=True)
    fleet.attach("live", windows[1])             # open-ended, stays attached
    fleet.drain()
    assert fleet.stats()["completed"] == 41
    assert len(fleet._owner) == 42               # finished ids still held...
    fleet._compact_owners()
    assert set(fleet._owner) == {"tapped", "live"}   # ...until compaction
    assert fleet.trajectory("tapped").shape == (8, 16)


def test_export_unknown_stream_raises(qp):
    eng = StreamingEngine(qp, StreamingConfig(max_slots=2))
    with pytest.raises(KeyError):
        eng.export_stream("ghost")
