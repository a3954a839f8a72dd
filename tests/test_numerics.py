"""Numeric-health observability: saturation counters, drift, crosscheck.

The contract under test (see docs/observability.md):

* monitoring is byte-invisible — monitored and unmonitored runs produce
  identical predictions / logits / trajectories on every backend and
  fleet shape, because monitors only *read* intermediates the engines
  already materialize;
* the qvm's per-site saturation counters and the
  ``-DFG_NUMERIC_COUNTERS`` C build's agree exactly on shared windows;
* dynamic witnesses are contained in the statically reachable site set
  (:mod:`repro.analysis.crosscheck`), with the x8 stress segment
  proving the counters actually count;
* fleet crash/rebuild conserves every site counter (live + retired ==
  totals) and the flight recorder captures the dead shard's last
  numeric-health snapshot.
"""
from __future__ import annotations

import json
import os

import jax
import numpy as np
import pytest

from faultharness import (assert_counters_conserved, make_streams,
                          reference_log, run_crash_schedule)
from repro.core import fastgrnn as fg
from repro.core.quantization import QuantConfig, quantize_params
from repro.data import hapt
from repro.deploy import emit_c
from repro.deploy.goldens import build_reference_artifact
from repro.deploy.image import build_image
from repro.deploy.qvm import QVM
from repro.obs import (MetricsRegistry, Observability,
                       check_numerics_conservation)
from repro.obs.numerics import (NumericsMonitor, limits_from_scales,
                                merge_site_counts, site_order)
from repro.serve.fleet import FleetConfig, FleetEngine, crash_matrix
from repro.serve.streaming import StreamingConfig, StreamingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Gain that drives h_next saturation on the reference model.
STRESS = 8


@pytest.fixture(scope="module")
def art():
    return build_reference_artifact(seed=0)


@pytest.fixture(scope="module")
def img(art):
    return build_image(art)


@pytest.fixture(scope="module")
def windows():
    return hapt.load("test", n=32).windows


@pytest.fixture(scope="module")
def qp():
    cfg = fg.FastGRNNConfig(rank_w=2, rank_u=8)
    return quantize_params(fg.init_params(cfg, jax.random.PRNGKey(0)),
                           QuantConfig())


@pytest.fixture(scope="module")
def input_dim(qp):
    return StreamingEngine(qp, StreamingConfig(max_slots=1)).kernel.input_dim


def mon_obs(**kw) -> Observability:
    return Observability(metrics=MetricsRegistry(),
                         numerics=NumericsMonitor(), **kw)


# ---------------------------------------------------------------------------
# Monitor unit behavior
# ---------------------------------------------------------------------------

def test_site_vocabulary_matches_qlint_classification():
    """Every site the static analyzer classifies exists in the runtime
    counter vocabulary, in both low-rank and dense shapes."""
    with open(os.path.join(REPO, "ANALYSIS_report.json")) as f:
        report = json.load(f)
    lr = set(site_order(True))
    for t in report["qlint"]["targets"]:
        sat = t["saturation"]
        assert set(sat["reachable"]) | set(sat["dead"]) <= lr
    assert "w.out" in site_order(False) and "w1.out" not in site_order(False)


def test_monitor_counts_limits_and_snapshot_determinism():
    mon = NumericsMonitor()
    mon.declare(("h_next", "gate.hf_clip"))
    mon.count("h_next", 3)
    mon.count_events({"h_next": 2})
    mon.set_default_limits({"h": 1.0})
    mon.observe("h", np.array([0.5, -2.5, 0.25], np.float32))
    snap = mon.snapshot()
    assert snap["sites"]["h_next"] == 5 and snap["sites"]["gate.hf_clip"] == 0
    t = snap["tensors"]["h"]
    assert t["n"] == 3 and t["n_over"] == 1
    assert t["min"] == -2.5 and t["max"] == 0.5
    assert mon.drift() > 0
    assert json.dumps(snap, sort_keys=True) == json.dumps(mon.snapshot(),
                                                          sort_keys=True)


def test_shard_children_share_late_bound_limits(art):
    mon = NumericsMonitor()
    child = mon.shard(0)
    mon.set_default_limits(limits_from_scales(art.act_scales))
    assert child.limit("h") == mon.limit("h") and child.limit("h")
    child.count("h_next", 2)
    other = mon.shard(1)
    other.count("h_next", 1)
    assert mon.snapshot()["sites"]["h_next"] == 3       # parent aggregates
    assert mon.snapshot(per_shard=True)["per_shard"]["0"]["sites"][
        "h_next"] == 2


def test_merge_site_counts():
    acc = {"a": 1}
    out = merge_site_counts(acc, {"a": 2, "b": 5})
    assert out is acc and acc == {"a": 3, "b": 5}


# ---------------------------------------------------------------------------
# qvm: byte-identity + witnesses
# ---------------------------------------------------------------------------

def test_monitored_qvm_byte_identical_and_clean_on_goldens(img, windows):
    vm = QVM(img)
    xq = vm.quantize_input(windows)
    logits, traces = vm.run_windows(xq, return_trajectory=True)
    mon = NumericsMonitor()
    mvm = QVM(img, monitor=mon)
    mxq = mvm.quantize_input(windows)       # x telemetry rides quantize
    np.testing.assert_array_equal(xq, mxq)
    mlogits, mtraces = mvm.run_windows(mxq, return_trajectory=True)
    np.testing.assert_array_equal(logits, mlogits)
    np.testing.assert_array_equal(traces, mtraces)
    snap = mon.snapshot()
    assert all(v == 0 for v in snap["sites"].values())
    assert snap["tensors"]["h"]["n"] > 0                # telemetry flowed
    assert snap["tensors"]["x"]["n_over"] == 0


def test_stress_gain_witnesses_h_next_saturation(img, windows):
    mon = NumericsMonitor()
    vm = QVM(img, monitor=mon)
    vm.run_windows(vm.quantize_input(
        np.asarray(windows, np.float32) * STRESS))
    sites = mon.snapshot()["sites"]
    assert sites["h_next"] > 0                          # the witness
    assert sites["gate.hf_clip"] == 0                   # still unreachable
    dead = [s for s in sites if s not in ("h_next", "gate.hf_clip")]
    assert all(sites[s] == 0 for s in dead)             # containment


# ---------------------------------------------------------------------------
# C twin: exact counter parity
# ---------------------------------------------------------------------------

@pytest.mark.skipif(not emit_c.find_cc(), reason="no host C compiler")
def test_c_counter_parity_with_qvm(img, windows, tmp_path):
    vm = QVM(img)
    order = site_order(bool(img.low_rank))
    binary = emit_c.compile_host(img, str(tmp_path), engine="int",
                                 numeric_counters=True)
    cm = emit_c.CHostModel(binary, img.H, img.C, engine="int")
    for gain in (1, STRESS):
        xq = vm.quantize_input(np.asarray(windows, np.float32) * gain)
        mon = NumericsMonitor()
        q_logits = QVM(img, monitor=mon).run_windows(xq)
        q_counts = np.array([mon.snapshot()["sites"][s] for s in order],
                            np.uint64)
        c_preds, c_counts = cm.counters(xq)
        np.testing.assert_array_equal(
            c_preds, np.argmax(q_logits, axis=1).astype(np.int32))
        np.testing.assert_array_equal(c_counts, q_counts)
    assert c_counts[order.index("h_next")] > 0          # stress witnessed


@pytest.mark.skipif(not emit_c.find_cc(), reason="no host C compiler")
def test_plain_c_build_refuses_counter_mode(img, windows, tmp_path):
    """A binary compiled WITHOUT -DFG_NUMERIC_COUNTERS must die loudly on
    the counter protocol, not emit garbage."""
    vm = QVM(img)
    binary = emit_c.compile_host(img, str(tmp_path), engine="int")
    cm = emit_c.CHostModel(binary, img.H, img.C, engine="int")
    with pytest.raises(Exception):
        cm.counters(vm.quantize_input(windows[:2]))


# ---------------------------------------------------------------------------
# Engine: monitoring is byte-invisible on every backend
# ---------------------------------------------------------------------------

def _engine_log(art, windows, backend, obs):
    eng = StreamingEngine.from_artifact(
        art, StreamingConfig(max_slots=len(windows), backend=backend),
        obs=obs)
    for i, w in enumerate(windows):
        eng.attach(f"w{i}", w, total_steps=len(w),
                   record_trajectory=(i < 2))
    events = eng.drain()
    log = [(e.stream_id, e.kind, int(e.step), int(e.prediction),
            np.asarray(e.logits, np.float32).tobytes()) for e in events]
    trajs = [np.asarray(eng.trajectory(f"w{i}")).tobytes() for i in range(2)]
    return log, trajs, eng


@pytest.mark.parametrize("backend", ["exact", "jit", "pallas"])
def test_monitored_engine_byte_identical(art, windows, backend):
    n = 16 if backend == "pallas" else 24
    w = windows[:n]
    log0, trajs0, _ = _engine_log(art, w, backend, None)
    obs = mon_obs()
    log1, trajs1, eng = _engine_log(art, w, backend, obs)
    assert log0 == log1
    assert trajs0 == trajs1
    snap = eng.stats()["numerics"]
    # device-resident backends skip per-tick pre tallies by design
    # (zero-h-copy contract); input + emission telemetry always flows
    if backend == "exact":
        assert snap["tensors"]["pre"]["n"] > 0
    assert snap["tensors"]["x"]["n"] > 0
    assert snap["tensors"]["h"]["n"] > 0
    assert snap["tensors"]["h"]["limit"] is not None    # limits late-bound
    # throttled publish still exported the counter series
    assert any(k.startswith("numerics.sat.")
               for k in obs.metrics.snapshot()["counters"])


@pytest.mark.parametrize("shards", [1, 2, 4, 8])
def test_monitored_fleet_byte_identical_with_crash(qp, input_dim, shards):
    streams = make_streams(16, 300, input_dim, seed=3)
    want = reference_log(qp, streams)
    obs = mon_obs(debug=True)                # debug => conservation asserted
    log, stats = run_crash_schedule(
        qp, streams, shards=shards, slots_per_shard=16,
        injector=crash_matrix(shards), obs=obs)
    assert log == want                       # byte-identical through crashes
    assert_counters_conserved(stats)
    num = stats["numerics"]
    assert num["tensors"]["pre"]["n"] > 0
    # engines declare the two kernel-side LUT sites on their shard child
    assert {"act.z.idx", "act.ht.idx"} <= set(num["sites"])


def test_numerics_conservation_check_catches_drift():
    stats = {
        "numerics": {"sites": {"h_next": 5}, "retired_sites": {"h_next": 2}},
        "per_shard": [{"numerics": {"sites": {"h_next": 3}}}],
    }
    assert check_numerics_conservation(stats) == []
    stats["numerics"]["sites"]["h_next"] = 6
    errs = check_numerics_conservation(stats)
    assert len(errs) == 1 and "h_next" in errs[0]
    assert check_numerics_conservation({"per_shard": []}) == []


# ---------------------------------------------------------------------------
# Fleet crash: retirement + flight recorder
# ---------------------------------------------------------------------------

def test_crash_folds_numerics_into_flight_dump(qp, input_dim):
    obs = Observability.full(numerics=True, debug=True)
    fleet = FleetEngine(qp, FleetConfig(
        shards=2, snapshot_every=16,
        stream=StreamingConfig(max_slots=8)), obs=obs)
    for sid, w in make_streams(8, 200, input_dim).items():
        fleet.attach(sid, w, total_steps=200)
    for _ in range(60):
        fleet.step()
    fleet.crash_shard(1)
    dump = obs.recorder.last()
    num = dump["counters"]["numerics"]
    assert num is not None and num["tensors"]["pre"]["n"] > 0
    assert dump["counters"]["retired_numerics"] == dict(
        sorted(num["sites"].items()))
    stats = fleet.stats()                    # debug => conservation holds
    assert stats["numerics"]["retired_sites"] == dump[
        "counters"]["retired_numerics"]
    fleet.drain()
    assert_counters_conserved(fleet.stats())


def test_crash_matrix_numeric_dumps_byte_stable(qp, input_dim):
    """Identical monitored runs under the full phase x shard crash matrix
    produce byte-identical deterministic flight dumps, numeric-health
    snapshots included."""
    streams = make_streams(12, 300, input_dim, seed=5)

    def run():
        obs = Observability.full(numerics=True)
        log, stats = run_crash_schedule(
            qp, streams, shards=2, slots_per_shard=8,
            injector=crash_matrix(2), obs=obs)
        return obs, log, stats

    obs_a, log_a, stats_a = run()
    obs_b, log_b, stats_b = run()
    assert log_a == log_b
    assert obs_a.recorder.dumps(deterministic=True) == \
        obs_b.recorder.dumps(deterministic=True)
    dump = json.loads(obs_a.recorder.dumps(deterministic=True))
    assert all("numerics" in c["counters"] for c in dump["crashes"])
    assert json.dumps(stats_a["numerics"], sort_keys=True) == \
        json.dumps(stats_b["numerics"], sort_keys=True)
    assert_counters_conserved(stats_a)


# ---------------------------------------------------------------------------
# Crosscheck gate + drift
# ---------------------------------------------------------------------------

def test_crosscheck_reference_images(img, windows):
    from repro.analysis import crosscheck, target_by_name
    from repro.analysis.qlint import analyze_image
    with open(os.path.join(REPO, "ANALYSIS_report.json")) as f:
        report = json.load(f)
    target = target_by_name(report, "reference-q15-s0")
    # committed report matches a fresh analysis of the same image
    fresh = analyze_image(img, name="reference-q15-s0")
    assert fresh["saturation"] == target["saturation"]
    vm = QVM(img)
    for bits_target in (target, target_by_name(report, "reference-q7-s0")):
        mon = NumericsMonitor()
        QVM(img, monitor=mon).run_windows(vm.quantize_input(windows))
        v = crosscheck(bits_target, mon.snapshot())
        assert v["ok"] and v["witnessed"] == []
        assert "h_next" in v["unwitnessed_reachable"]
    # stress run: witnessed, still contained, expect_nonzero satisfied
    mon = NumericsMonitor()
    QVM(img, monitor=mon).run_windows(vm.quantize_input(
        np.asarray(windows, np.float32) * STRESS))
    v = crosscheck(target, mon.snapshot(), expect_nonzero=("h_next",))
    assert v["ok"] and v["witnessed"] == ["h_next"]


def test_crosscheck_flags_violations():
    from repro.analysis import crosscheck, target_by_name
    with open(os.path.join(REPO, "ANALYSIS_report.json")) as f:
        target = target_by_name(json.load(f), "reference-q15-s0")
    zeros = {s: 0 for s in site_order(True)}
    v = crosscheck(target, {"sites": {**zeros, "w1.out": 3}})
    assert not v["ok"] and "dead" in v["violations"][0]
    v = crosscheck(target, {"sites": {**zeros, "head.logits": 1}})
    assert not v["ok"] and "never" in v["violations"][0]
    v = crosscheck(target, {"sites": zeros}, expect_nonzero=("h_next",))
    assert not v["ok"] and "witness" in v["violations"][0]
    with pytest.raises(KeyError):
        target_by_name({"qlint": {"targets": []}}, "nope")


def test_drift_score_monotone_under_gain(img, windows):
    scores = []
    vm = QVM(img)
    for gain in (1, 2, 8):
        mon = NumericsMonitor()
        QVM(img, monitor=mon).run_windows(vm.quantize_input(
            np.asarray(windows[:8], np.float32) * gain))
        scores.append(mon.drift())
    assert scores == sorted(scores) and scores[-1] > scores[0]


def test_verify_parity_report_carries_numerics(art, windows):
    from repro.deploy.verify import quantized_paths_agree, run_parity
    report = run_parity(art, windows=windows[:8], n_scalar=2, n_trace=2,
                        use_fp32=False)
    if emit_c.find_cc():
        assert report["bitwise"]["c_int_qvm_counters"]
        assert report["bitwise"]["numerics_crosscheck"]
        assert report["numerics"]["crosscheck"]["ok"]
    assert quantized_paths_agree(report)
