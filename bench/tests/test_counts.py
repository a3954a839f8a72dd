"""Work counts, the readers built on them, the traffic schedule and the
comparison, at tiny sizes."""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

import check
import harness
from conftest import BENCH, TINY, load_json

T = harness._load_module(os.path.join(BENCH, "generators", "staggered.py"))

CONFIGS = {"fastgrnn-har-lowrank-q15": 748, "fastgrnn-har-fullrank-q15": 768}


def _work(name):
    """A configuration and the work module its ``model.cell`` names."""
    cfg = load_json(f"bench/configs/{name}.json")
    return cfg, harness._load_module(
        os.path.join(BENCH, "work", cfg["model"]["cell"] + ".py"))


@pytest.mark.parametrize("name,flops", sorted(CONFIGS.items()))
def test_work_per_stream_step(name, flops):
    cfg, work = _work(name)
    assert work.count(cfg["model"], {"stream_steps": 1}) == {"flops": flops, "hbm_bytes": 140}
    assert work.count(cfg["model"], {"stream_steps": 131072, "ticks": 1}) \
        == {"flops": 131072 * flops, "hbm_bytes": 131072 * 140}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_work_matches_the_programs_own_count(name):
    from repro.compress import ModelArtifact, default_deploy_pipeline
    from repro.kernels.fastgrnn_cell.ops import Q15StreamStep
    cfg, work = _work(name)
    ref = harness._load_module(os.path.join(BENCH, "references", "fastgrnn_q15.py"))
    params = ref.make_params(cfg, np.random.SeedSequence(1))
    art = default_deploy_pipeline().run(ModelArtifact.from_params(params))
    prog = Q15StreamStep(art.qp).work_per_stream_step()
    w = work.count(cfg["model"], {"stream_steps": 1})
    assert (prog["model_flops_per_stream_step"], prog["hbm_bytes_per_stream_step"]) \
        == (w["flops"], w["hbm_bytes"])


#: What the readers gave on the fixed window below before the work count
#: became the window's totals: (q15_step_roofline, fleet.mfu) per config.
PARENT_READINGS = {"fastgrnn-har-lowrank-q15": (2.257591786837196, 0.0006294561284282356),
                   "fastgrnn-har-fullrank-q15": (2.257591786837196, 0.0006462865061936965)}


@pytest.mark.parametrize("name", sorted(PARENT_READINGS))
def test_readers_on_a_fixed_window(name):
    cfg, work = _work(name)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peak = json.load(f)["devices"]["TPU v5 lite"]
    counters = {"stream_steps": 49938432, "ticks": 381}
    ctx = {"trace": {"devices": 1, "ops": {"q15_step.1": 0.3781234567, "fusion.3": 0.02},
                     "op_text": {"q15_step.1": "", "fusion.3": ""}},
           "peak": peak, "stream_steps": counters["stream_steps"], "counters": counters,
           "system_s": 30.123456789, "chips": 1, "work": work.count(cfg["model"], counters)}
    got = [harness._load_module(os.path.join(BENCH, "metrics", m + ".py")).read(ctx)
           for m in ("q15_step_roofline", "fleet.mfu")]
    assert got == pytest.approx(list(PARENT_READINGS[name]), rel=1e-12, abs=0)


def _gen(mix, window, seed):
    return T.Generator(mix, {"window": window}, np.random.SeedSequence(seed))


def _schedule(mix, ticks, seed=5, shards=8):
    tr = _gen(mix, 128, seed)
    tr.assign_cohorts(np.arange(mix["streams"]) % shards)
    buffered = np.zeros(mix["streams"], np.int64)
    fed_per_tick, stepped = [], []
    for t in range(ticks):
        idx = tr.feeds(t)
        pk = tr.packets(idx)
        assert pk.shape == (len(idx), mix["packet_samples"], 3)
        buffered[idx] += mix["packet_samples"]
        assert buffered.max() <= tr.max_buffered
        fed_per_tick.append(len(idx))
        live = tr.cohort <= t
        assert np.all(buffered[live] > 0), f"a stream ran dry on tick {t}"
        buffered[live] -= 1
        stepped.append(int(live.sum()))
    return tr, fed_per_tick, stepped


@pytest.mark.parametrize("mix", [
    TINY, dict(TINY, streams=1024, window_phases=128, packet_samples=25),
    dict(TINY, streams=1024, window_phases=1, packet_samples=25)],
    ids=["tiny", "128-phase", "lockstep"])
def test_every_stream_advances_on_every_tick(mix):
    ticks = mix["window_phases"] + 3 * mix["packet_samples"] + 7
    tr, fed, stepped = _schedule(mix, ticks)
    n, p = mix["streams"], mix["packet_samples"]
    assert stepped[-1] == n
    # after the roll-in, 1/P of the streams are fed on each tick (to one)
    after = fed[mix["window_phases"]:]
    assert min(after) >= n // p and max(after) <= -(-n // p)
    # each stream received exactly the packets that cover its steps so far
    assert np.all(tr.fed * p >= ticks - tr.cohort)


def test_window_phases_balanced_inside_each_shard():
    mix = dict(TINY, streams=1024, window_phases=128, packet_samples=25)
    tr = _gen(mix, 128, 9)
    shard_of = np.random.default_rng(0).integers(0, 8, 1024)
    tr.assign_cohorts(shard_of)
    for s in range(8):
        counts = np.bincount(tr.cohort[shard_of == s], minlength=128)
        assert counts.max() - counts.min() <= 1


def test_same_seed_same_inputs_other_seed_other_samples():
    a = _gen(TINY, 128, 2**31 + 11)
    b = _gen(TINY, 128, 2**31 + 11)
    c = _gen(TINY, 128, 2**31 + 12)
    for tr in (a, b, c):
        tr.assign_cohorts(np.arange(128) % 8)
    idx = np.arange(128)
    assert np.array_equal(a.packets(idx), b.packets(idx))
    assert not np.array_equal(a.packets(idx), c.packets(idx))
    assert len(a.feeds(200)) == len(c.feeds(200))


def test_packets_play_the_streams_windows_in_order():
    tr = _gen(TINY, 128, 4)
    tr.assign_cohorts(np.zeros(128, int))
    i = np.array([7])
    got = np.concatenate([tr.packets(i)[0] for _ in range(128 // 4 * 2)])
    want = tr.pool[tr.window_index(7, np.arange(2))].reshape(256, 3)
    assert np.array_equal(got, want)


class _Ref:
    def logits(self, windows):
        return np.stack(windows).sum(axis=1)[:, :2].astype(np.float32)


def test_expected_predictions_follow_the_cohorts():
    tr = _gen(TINY, 16, 6)
    tr.assign_cohorts(np.arange(128) % 8)
    exp = tr.expected(40, {})
    assert {sid for sid, _ in exp} <= tr.check_ids
    for i in tr.check:
        n = (40 - tr.cohort[i] + 1) // 16
        steps = sorted(st for sid, st in exp if sid == tr.ids[i])
        assert steps == [16 * (k + 1) for k in range(n)]
        for k in range(n):
            assert np.array_equal(exp[(tr.ids[i], 16 * (k + 1))],
                                  tr.pool[tr.window_index(i, k)])


FLEET_NAMES = ("logit_max_abs_gap", "missing_predictions", "extra_predictions")
LIMITS = {"logit_max_abs_gap": 0.0, "missing_predictions": 0, "extra_predictions": 0}


def test_check_numbers_and_verdict():
    wins = np.random.default_rng(0).normal(size=(4, 128, 3)).astype(np.float32)
    expected = {("s0", 128): wins[0], ("s0", 256): wins[1], ("s1", 128): wins[2]}
    logits = _Ref().logits(wins)
    log = [(["s0", "s1"], np.array([128, 128]), logits[[0, 2]]),
           (["s0", "s9"], np.array([256, 128]), logits[[1, 3]])]
    got, chosen = check.collect(log, {"s0", "s1"})
    assert chosen == {}
    nums = check.numbers(got, expected, _Ref(), LIMITS)
    assert {k: nums[k] for k in FLEET_NAMES} == {
        "logit_max_abs_gap": 0.0, "missing_predictions": 0, "extra_predictions": 0}
    assert "choice_mismatches" not in nums
    assert nums["due"] == 3 and list(nums["gaps"]) == [0.0] * 3
    assert check.verdict(nums, LIMITS)[::2] == (True, 0)
    # one logit one ulp off, one prediction missing, one emitted twice
    bad = [(s, st, lg.copy()) for s, st, lg in log]
    bad[0][2][0, 0] = np.nextafter(bad[0][2][0, 0], np.float32(np.inf))
    nums = check.numbers(check.collect(bad[:1] + bad[:1], {"s0", "s1"})[0], expected,
                         _Ref(), LIMITS)
    assert nums["logit_max_abs_gap"] > 0 and nums["missing_predictions"] == 1
    assert nums["extra_predictions"] == 2
    ok, checks, failed = check.verdict(nums, LIMITS)
    assert not ok and set(checks) == set(FLEET_NAMES) and failed == 2


class _LastRow:
    """Logits of a token sequence: row ``seq[-1]`` of a fixed table."""
    TABLE = np.random.default_rng(1).normal(size=(5, 5)).astype(np.float32)

    def logits(self, seqs):
        return [self.TABLE[s[-1]] for s in seqs]


@pytest.mark.parametrize("plant,mismatches,failed", [
    (None, 0, 0), ("second-best", 1, 1), ("no-choice", 2, 2), ("out-of-range", 1, 1),
    ("nan", 1, 1)])
def test_check_choices(plant, mismatches, failed):
    """Prompts of different lengths, the reference's inputs built from the
    emitted tokens, and each emitted token held to greedy decode (argmax)."""
    ref = _LastRow()
    seqs = {("a", 0): [1, 2, 3], ("b", 0): [4]}
    rows = np.stack(ref.logits(list(seqs.values())))
    toks = rows.argmax(1)
    seqs.update({("a", 1): [1, 2, 3, toks[0]], ("b", 1): [4, toks[1]]})
    nxt = np.stack(ref.logits([seqs[("a", 1)], seqs[("b", 1)]]))
    log = [(["a", "b"], [0, 0], rows, toks), (["a", "b"], [1, 1], nxt, nxt.argmax(1))]
    if plant == "second-best":
        log[1] = log[1][:3] + (np.array([np.argsort(nxt[0])[-2], nxt[1].argmax()]),)
    elif plant == "no-choice":
        log[1] = log[1][:3]
    elif plant == "out-of-range":
        log[1] = log[1][:3] + (np.array([99, nxt[1].argmax()]),)
    elif plant == "nan":
        log[0] = (log[0][0], log[0][1], rows.copy(), toks)
        log[0][2][1, 2] = np.nan
    limits = dict(LIMITS, choice_mismatches=0)
    got, chosen = check.collect(log, {"a", "b"})
    nums = check.numbers(got, seqs, ref, limits, chosen)
    ok, checks, n_failed = check.verdict(nums, limits)
    assert set(checks) == set(check.NAMES)
    assert checks["choice_mismatches"]["value"] == mismatches
    assert np.isnan(nums["logit_max_abs_gap"]) == (plant == "nan")
    assert ok == (plant is None) and n_failed == failed
