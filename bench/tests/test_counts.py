"""Work counts, the traffic schedule and the comparison, at tiny sizes."""
from __future__ import annotations

import os

import numpy as np
import pytest

import check
import harness
import work
from conftest import BENCH, TINY, load_json

T = harness._load_module(os.path.join(BENCH, "generators", "staggered.py"))

CONFIGS = {"fastgrnn-har-lowrank-q15": 748, "fastgrnn-har-fullrank-q15": 768}


@pytest.mark.parametrize("name,flops", sorted(CONFIGS.items()))
def test_work_per_stream_step(name, flops):
    cfg = load_json(f"bench/configs/{name}.json")
    w = work.per_stream_step(cfg["model"])
    assert w == {"flops": flops, "hbm_bytes": 140}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_work_matches_the_programs_own_count(name):
    import harness
    from repro.compress import ModelArtifact, default_deploy_pipeline
    from repro.kernels.fastgrnn_cell.ops import Q15StreamStep
    cfg = load_json(f"bench/configs/{name}.json")
    ref = harness._load_module(os.path.join(BENCH, "references", "fastgrnn_q15.py"))
    params = ref.make_params(cfg, np.random.SeedSequence(1))
    art = default_deploy_pipeline().run(ModelArtifact.from_params(params))
    prog = Q15StreamStep(art.qp).work_per_stream_step()
    w = work.per_stream_step(cfg["model"])
    assert (prog["model_flops_per_stream_step"], prog["hbm_bytes_per_stream_step"]) \
        == (w["flops"], w["hbm_bytes"])


def _schedule(mix, ticks, seed=5, shards=8):
    tr = T.Generator(mix, 128, np.random.SeedSequence(seed))
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
    tr = T.Generator(mix, 128, np.random.SeedSequence(9))
    shard_of = np.random.default_rng(0).integers(0, 8, 1024)
    tr.assign_cohorts(shard_of)
    for s in range(8):
        counts = np.bincount(tr.cohort[shard_of == s], minlength=128)
        assert counts.max() - counts.min() <= 1


def test_same_seed_same_inputs_other_seed_other_samples():
    a = T.Generator(TINY, 128, np.random.SeedSequence(2**31 + 11))
    b = T.Generator(TINY, 128, np.random.SeedSequence(2**31 + 11))
    c = T.Generator(TINY, 128, np.random.SeedSequence(2**31 + 12))
    for tr in (a, b, c):
        tr.assign_cohorts(np.arange(128) % 8)
    idx = np.arange(128)
    assert np.array_equal(a.packets(idx), b.packets(idx))
    assert not np.array_equal(a.packets(idx), c.packets(idx))
    assert len(a.feeds(200)) == len(c.feeds(200))


def test_packets_play_the_streams_windows_in_order():
    tr = T.Generator(TINY, 128, np.random.SeedSequence(4))
    tr.assign_cohorts(np.zeros(128, int))
    i = np.array([7])
    got = np.concatenate([tr.packets(i)[0] for _ in range(128 // 4 * 2)])
    want = tr.pool[tr.window_index(7, np.arange(2))].reshape(256, 3)
    assert np.array_equal(got, want)


class _Ref:
    def logits(self, windows):
        return windows.sum(axis=1)[:, :2].astype(np.float32)


def test_expected_predictions_follow_the_cohorts():
    tr = T.Generator(TINY, 16, np.random.SeedSequence(6))
    tr.assign_cohorts(np.arange(128) % 8)
    exp = tr.expected(40)
    assert {sid for sid, _ in exp} <= tr.check_ids
    for i in tr.check:
        n = (40 - tr.cohort[i] + 1) // 16
        steps = sorted(st for sid, st in exp if sid == tr.ids[i])
        assert steps == [16 * (k + 1) for k in range(n)]
        for k in range(n):
            assert np.array_equal(exp[(tr.ids[i], 16 * (k + 1))],
                                  tr.pool[tr.window_index(i, k)])


def test_check_numbers_and_verdict():
    wins = np.random.default_rng(0).normal(size=(4, 128, 3)).astype(np.float32)
    expected = {("s0", 128): wins[0], ("s0", 256): wins[1], ("s1", 128): wins[2]}
    logits = _Ref().logits(wins)
    log = [(["s0", "s1"], np.array([128, 128]), logits[[0, 2]]),
           (["s0", "s9"], np.array([256, 128]), logits[[1, 3]])]
    got = check.collect(log, {"s0", "s1"})
    nums = check.numbers(got, expected, _Ref())
    assert {k: nums[k] for k in check.NAMES} == {
        "logit_max_abs_gap": 0.0, "missing_predictions": 0, "extra_predictions": 0}
    assert nums["due"] == 3 and list(nums["gaps"]) == [0.0] * 3
    limits = {"logit_max_abs_gap": 0.0, "missing_predictions": 0, "extra_predictions": 0}
    assert check.verdict(nums, limits)[::2] == (True, 0)
    # one logit one ulp off, one prediction missing, one emitted twice
    bad = [(s, st, lg.copy()) for s, st, lg in log]
    bad[0][2][0, 0] = np.nextafter(bad[0][2][0, 0], np.float32(np.inf))
    nums = check.numbers(check.collect(bad[:1] + bad[:1], {"s0", "s1"}), expected, _Ref())
    assert nums["logit_max_abs_gap"] > 0 and nums["missing_predictions"] == 1
    assert nums["extra_predictions"] == 2
    ok, checks, failed = check.verdict(nums, limits)
    assert not ok and set(checks) == set(check.NAMES) and failed == 2
