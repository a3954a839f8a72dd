"""Whole runs of a cell on the CPU at a tiny size (the Pallas step
interpreted): a sound run passes the comparison, the Q7 control and each
fault planted under the timed path fail it, and a mix and a metric found
only in another directory are used by name."""
from __future__ import annotations

import numpy as np
import pytest

from conftest import CPU_GAP_LIMIT, run_tiny, tiny_bench

LOW = "fastgrnn-har-lowrank-q15"


def test_sound_run_is_correct(tmp_path):
    res = run_tiny(tiny_bench(tmp_path, LOW), LOW)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["run"]["compiles_in_window"] == 0
    assert set(res["metrics"]) == {"stream_steps_per_s", "setup_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("config", ["fastgrnn-har-lowrank-q15", "fastgrnn-har-fullrank-q15"])
def test_q7_control_fails(tmp_path, config):
    """The program's own Q7 path in place of Q15: the precision below the
    configuration's."""
    res = run_tiny(tiny_bench(tmp_path, config), config, bits=7)
    assert not res["correct"]
    assert res["checks"]["logit_max_abs_gap"]["value"] > 100 * CPU_GAP_LIMIT


def _unchanged(orig):
    return lambda self, h, x, active: h


def _half_batch(orig):
    def step(self, h, x, active):
        active = np.asarray(active, bool).copy()
        active[len(active) // 2:] = False
        return orig(self, h, x, active)
    return step


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state-unchanged", "half-batch"])
def test_faults_in_the_step_fail(tmp_path, monkeypatch, fault):
    from repro.kernels.fastgrnn_cell.ops import Q15StreamStep
    monkeypatch.setattr(Q15StreamStep, "step_resident",
                        fault(Q15StreamStep.step_resident))
    res = run_tiny(tiny_bench(tmp_path, LOW), LOW)
    assert not res["correct"], res["checks"]


def test_altered_answer_fails(tmp_path, monkeypatch):
    from repro.kernels.fastgrnn_cell.ops import Q15StreamStep
    orig = Q15StreamStep.head_logits

    def head(self, h):
        out = orig(self, h).copy()
        out[0, 0] += np.float32(1e-3)
        return out
    monkeypatch.setattr(Q15StreamStep, "head_logits", head)
    res = run_tiny(tiny_bench(tmp_path, LOW), LOW)
    assert not res["correct"]
    assert res["checks"]["logit_max_abs_gap"]["value"] >= 9e-4


def test_new_mix_and_metric_are_found_by_name(tmp_path):
    extra = tmp_path / "extra"
    (extra / "metrics").mkdir(parents=True)
    (extra / "metrics" / "throwaway.ticks_seen.py").write_text(
        "def read(ctx):\n    return float(ctx['ticks'])\n")
    mix = dict(__import__("conftest").TINY, name="throwaway", packet_samples=2)
    bench = tiny_bench(tmp_path, LOW, mix=mix, search=[str(extra)])
    bench.spec["per_layer"].append(
        {"name": "throwaway.ticks_seen", "unit": "ticks", "better": "higher",
         "source": "host_clock", "layer": "test", "moves": "stream_steps_per_s",
         "workloads": ["tiny." + LOW]})
    res = run_tiny(bench, LOW, trace=True)
    assert res["metrics"]["throwaway.ticks_seen"]["value"] == res["run"]["ticks_in_window"]
    assert res["correct"]
    assert {"fleet.dispatch_ms_per_tick", "engine.gather_ms_per_tick",
            "engine.emit_ms_per_tick", "step.h2d_bytes_per_stream_step",
            "tick.latency_ms_p99"} <= set(res["metrics"])


def test_lockstep_mix_is_data_only(tmp_path):
    """Every window ends on the same tick: the staggered generator with one
    window phase, read from a mix file alone."""
    from conftest import TINY
    mix = dict(TINY, name="lockstep", window_phases=1)
    res = run_tiny(tiny_bench(tmp_path, LOW, mix=mix), LOW)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0


CHURN = '''
import numpy as np
import synth_hapt


class Generator:
    """One-sample packets to every stream on every tick; on tick 5 one
    stream leaves and a new one joins."""
    JOIN = 5

    def __init__(self, mix, window, seed_seq):
        rng = np.random.default_rng(seed_seq)
        self.window, n = window, mix["streams"]
        self.ids = [f"s{i}" for i in range(n)] + ["newcomer"]
        self.pool = synth_hapt.windows("test", int(rng.integers(2**31)), n + 1)
        self.capacity, self.max_buffered, self.rollin_ticks = n + 8, 1, 0
        self.check_ids = set(self.ids[1:])
        self.start = np.zeros(n + 1, np.int64)
        self.start[n] = self.JOIN

    def setup(self, system):
        system.attach(self.ids[:-1])

    def prepare(self, tick):
        live = np.arange(len(self.ids))
        live = live[1:] if tick >= self.JOIN else live[:-1]
        return tick, live, self.pool[live, (tick - self.start[live]) % self.window]

    def drive(self, system, batch):
        tick, live, samples = batch
        if tick == self.JOIN:
            system.detach(self.ids[0])
            system.attach(self.ids[-1:])
        for i, x in zip(live, samples):
            system.feed(self.ids[i], x[None])

    def expected(self, last_tick):
        out = {}
        for i, sid in enumerate(self.ids[1:], 1):
            for k in range((last_tick - self.start[i] + 1) // self.window):
                out[(sid, (k + 1) * self.window)] = self.pool[i]
        return out
'''


def test_new_generator_is_found_by_name(tmp_path):
    """A mix that names a generator of its own, which attaches and detaches
    streams inside the window, runs without an edit to the harness."""
    from conftest import TINY
    extra = tmp_path / "extra"
    (extra / "generators").mkdir(parents=True)
    (extra / "generators" / "churn.py").write_text(CHURN)
    mix = dict(TINY, name="churn", generator="churn")
    res = run_tiny(tiny_bench(tmp_path, LOW, mix=mix, search=[str(extra)]), LOW)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 127
