"""Whole runs of a cell on the CPU at a tiny size (the Pallas step
interpreted): a sound run passes the comparison, the Q7 control and each
fault planted under the timed path fail it, and a mix, a metric, a
generator and a whole generative cell found only in another directory are
used by name."""
from __future__ import annotations

import copy
import json
import time

import numpy as np
import pytest

from conftest import CPU_GAP_LIMIT, ROOT, load_json, run_tiny, tiny_bench

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

    def __init__(self, mix, model, seed_seq):
        rng = np.random.default_rng(seed_seq)
        self.window, n = model["window"], mix["streams"]
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

    def expected(self, last_tick, emitted):
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


# A generative cell of its own kind: a seeded bigram language model served
# by greedy decode, with its system, reference, generator and work count,
# and a configuration with no window.
TOY_FILES = {
    "systems/toy_bigram.py": '''
import numpy as np


class System:
    """Greedy decode of a bigram table, one token a tick for every live
    sequence.  ``serving.fault`` plants a fault where a token is produced:
    "logit" moves one emitted logit by 1e-3, "token" emits (and goes on
    from) the second-best token once."""

    def __init__(self, cfg, params, *, slots, ring, bits=None, trace=False):
        self.table = np.asarray(params["table"], np.float32)
        self.fault = cfg["serving"]["fault"]
        self.live = {}              # id -> [last token, step, tokens left]
        self.stream_steps = 0

    def attach(self, ids):
        return np.zeros(len(ids), np.int64)

    def submit(self, sid, prompt, new_tokens, first_step):
        self.live[sid] = [int(prompt[-1]), first_step, new_tokens]

    def step(self):
        ids = sorted(self.live)
        if not ids:
            return []
        logits = self.table[[self.live[s][0] for s in ids]]
        toks = logits.argmax(1)
        if self.fault == "logit":
            logits[0, logits[0].argmin()] += np.float32(1e-3)
        elif self.fault == "token":
            toks[0] = np.argsort(logits[0])[-2]
        self.fault = None
        steps = [self.live[s][1] for s in ids]
        for sid, tok in zip(ids, toks):
            e = self.live[sid]
            e[0], e[1], e[2] = int(tok), e[1] + 1, e[2] - 1
            if not e[2]:
                del self.live[sid]
        self.stream_steps += len(ids)
        return [(ids, steps, logits, toks)]

    def sync(self):
        pass

    def counters(self):
        return {"stream_steps": self.stream_steps}

    def span_totals(self):
        return {}

    def close(self):
        self.live = None
''',
    "references/toy_bigram.py": '''
import numpy as np


def make_params(cfg, seed_seq):
    v = cfg["model"]["vocab"]
    return {"table": np.random.default_rng(seed_seq).normal(size=(v, v)).astype(np.float32)}


class Reference:
    def __init__(self, cfg, params):
        self.table = np.asarray(params["table"], np.float32)

    def logits(self, seqs):
        """Token sequences of any length -> the next token's logits."""
        return [self.table[int(seq[-1])] for seq in seqs]
''',
    "generators/toy_decode.py": '''
import numpy as np


class Generator:
    """Slot i serves request r on ticks r L .. r L + L - 1, one new token a
    tick; a slot's step is the tick.  Prompt lengths cycle over the mix's
    list, so every seed has the same lengths in another order."""

    def __init__(self, mix, model, seed_seq):
        self.rng = np.random.default_rng(seed_seq)
        self.L, self.lens, self.vocab = mix["new_tokens"], mix["prompt_lens"], model["vocab"]
        self.ids = [f"slot{i}" for i in range(mix["slots"])]
        self.capacity, self.max_buffered, self.rollin_ticks = len(self.ids), 1, 0
        self.check_ids = set(self.ids)
        self.prompts = {}               # (slot, request) -> prompt

    def setup(self, system):
        system.attach(self.ids)

    def prepare(self, tick):
        if tick % self.L:
            return []
        r = tick // self.L
        for i in range(len(self.ids)):
            n = self.lens[(i + r) % len(self.lens)]
            self.prompts[(i, r)] = self.rng.integers(0, self.vocab, n)
        return [(self.ids[i], self.prompts[(i, r)], tick) for i in range(len(self.ids))]

    def drive(self, system, batch):
        for sid, prompt, first in batch:
            system.submit(sid, prompt, self.L, first)

    def expected(self, last_tick, emitted):
        """Step k's input is the prompt and the program's first k tokens; a
        token never emitted is already missing, and 0 stands in for it."""
        out = {}
        for (i, r), prompt in self.prompts.items():
            seq = list(prompt)
            for k in range(min(self.L, last_tick - r * self.L + 1)):
                key = (self.ids[i], r * self.L + k)
                out[key] = np.array(seq)
                seq.append(emitted.get(key, 0))
        return out
''',
    "work/toy_bigram.py": '''
def count(model, counters):
    return {"flops": 0, "hbm_bytes": 4 * model["vocab"] * counters["stream_steps"]}
''',
}
TOY_FILES["traffic/toy-decode.json"] = json.dumps(
    {"name": "toy-decode", "generator": "toy_decode", "loop": "open", "tick_hz": 100.0,
     "slots": 6, "prompt_lens": [1, 2, 5, 9], "new_tokens": 7})
TOY_CHECK = {"logit_max_abs_gap": 0.0, "missing_predictions": 0, "extra_predictions": 0,
             "choice_mismatches": 0}


def _toy_run(tmp_path, fault=None):
    import harness
    for rel, text in TOY_FILES.items():
        (tmp_path / rel).parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / rel).write_text(text)
    cfg = tmp_path / "toy-bigram.json"
    cfg.write_text(json.dumps({
        "model": {"cell": "toy_bigram", "vocab": 48}, "reference": "toy_bigram",
        "serving": {"system": "toy_bigram", "fault": fault}, "check": TOY_CHECK}))
    spec = copy.deepcopy(load_json("BENCHMARK.json"))
    spec["configs"].append({"name": "toy-bigram", "source": "test", "file": str(cfg),
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "toy.decode", "config": "toy-bigram",
                              "traffic": "toy-decode", "chips": 1, "why": "test"})
    bench = harness.Bench(ROOT, spec=spec, search=[str(tmp_path)])
    return bench.run("toy.decode", 2**31 + 5, 1.0, False, t_start=time.perf_counter(),
                     require_chip=False)


def test_generative_cell_is_found_by_name(tmp_path):
    res = _toy_run(tmp_path)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 6 * 100 and res["failed"] == 0
    assert res["run"]["predictions_compared"] == res["attempted"]
    assert set(res["checks"]) == set(TOY_CHECK)
    assert set(res["metrics"]) == {"stream_steps_per_s", "setup_s"}


@pytest.mark.parametrize("fault,gap,mismatches", [("logit", 1e-3, 0), ("token", 0.0, 1)])
def test_generative_cell_planted_fault_fails(tmp_path, fault, gap, mismatches):
    """A logit moved by 1e-3 shows in the gap; a token its own logits did
    not choose, which the reference then follows, only in the choices."""
    res = _toy_run(tmp_path, fault)
    assert not res["correct"] and res["failed"] == 1
    assert res["checks"]["logit_max_abs_gap"]["value"] == pytest.approx(gap, rel=1e-3)
    assert res["checks"]["choice_mismatches"]["value"] == mismatches
