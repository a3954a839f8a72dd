"""The LM cell's own files (system, reference, generator, traffic, work count
and readers) in whole runs on the CPU, from a reduced copy of the
``moonlight-16b-a3b-5l`` configuration in float32: a sound run is correct,
and a planted inadmissible route fails it."""
from __future__ import annotations

import copy
import json
import os
import time

import numpy as np
import pytest

import harness
from conftest import BENCH, ROOT, load_json

CELL = "moonlight.backlog-8k"
SMALL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
             moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2,
             n_shared_experts=1, num_hidden_layers=3, vocab_size=128,
             max_position_embeddings=64)


def _tiny_lm(tmp_path):
    """The cell's configuration and mix cut to a CPU size, as cell tiny.lm."""
    spec = copy.deepcopy(load_json("BENCHMARK.json"))
    entry = next(w for w in spec["workloads"] if w["name"] == CELL)
    cfg = load_json(next(c["file"] for c in spec["configs"] if c["name"] == entry["config"]))
    cfg.update(SMALL)
    cfg["model"].update({k: v for k, v in SMALL.items() if k in cfg["model"]})
    cfg["serving"].update(compute_dtype="float32", max_prompt=24)
    # f32 program against the f32 reference: rounding only, and no route
    # may sit on the wrong side of the reference's own top-k boundary
    cfg["check"].update(logit_max_abs_gap=1e-4, route_delta=0.0)
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    mix = load_json(f"bench/traffic/{entry['traffic']}.json")
    mix.update(name="tiny-lm", slots=4, quantiles=8, check_requests=2,
               prompt={"median": 8, "sigma": 0.6, "min": 4, "max": 24},
               output={"median": 8, "sigma": 0.6, "min": 2, "max": 16})
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "tiny-lm.json").write_text(json.dumps(mix))
    spec["configs"].append({"name": "tiny-lm", "source": "test", "file": str(tmp_path / "cfg.json"),
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny.lm", "config": "tiny-lm", "traffic": "tiny-lm",
                              "chips": 1, "why": "test"})
    for m in spec["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny.lm")
    return harness.Bench(ROOT, spec=spec, search=[str(tmp_path)])


def _run(bench, trace=False):
    return bench.run("tiny.lm", 2**31 + 12345, 1.0, trace, t_start=time.perf_counter(),
                     require_chip=False)


def test_sound_lm_run_is_correct(tmp_path):
    res = _run(_tiny_lm(tmp_path), trace=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["run"]["compiles_in_window"] == 0
    assert set(res["checks"]) == {"logit_max_abs_gap", "missing_predictions",
                                  "extra_predictions", "choice_mismatches"}
    assert {"lm.prefill_ms_per_tick", "lm.decode_ms_per_tick", "lm.sample_wait_ms_per_tick",
            "moe.expert_load_max_over_mean"} <= set(res["metrics"])


def test_inadmissible_route_fails(tmp_path, monkeypatch):
    """The program takes the lowest-scored experts: the reference finds the
    sets inadmissible and answers NaN, which fails the check."""
    import jax
    from repro.models import moe as M
    orig = M.route_sigmoid

    def worst(p, x, **kw):
        w, _ = orig(p, x, **kw)
        scores = jax.nn.sigmoid(x.astype("float32") @ p["router"]["w"])
        _, ids = jax.lax.top_k(-(scores + p["e_bias"]), kw["top_k"])
        return w, ids.astype("int32")
    monkeypatch.setattr(M, "route_sigmoid", worst)
    res = _run(_tiny_lm(tmp_path))
    assert not res["correct"]
    assert np.isnan(res["checks"]["logit_max_abs_gap"]["value"])


def test_work_counts_the_real_work():
    cfg = load_json("bench/configs/moonlight-16b-a3b-5l.json")
    work = harness._load_module(os.path.join(BENCH, "work", cfg["model"]["cell"] + ".py"))
    one = {"prefill_tokens": 0, "prefill_attended_positions": 0, "prefills": 0,
           "decode_rows": 1, "decode_steps": 1, "attended_positions": 1000,
           "moe.experts_hit_prefill": 0, "moe.experts_hit_decode": 4 * 6}
    w = work.count(cfg["model"], one)
    # one decode row over 1000 positions: 5 layers of 576-wide latent rows
    assert w["mla_decode"]["hbm_bytes"] == 5 * 2 * 576 * 1000
    assert w["mla_decode"]["flops"] == 5 * 2 * 16 * (576 + 512) * 1000
    assert w["moe_experts"]["decode"]["flops"] == 2 * 3 * 2048 * 1408 * 4 * 6
    assert w["moe_experts"]["prefill"] == {"flops": 0, "hbm_bytes": 0}
    # per decoded token: the active body (about 2 x 0.42 G) and the head
    absorb = 5 * 2 * 16 * 512 * 256
    body = w["flops"] - w["mla_decode"]["flops"] - absorb - 2 * 2048 * 163840
    assert 0.80e9 < body < 0.86e9


def test_configuration_holds_the_published_config():
    """Every catalog number at the top level as published, but the depth;
    the model section repeats the widths the work count reads."""
    cfg = load_json("bench/configs/moonlight-16b-a3b-5l.json")
    spec = load_json("BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == ["num_hidden_layers"] and cfg["num_hidden_layers"] == 5
    assert all(cfg[k] == v for k, v in cfg["model"].items() if k != "cell")
    import repro.configs as C
    prog = C.get(cfg["serving"]["arch"])
    system = harness._load_module(os.path.join(BENCH, "systems", "lm_engine.py"))
    mc = system.model_config(cfg)
    assert {k: getattr(mc, a) for k, a in system.SHAPE_KEYS.items()} == \
        {k: getattr(prog, a) for k, a in system.SHAPE_KEYS.items()}


@pytest.mark.parametrize("name", ["mla_decode_roofline", "moe_experts_roofline"])
def test_roofline_reader_raises_when_no_op_matches(name):
    reader = harness._load_module(os.path.join(BENCH, "metrics", name + ".py"))
    trace = {"devices": 1, "ops": {"%fusion.1 = f32[8] fusion(...), metadata={op_name=\"mla_decode/moe_experts\"}": 1.0},
             "op_text": {}}
    with pytest.raises(LookupError):
        reader.read({"trace": trace, "peak": {"flops": 1.0, "hbm_bytes_per_s": 1.0}, "work": {}})
