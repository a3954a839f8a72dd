"""Moonlight-16B-A3B (DeepSeek-V3 block) served by the LM engine, against the
benchmark's plain float32 reference (``bench/references/moonlight.py``,
loaded by path, so the repository keeps one reference): prefill into a
slot, then slotted decode through the latent cache, at a reduced size in
float32 on seeded weights.  The dropless router keeps every token when all
of them pick one expert, and each planted fault of the block is caught."""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import transformer as T
from repro.serve import engine as E
from repro.serve.engine import Engine, ServeConfig

REF_PATH = os.path.join(os.path.dirname(__file__), "..", "bench", "references", "moonlight.py")
TOL = 1e-4          # f32 program against the f32 reference: rounding only


def _reference_module():
    spec = importlib.util.spec_from_file_location("moonlight_reference", REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


R = _reference_module()


def _config():
    return C.reduced(C.get("moonlight-16b-a3b"), compute_dtype="float32")


def _ref_cfg(mc, bias_std=0.5):
    """The reference's configuration keys (the published config.json names)."""
    return {"hidden_size": mc.d_model, "vocab_size": mc.vocab_size,
            "num_hidden_layers": mc.num_layers, "first_k_dense_replace": mc.first_k_dense_replace,
            "num_attention_heads": mc.num_heads, "kv_lora_rank": mc.kv_lora_rank,
            "qk_nope_head_dim": mc.qk_nope_head_dim, "qk_rope_head_dim": mc.qk_rope_head_dim,
            "v_head_dim": mc.v_head_dim, "intermediate_size": mc.d_ff,
            "moe_intermediate_size": mc.moe_d_ff, "n_routed_experts": mc.num_experts,
            "num_experts_per_tok": mc.top_k, "n_shared_experts": mc.n_shared_experts,
            "rms_norm_eps": mc.norm_eps, "rope_theta": mc.rope_theta,
            "routed_scaling_factor": mc.routed_scaling_factor,
            "norm_topk_prob": mc.norm_topk_prob,
            "init": {"attn_score_std": 1.0, "e_bias_std": bias_std},
            "check": {"route_delta": 0.0}}


@pytest.fixture(autouse=True)
def _small_buckets(monkeypatch):
    """Prefill buckets of 8 positions at the tests' 32 (512 when serving)."""
    monkeypatch.setattr(E, "PREFILL_BUCKET", 8)


PROMPTS = [np.random.default_rng(1).integers(0, 128, n).astype(np.int32) for n in (11, 5, 17)]
NEW = 6


def _setup(seed=0):
    mc = _config()
    rc = _ref_cfg(mc)
    return mc, rc, R.make_params(rc, np.random.SeedSequence(seed))


def _serve(mc, params, prompts=PROMPTS, *, slots=2):
    """Every step's (token, logits, routes) per prompt, served through
    bucketed prefill and slotted decode over fewer slots than prompts."""
    eng = Engine(mc, params, ServeConfig(max_len=32, max_slots=slots, max_prompt=24))
    rids = [eng.submit(p, NEW, return_logits=True) for p in prompts]
    eng.run()
    outs = eng.drain_step_outputs()
    return [[o for o in outs if o.request_id == rid] for rid in rids], eng


def _gap(mc, rc, params, served, prompts=PROMPTS, *, follow_routes=False):
    """Widest |program - reference| logit over every served step; the
    reference either routes itself or follows the program's routes."""
    ref = R.Reference(rc, params)
    gap = 0.0
    for prompt, steps in zip(prompts, served):
        assert [o.step for o in steps] == list(range(NEW))
        toks = np.concatenate([prompt, [o.token for o in steps[:-1]]])
        routes = np.concatenate([o.routes for o in steps]) if follow_routes else None
        inputs = [{"request": "r", "tokens": toks[:len(prompt) + k],
                   "routes": None if routes is None else routes[:len(prompt) + k]}
                  for k in range(NEW)]
        want = ref.logits(inputs)
        for o, w in zip(steps, want):
            gap = max(gap, float(np.max(np.abs(o.logits - w))))
    return gap


def test_engine_prefill_and_latent_decode_match_the_reference():
    mc, rc, params = _setup()
    served, eng = _serve(mc, params)
    # r + rope values a position and layer: c_kv beside k_pe folded by halves
    assert eng.cache["ckv"].shape == (mc.num_layers, 2, 32, mc.kv_lora_rank)
    assert eng.cache["kpe"].shape == (mc.num_layers, 2, 16, 2 * mc.qk_rope_head_dim)
    assert _gap(mc, rc, params, served) < TOL
    st = eng.stats()
    assert st["prefill_tokens"] == sum(len(p) for p in PROMPTS)
    assert st["prefill_padded_tokens"] == 16 + 8 + 24
    assert st["decode_rows"] == len(PROMPTS) * (NEW - 1)
    moe = eng.moe_stats()
    n_moe = mc.num_layers - mc.first_k_dense_replace
    assert moe["expert_tokens"].sum() == n_moe * mc.top_k * (
        st["prefill_tokens"] + st["decode_rows"])


def test_reference_follows_admissible_program_routes():
    mc, rc, params = _setup()
    served, _ = _serve(mc, params)
    assert _gap(mc, rc, params, served, follow_routes=True) < TOL
    assert R.LAST_STATS["flips"] == 0 and R.LAST_STATS["max_inversion"] < 0


def test_one_expert_for_every_token_drops_nothing():
    """The correction bias sends every token to the same experts: capacity
    1.25 would keep a fraction of them; the dropless layer keeps all."""
    mc, rc, params = _setup()
    bias = np.zeros(params["blocks"]["moe"]["e_bias"].shape, np.float32)
    bias[:, :mc.top_k] = 100.0
    params = {**params, "blocks": {**params["blocks"], "moe": {
        **params["blocks"]["moe"], "e_bias": jnp.asarray(bias)}}}
    served, eng = _serve(mc, params)
    load = eng.moe_stats()["expert_tokens"]
    n = eng.stats()["prefill_tokens"] + eng.stats()["decode_rows"]
    assert (load[:, :mc.top_k] == n).all() and (load[:, mc.top_k:] == 0).all()
    assert M.capacity(max(len(p) for p in PROMPTS), mc.top_k, mc.num_experts, 1.25) \
        < max(len(p) for p in PROMPTS)
    assert _gap(mc, rc, params, served) < TOL


def _softmax_routing(p, x, *, top_k, norm_topk_prob=True, scaling=1.0):
    logits = L.dense_apply(p["router"], x.astype(jnp.float32), compute_dtype=jnp.float32)
    scores = jax.nn.softmax(logits, -1)
    _, ids = jax.lax.top_k(scores + p["e_bias"], top_k)
    w = jnp.take_along_axis(scores, ids, -1)
    return w / w.sum(-1, keepdims=True) * scaling, ids.astype(jnp.int32)


def _no_bias(orig):
    return lambda p, x, **kw: orig({**p, "e_bias": jnp.zeros_like(p["e_bias"])}, x, **kw)


def _no_shared(orig):
    return lambda p, *a, **kw: orig({k: v for k, v in p.items() if k != "shared"}, *a, **kw)


FAULTS = {
    "softmax-routing": lambda mp: mp.setattr(M, "route_sigmoid", _softmax_routing),
    "no-bias": lambda mp: mp.setattr(M, "route_sigmoid", _no_bias(M.route_sigmoid)),
    "no-shared-expert": lambda mp: mp.setattr(M, "moe_dropless", _no_shared(M.moe_dropless)),
    "rotate-half-rope": lambda mp: mp.setattr(A, "rope_interleaved", L.apply_rope),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_in_the_block_fails(monkeypatch, fault):
    mc, rc, params = _setup()
    FAULTS[fault](monkeypatch)
    served, _ = _serve(mc, params)
    assert _gap(mc, rc, params, served) > 100 * TOL


def test_planted_unscaled_routing_fails():
    mc, rc, params = _setup()
    served, _ = _serve(dataclasses.replace(mc, routed_scaling_factor=1.0), params)
    assert _gap(mc, rc, params, served) > 100 * TOL


def test_planted_moe_layer_zero_fails(monkeypatch):
    """Layer 0 run as an MoE layer (the first MoE layer's weights) in place
    of the dense MLP."""
    mc, rc, params = _setup()
    monkeypatch.setattr(T, "_n_dense", lambda cfg: 0)
    blocks = jax.tree.map(lambda a: jnp.concatenate([a[:1], a]), params["blocks"])
    blocks["attn"] = jax.tree.map(lambda d, b: jnp.concatenate([d, b[1:]]),
                                  params["dense"]["attn"], blocks["attn"])
    rest = {k: v for k, v in params.items() if k != "dense"}
    served, _ = _serve(mc, {**rest, "blocks": blocks})
    assert _gap(mc, rc, params, served) > 100 * TOL


def test_planted_fp8_latent_cache_fails(monkeypatch):
    mc, rc, params = _setup()
    orig = T.init_slot_cache
    monkeypatch.setattr(T, "init_slot_cache", lambda cfg, n, m, dtype=None: orig(
        cfg, n, m, dtype=jnp.float8_e4m3fn))
    served, eng = _serve(mc, params)
    assert eng.cache["ckv"].dtype == eng.cache["kpe"].dtype == jnp.float8_e4m3fn
    assert _gap(mc, rc, params, served) > 100 * TOL


def test_prompt_padding_needs_an_attention_only_cache():
    mc = C.reduced(C.get("mamba2-780m"))
    with pytest.raises(ValueError, match="attention-only"):
        Engine(mc, T.init(mc, jax.random.PRNGKey(0)),
               ServeConfig(max_len=32, max_slots=2, max_prompt=24))


def test_prompt_padding_refuses_a_capacity_moe():
    """Padded positions would take expert capacity from real tokens."""
    mc = C.reduced(C.get("olmoe-1b-7b"))
    assert not mc.moe_dropless
    with pytest.raises(ValueError, match="MoE capacity"):
        Engine(mc, T.init(mc, jax.random.PRNGKey(0)),
               ServeConfig(max_len=32, max_slots=2, max_prompt=24))


def test_reference_layout_is_the_programs():
    """make_params draws exactly the tree the program's init builds."""
    mc, rc, params = _setup()
    want = jax.eval_shape(lambda k: T.init(dataclasses.replace(mc, param_dtype="bfloat16"), k),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.leaves(got) == jax.tree.leaves(want)
