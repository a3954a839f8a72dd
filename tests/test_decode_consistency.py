"""Serving-path correctness: token-by-token decode must reproduce the
full forward pass (dense/MoE/SSM/hybrid), and prefill->decode must be
continuous.  Run in f32 to make the comparison exact."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models import transformer as T


def _setup(arch):
    cfg = C.reduced(C.get(arch), compute_dtype="float32", param_dtype="float32")
    if cfg.family == "moe":   # no-drop so the oracle matches serving
        cfg = dataclasses.replace(cfg, capacity_factor=cfg.num_experts / cfg.top_k)
    params = T.init(cfg, jax.random.PRNGKey(0))
    toks = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 12))
    return cfg, params, toks


@pytest.mark.parametrize("arch", ["deepseek-7b", "olmoe-1b-7b",
                                  "moonlight-16b-a3b", "mamba2-780m",
                                  "zamba2-1.2b"])
def test_decode_matches_forward(arch):
    cfg, params, toks = _setup(arch)
    full, _, _ = T.forward(cfg, params, {"tokens": jnp.asarray(toks)})
    cache = T.init_cache(cfg, 2, 16, dtype=jnp.float32)
    step = jax.jit(lambda p, c, t: T.decode_step(cfg, p, c, t))
    for t in range(toks.shape[1]):
        lg, cache = step(params, cache, jnp.asarray(toks[:, t:t + 1]))
        err = float(jnp.max(jnp.abs(lg[:, 0] - full[:, t])))
        assert err < 1e-4, (arch, t, err)


@pytest.mark.parametrize("arch", ["deepseek-7b", "moonlight-16b-a3b",
                                  "mamba2-780m", "zamba2-1.2b"])
def test_prefill_then_decode_continuous(arch):
    cfg, params, toks = _setup(arch)
    full, _, _ = T.forward(cfg, params, {"tokens": jnp.asarray(toks)})
    half = 6
    _, cache = T.prefill(cfg, params, {"tokens": jnp.asarray(toks[:, :half])},
                         max_len=16)
    step = jax.jit(lambda p, c, t: T.decode_step(cfg, p, c, t))
    for t in range(half, toks.shape[1]):
        lg, cache = step(params, cache, jnp.asarray(toks[:, t:t + 1]))
        err = float(jnp.max(jnp.abs(lg[:, 0] - full[:, t])))
        assert err < 1e-4, (arch, t, err)


def test_sliding_window_decode_matches_windowed_forward():
    cfg, params, toks = _setup("zamba2-1.2b")
    w = 4
    full, _, _ = T.forward(cfg, params, {"tokens": jnp.asarray(toks)}, window=w)
    cache = T.init_cache(cfg, 2, 16, dtype=jnp.float32)
    step = jax.jit(lambda p, c, t: T.decode_step(cfg, p, c, t, window=w))
    for t in range(toks.shape[1]):
        lg, cache = step(params, cache, jnp.asarray(toks[:, t:t + 1]))
        err = float(jnp.max(jnp.abs(lg[:, 0] - full[:, t])))
        assert err < 1e-4, (t, err)


def test_serving_engine_generate():
    from repro.serve.engine import Engine, ServeConfig
    cfg, params, toks = _setup("deepseek-7b")
    eng = Engine(cfg, params, ServeConfig(max_len=32))
    out = eng.generate(toks[:, :6], max_new=5)
    assert out.shape == (2, 5)
    assert (out >= 0).all() and (out < cfg.vocab_size).all()


def test_quantized_serving_engine_close_to_fp():
    from repro.serve.engine import Engine, ServeConfig
    cfg, params, toks = _setup("deepseek-7b")
    fp = Engine(cfg, params, ServeConfig(max_len=32))
    q8 = Engine(cfg, params, ServeConfig(max_len=32, quant_bits=8))
    a = fp.generate(toks[:, :6], max_new=4)
    b = q8.generate(toks[:, :6], max_new=4)
    # random-init logits are near-uniform; just require the quantized
    # engine runs and emits valid tokens (accuracy tested on trained HAR)
    assert b.shape == a.shape
