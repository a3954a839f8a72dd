"""Per-architecture smoke tests (assignment requirement): a REDUCED config
of the same family runs one forward/train step on CPU, asserting output
shapes and finiteness.  The FULL configs are exercised only via the
dry-run (launch/dryrun.py, ShapeDtypeStruct — no allocation)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models import transformer as T

ARCHS = list(C.ARCHS)


def _batch(cfg, B=2, S=16):
    rng = np.random.default_rng(0)
    b = {}
    if cfg.family == "audio":
        b["frames"] = jnp.asarray(rng.normal(size=(B, S, cfg.d_model)), jnp.float32)
    else:
        b["tokens"] = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    b["labels"] = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    if cfg.family == "vlm":
        b["patch_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.num_patches, cfg.d_model)), jnp.float32)
    return b


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_train_step(arch):
    cfg = C.reduced(C.get(arch))
    params = T.init(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    (loss, metrics), grads = jax.jit(
        lambda p, b: jax.value_and_grad(
            lambda q: T.train_loss(cfg, q, b), has_aux=True)(p))(params, batch)
    assert np.isfinite(float(loss)), arch
    gn = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0, arch


@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if C.get(a).has_decode])
def test_reduced_decode_step(arch):
    cfg = C.reduced(C.get(arch))
    params = T.init(cfg, jax.random.PRNGKey(0))
    B = 2
    cache = T.init_cache(cfg, B, 24)
    logits, cache2 = jax.jit(lambda p, c, t: T.decode_step(cfg, p, c, t))(
        params, cache, jnp.ones((B, 1), jnp.int32))
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))
    assert int(cache2["len"]) == 1


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_forward_shapes(arch):
    cfg = C.reduced(C.get(arch))
    params = T.init(cfg, jax.random.PRNGKey(1))
    B, S = 2, 16
    batch = _batch(cfg, B, S)
    logits, aux, _ = T.forward(cfg, params, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))


def test_full_config_parameter_counts_sane():
    """Full configs are never materialized — but eval_shape param counts
    must land in the right ballpark for each architecture."""
    from repro.models import registry
    expected = {  # billions, loose bands from the source papers
        "minitron-4b": (3.5, 5.5), "qwen2-1.5b": (1.2, 2.0),
        "deepseek-7b": (6.0, 8.0), "nemotron-4-340b": (300, 380),
        "olmoe-1b-7b": (6.0, 8.0), "moonlight-16b-a3b": (15.9, 16.0),
        "internvl2-76b": (65, 80), "zamba2-1.2b": (0.9, 1.6),
        "hubert-xlarge": (0.7, 1.3), "mamba2-780m": (0.6, 1.0),
    }
    for arch, (lo, hi) in expected.items():
        n = registry.param_count(C.get(arch)) / 1e9
        assert lo <= n <= hi, (arch, n)
