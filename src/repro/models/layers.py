"""Shared neural building blocks for the LM zoo.

All modules are pure functions over explicit parameter pytrees (dicts), so
they compose with pjit/shard_map, jax.lax.scan over stacked layer params,
and the L-S-Q compression machinery (core/compression.py applies IHT masks
to these leaves; core/quantization.py quantizes them; low-rank Dense below
is the generalized  U = U1 @ U2^T  of the paper).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def truncated_normal(key, shape, std=0.02, dtype=jnp.float32):
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Dense (optionally low-rank factorized — the paper's W = W1 W2^T at LM scale)
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, *, bias: bool = False,
               rank: int | None = None, dtype=jnp.float32, std: float | None = None):
    std = std if std is not None else (1.0 / np.sqrt(d_in))
    if rank is None:
        p = {"w": truncated_normal(key, (d_in, d_out), std, dtype)}
    else:
        k1, k2 = jax.random.split(key)
        # product variance matched to the unfactored init
        s = float(np.sqrt(std / np.sqrt(rank)))
        p = {"w1": truncated_normal(k1, (d_in, rank), s, dtype),
             "w2": truncated_normal(k2, (rank, d_out), s, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense_apply(p, x, *, compute_dtype=jnp.bfloat16):
    x = x.astype(compute_dtype)
    if "w" in p:
        y = x @ p["w"].astype(compute_dtype)
    else:
        y = (x @ p["w1"].astype(compute_dtype)) @ p["w2"].astype(compute_dtype)
    if "b" in p:
        y = y + p["b"].astype(compute_dtype)
    return y


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm_apply(p, x, eps: float = 1e-5):
    """Mean-of-squares reduced in f32 (fuses into the reduction); the
    elementwise rescale stays in x.dtype.  Keeping a full f32 (B,S,D)
    intermediate here makes XLA store the remat carry stack in f32 —
    observed +5.6 GB/device on the 4k-train dry-run."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return (x * inv) * p["scale"].astype(x.dtype)


def layernorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm_apply(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 1e4):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float = 1e4):
    """x: (..., S, H, hd); positions: (..., S) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                          # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP variants
# ---------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, kind: str, *, bias: bool = False,
             rank: int | None = None, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    if kind in ("swiglu", "geglu"):
        return {"w_gate": dense_init(ks[0], d_model, d_ff, bias=bias, rank=rank, dtype=dtype),
                "w_in": dense_init(ks[1], d_model, d_ff, bias=bias, rank=rank, dtype=dtype),
                "w_out": dense_init(ks[2], d_ff, d_model, bias=bias, rank=rank, dtype=dtype)}
    # relu2 (squared ReLU, nemotron) / gelu (hubert, internvl ViT-style)
    return {"w_in": dense_init(ks[0], d_model, d_ff, bias=bias, rank=rank, dtype=dtype),
            "w_out": dense_init(ks[1], d_ff, d_model, bias=bias, rank=rank, dtype=dtype)}


def mlp_apply(p, x, kind: str, *, compute_dtype=jnp.bfloat16, act_override=None):
    if kind == "swiglu":
        act = act_override or jax.nn.silu
        h = act(dense_apply(p["w_gate"], x, compute_dtype=compute_dtype)) \
            * dense_apply(p["w_in"], x, compute_dtype=compute_dtype)
    elif kind == "geglu":
        act = act_override or jax.nn.gelu
        h = act(dense_apply(p["w_gate"], x, compute_dtype=compute_dtype)) \
            * dense_apply(p["w_in"], x, compute_dtype=compute_dtype)
    elif kind == "relu2":
        h = dense_apply(p["w_in"], x, compute_dtype=compute_dtype)
        h = jnp.square(jax.nn.relu(h))
    elif kind == "gelu":
        act = act_override or jax.nn.gelu
        h = act(dense_apply(p["w_in"], x, compute_dtype=compute_dtype))
    else:
        raise ValueError(f"unknown mlp kind {kind!r}")
    return dense_apply(p["w_out"], h, compute_dtype=compute_dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed_init(key, vocab: int, d_model: int, dtype=jnp.float32):
    return {"table": truncated_normal(key, (vocab, d_model), 0.02, dtype)}


def embed_apply(p, tokens, compute_dtype=jnp.bfloat16):
    return jnp.take(p["table"], tokens, axis=0).astype(compute_dtype)


def unembed_apply(p, x, compute_dtype=jnp.bfloat16):
    """Tied unembedding: logits = x @ table^T, f32 accumulation."""
    return jnp.einsum("...d,vd->...v", x.astype(compute_dtype),
                      p["table"].astype(compute_dtype),
                      preferred_element_type=jnp.float32)


def cross_entropy(logits, labels, z_loss: float = 1e-4):
    """Stable CE with optional z-loss; logits f32 (..., V), labels (...).

    The label pick uses a one-hot reduction, NOT take_along_axis: the
    scatter in take_along_axis's backward defeats GSPMD when V is
    TP-sharded (observed: it all-gathers the full f32 d_logits over the
    batch axis — 40 GB/device at 4k x 256).  eq(iota)+multiply+reduce stays
    fused and shards cleanly on both batch and vocab axes."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    v = logits.shape[-1]
    onehot = (labels[..., None] == jax.lax.broadcasted_iota(
        jnp.int32, labels.shape + (v,), labels.ndim)).astype(jnp.float32)
    ll = jnp.sum(logits * onehot, axis=-1)
    loss = lse - ll
    if z_loss:
        loss = loss + z_loss * jnp.square(lse)
    return loss.mean()
