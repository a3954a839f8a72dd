"""Absorbed MLA decode attention over a latent slot cache: one grid row per
slot, online softmax over blocks of positions, and only the blocks a slot
attends are read.

The cache holds, per layer, slot and position, the normed c_kv (r wide) in
``ckv`` (L, S, T, r), and the roped k_pe (rope wide) in ``kpe``
(L, S, T/2, 2 rope): row u holds position u in its first half and position
u + T/2 in its second, so neither array is padded past the chip's 128
lanes.  A query is the absorbed q_lat (H, r) beside q_pe (H, rope);
position t scores (q_lat . c_t + q_pe . k_pe_t) * scale.  Block i of a
slot's positions reads ckv rows [i tb, i tb + tb) and kpe rows of the same
block folded into T/2, against q_pe placed in that half.  A slot's blocks
past its last position repeat the last block's index, so the pipeline
fetches nothing new for them and their compute is skipped.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

BLOCK_T = 1024          # positions a grid step reads


def _kernel(layer_ref, last_ref, pos_ref, ql_ref, q2_ref, c_ref, pe_ref, o_ref,
            m_ref, l_ref, acc_ref, *, tb, scale, n_blocks):
    del layer_ref
    b, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -1e30, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(i <= last_ref[b])
    def _():
        dims, dt = (((1,), (1,)), ((), ())), ql_ref.dtype
        c = c_ref[...].astype(dt)
        s = jax.lax.dot_general(ql_ref[...], c, dims, preferred_element_type=jnp.float32)
        s += jax.lax.dot_general(q2_ref[...], pe_ref[...].astype(dt), dims,
                                 preferred_element_type=jnp.float32)
        t = i * tb + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(t <= pos_ref[b], s * scale, -1e30)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = corr * acc_ref[...] + jnp.dot(
            p.astype(dt), c, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(i == n_blocks - 1)
    def _():
        o_ref[...] = acc_ref[...] / l_ref[...]


def mla_decode_attention(q_lat, q_pe, ckv, kpe, layer, pos, *, scale: float):
    """q_lat: (S, H, r), in the compute dtype; q_pe: (S, H, rope); ckv: (L, S, T, r); kpe:
    (L, S, T/2, 2 rope); layer: int32 scalar; pos: (S,) int32, each slot
    attending positions 0..pos[s].  -> o_lat (S, H, r) f32: the softmax-
    weighted sum of the attended c_kv rows."""
    _, S, T, r = ckv.shape
    H, rope = q_pe.shape[1], q_pe.shape[2]
    tb = min(BLOCK_T, T // 2)
    n_blocks, half = T // tb, T // tb // 2
    zeros = jnp.zeros_like(q_pe)
    q2 = jnp.stack([jnp.concatenate([q_pe, zeros], -1),
                    jnp.concatenate([zeros, q_pe], -1)], axis=1).astype(q_lat.dtype)
    pos = pos.astype(jnp.int32)
    last = jnp.clip(pos, 0, T - 1) // tb
    blk = lambda i, last, b: jnp.minimum(i, last[b])
    kernel = functools.partial(_kernel, tb=tb, scale=scale, n_blocks=n_blocks)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S, n_blocks),
            in_specs=[
                pl.BlockSpec((None, H, r), lambda b, i, lay, last, pos: (b, 0, 0)),
                pl.BlockSpec((None, None, H, 2 * rope),
                             lambda b, i, lay, last, pos: (b, blk(i, last, b) // half, 0, 0)),
                pl.BlockSpec((None, None, tb, r),
                             lambda b, i, lay, last, pos: (lay[0], b, blk(i, last, b), 0)),
                pl.BlockSpec((None, None, tb, 2 * rope),
                             lambda b, i, lay, last, pos: (lay[0], b, blk(i, last, b) % half, 0)),
            ],
            out_specs=pl.BlockSpec((None, H, r), lambda b, i, lay, last, pos: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32), pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, r), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((S, H, r), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(),
        name="mla_decode",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), last, pos, q_lat, q2, ckv, kpe)
