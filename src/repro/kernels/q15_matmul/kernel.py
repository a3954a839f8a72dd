"""Pallas TPU kernel: blocked matmul with fused Q15/Q7 weight dequant.

The paper's Appendix-B runtime dequantizes each int16 weight on use
(``float w = (float) W_q15[i] * scale``).  TPU adaptation (DESIGN.md
Sec. 2): weights stream HBM->VMEM as int8/int16 (2-4x fewer HBM bytes than
f32 — decode is HBM-bound, so this moves the dominant roofline term
directly), convert to bf16 INSIDE the VMEM tile, hit the MXU, and apply
the per-tensor scale once to the f32 accumulator on the way out.

Grid (M/bm, N/bn, K/bk), K innermost; f32 accumulation in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

BM, BN, BK = 128, 128, 128


def _mm_kernel(x_ref, w_ref, scale_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xb = x_ref[...].astype(jnp.bfloat16)
    wb = w_ref[...].astype(jnp.bfloat16)          # int -> bf16 in-tile
    acc_ref[...] += jnp.dot(xb, wb, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _():
        o_ref[...] = (acc_ref[...] * scale_ref[0]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype",))  # detlint: ignore[det-jit-pallas] fixed block-padded shapes (ops.py pads pre-call); tolerance-gated, not bit-exact
def q15_matmul_padded(x, wq, scale, *, out_dtype=jnp.float32):
    """x: (M, K) bf16/f32; wq: (K, N) int8/int16; scale: (1,) f32.
    M, N, K must be multiples of the block sizes (ops.py pads)."""
    m, k = x.shape
    _, n = wq.shape
    grid = (m // BM, n // BN, k // BK)
    return pl.pallas_call(
        functools.partial(_mm_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BM, BK), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((BK, BN), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1,), lambda i, j, kk: (0,)),
        ],
        out_specs=pl.BlockSpec((BM, BN), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((BM, BN), jnp.float32)],
        interpret=interpret_mode(),
    )(x, wq, scale)
