"""Pallas TPU kernel: Mamba2 chunked SSD scan (arXiv:2405.21060).

One program per (batch, head) pair; the kernel walks the chunk sequence
with a fori_loop, holding the running (N, P) state in a VMEM scratch —
the inter-chunk recurrence never touches HBM.  Per chunk the intra-chunk
term is the masked decay-weighted (Q, Q) matmul pair (MXU work).

Layout per program: x (S, P), dt (S, 1), B/C (S, N) for ONE head (groups
pre-broadcast by ops.py); Q (chunk) a multiple of 8, N, P 128-lane padded.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref,
                state_ref, *, nc: int, q: int):
    a = a_ref[0]                                   # scalar A (negative)
    state_ref[...] = jnp.zeros_like(state_ref)

    def chunk(ci, _):
        sl = pl.dslice(ci * q, q)
        xq = x_ref[sl, :].astype(jnp.float32)      # (Q, P)
        dtq = dt_ref[sl, 0].astype(jnp.float32)    # (Q,)
        bq = b_ref[sl, :].astype(jnp.float32)      # (Q, N)
        cq = c_ref[sl, :].astype(jnp.float32)      # (Q, N)
        dA = dtq * a
        cs = jnp.cumsum(dA)                        # (Q,)
        # intra-chunk: L[i,j] = exp(cs_i - cs_j) for i >= j.  Clamp the
        # masked (i < j) entries BEFORE the exp — cs is decreasing so
        # cs_i - cs_j > 0 there, and once the chunk accumulates enough
        # |dA| (large chunks, or zero-padded tails pinning cs flat while
        # real rows keep decaying) exp overflows to inf and inf * 0 from
        # the post-hoc mask multiply poisons the whole row with NaN.
        # Same fix as the jnp oracle (models/mamba2.ssd_chunked).
        li = cs[:, None] - cs[None, :]
        mask = jnp.tril(jnp.ones((q, q), bool))
        Ldec = jnp.exp(jnp.where(mask, li, -1e30))
        scores = jnp.dot(cq, bq.T, preferred_element_type=jnp.float32)
        M = scores * Ldec * dtq[None, :]
        y_diag = jnp.dot(M, xq, preferred_element_type=jnp.float32)
        # inter-chunk: y_off = C_i exp(cs_i) . H_prev
        h_prev = state_ref[...]                    # (N, P)
        y_off = jnp.exp(cs)[:, None] * jnp.dot(
            cq, h_prev, preferred_element_type=jnp.float32)
        y_ref[sl, :] = (y_diag + y_off).astype(y_ref.dtype)
        # state update: H = exp(sum dA) H_prev + sum_j w_j B_j x_j^T
        decay_to_end = jnp.exp(cs[-1] - cs)        # (Q,)
        w = decay_to_end * dtq
        s_new = jnp.dot(bq.T * w[None, :], xq,
                        preferred_element_type=jnp.float32)
        state_ref[...] = jnp.exp(cs[-1]) * h_prev + s_new
        return 0

    jax.lax.fori_loop(0, nc, chunk, 0)
    hout_ref[...] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk",))  # detlint: ignore[det-jit-pallas] fixed chunk-padded shapes (ops.py pads pre-call); tolerance-gated, not bit-exact
def ssd_scan_heads(x, dt, A, B, C, *, chunk: int = 64):
    """Per-head layout: x (BH, S, P); dt (BH, S, 1); A (BH, 1); B/C
    (BH, S, N).  S % chunk == 0 (ops.py pads).  Returns (y, final_state)."""
    bh, s, p = x.shape
    n = B.shape[2]
    nc = s // chunk
    grid = (bh,)
    return pl.pallas_call(
        functools.partial(_ssd_kernel, nc=nc, q=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, s, p), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, s, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, 1), lambda i: (i, 0)),
            pl.BlockSpec((None, s, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, s, n), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, s, p), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, n, p), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, p), x.dtype),
            jax.ShapeDtypeStruct((bh, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret_mode(),
    )(x, dt, A, B, C)
