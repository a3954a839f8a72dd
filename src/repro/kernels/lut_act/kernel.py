"""Pallas TPU kernel: 256-entry LUT activation (paper Sec. III-E).

MCU -> TPU adaptation (DESIGN.md Sec. 2): the table lives in Flash on the
MSP430 and is re-read per call; here it is pinned in VMEM for the whole
tile sweep and the lookup vectorizes on the VPU (on TPU the win is
determinism/precision control, not speed).

Tiling: (BLOCK_R, 128) VMEM tiles of the input; the 256 x f32 table (1 KB)
is replicated to every grid step via a constant index_map.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

BLOCK_R = 256          # sublane-dim tile rows
BLOCK_C = 128          # lane dim (VPU width)


def _lut_kernel(table_ref, x_ref, o_ref, *, lo: float, hi: float,
                lerp: bool, linear_tail: bool):
    x = x_ref[...].astype(jnp.float32)
    table = table_ref[...]
    size = table.shape[0]
    bw = (hi - lo) / size
    if lerp:
        pos = (x - lo) / bw - 0.5
        i0 = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, size - 1)
        i1 = jnp.clip(i0 + 1, 0, size - 1)
        frac = jnp.clip(pos - i0.astype(jnp.float32), 0.0, 1.0)
        y = (1.0 - frac) * jnp.take(table, i0) + frac * jnp.take(table, i1)
    else:
        idx = jnp.clip(((x - lo) * (1.0 / bw)).astype(jnp.int32), 0, size - 1)
        y = jnp.take(table, idx)
    if linear_tail:
        y = jnp.where(x >= hi, x, jnp.where(x <= lo, 0.0, y))
    else:
        y = jnp.where(x >= hi, table[size - 1], jnp.where(x <= lo, table[0], y))
    o_ref[...] = y.astype(o_ref.dtype)


# detlint: ignore[det-jit-pallas] fixed block-padded shapes (ops.py pads pre-call); tolerance-gated, not bit-exact
@functools.partial(jax.jit, static_argnames=("lo", "hi", "mode",
                                             "linear_tail"))
def lut_act_2d(table, x2d, *, lo: float, hi: float, mode: str = "nearest",
               linear_tail: bool = False):
    """x2d: (R, C) padded to (BLOCK_R, BLOCK_C) multiples by ops.py."""
    r, c = x2d.shape
    grid = (r // BLOCK_R, c // BLOCK_C)
    return pl.pallas_call(
        functools.partial(_lut_kernel, lo=lo, hi=hi, lerp=(mode == "lerp"),
                          linear_tail=linear_tail),
        grid=grid,
        in_specs=[
            pl.BlockSpec((table.shape[0],), lambda i, j: (0,)),
            pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((BLOCK_R, BLOCK_C), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        interpret=interpret_mode(),
    )(table, x2d)
