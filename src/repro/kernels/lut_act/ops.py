"""jit'd public wrapper: arbitrary-shape LUT activations via the Pallas
kernel (pad -> 2D tiles -> unpad)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.lut import make_lut, INPUT_MIN, INPUT_MAX
from .kernel import lut_act_2d, BLOCK_R, BLOCK_C

_LINEAR_TAILS = ("silu", "gelu", "softplus")


def lut_act(x, fn: str = "tanh", *, mode: str = "nearest",
            lo: float = INPUT_MIN, hi: float = INPUT_MAX):
    table = jnp.asarray(make_lut(fn, 256, lo, hi))
    flat = x.reshape(-1)
    n = flat.shape[0]
    cols = BLOCK_C
    rows = -(-n // cols)
    rpad = -rows % BLOCK_R
    total = (rows + rpad) * cols
    flat = jnp.pad(flat, (0, total - n))
    x2d = flat.reshape(rows + rpad, cols)
    y = lut_act_2d(table, x2d, lo=lo, hi=hi, mode=mode,
                   linear_tail=(fn in _LINEAR_TAILS))
    return y.reshape(-1)[:n].reshape(x.shape)


def lut_sigmoid(x, **kw):
    return lut_act(x, "sigmoid", **kw)


def lut_tanh(x, **kw):
    return lut_act(x, "tanh", **kw)
