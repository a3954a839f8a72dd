"""jit'd wrapper: pad to (128,128,128) blocks, run, slice back.  Also the
serving entry point ``quantized_dense`` used by the L-S-Q serving path."""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import q15_matmul_padded, BM, BN, BK


def q15_matmul(x, wq, scale, *, out_dtype=jnp.float32):
    """x: (..., K); wq: (K, N) int8/int16; scale: scalar -> (..., N)."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = wq.shape[1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    mp, kp, np_ = -m % BM, -k % BK, -n % BN
    x2 = jnp.pad(x2.astype(jnp.float32), ((0, mp), (0, kp)))
    wqp = jnp.pad(wq, ((0, kp), (0, np_)))
    out = q15_matmul_padded(x2, wqp, jnp.asarray([scale], jnp.float32),
                            out_dtype=out_dtype)
    return out[:m, :n].reshape(lead + (n,))


def quantized_dense(p_q, p_scale, x):
    """Drop-in for layers.dense_apply with a quantized weight leaf."""
    y = q15_matmul(x, p_q["w"], p_scale["w"], out_dtype=jnp.float32)
    if "b" in p_q:
        y = y + p_q["b"]
    return y
