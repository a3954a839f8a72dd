"""jit'd wrapper: (B,S,H,P)/(B,S,G,N) model layout -> per-head kernel
layout (broadcast groups, fold B x H into the grid), pad S to the chunk."""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import ssd_scan_heads


def ssd_scan(x, dt, A, B, C, *, chunk: int = 64):
    """Same signature/semantics as models.mamba2.ssd_chunked (h0=None).
    x: (b,S,H,P); dt: (b,S,H); A: (H,); B,C: (b,S,G,N)."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    pad = (-s) % chunk
    if pad:
        zf = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        x, dt, B, C = zf(x), zf(dt), zf(B), zf(C)
    sp = s + pad
    xh = jnp.moveaxis(x, 2, 1).reshape(b * h, sp, p)
    dth = jnp.moveaxis(dt, 2, 1).reshape(b * h, sp, 1)
    Bh = jnp.repeat(B, rep, axis=2)
    Ch = jnp.repeat(C, rep, axis=2)
    Bh = jnp.moveaxis(Bh, 2, 1).reshape(b * h, sp, n)
    Ch = jnp.moveaxis(Ch, 2, 1).reshape(b * h, sp, n)
    Ah = jnp.tile(A.astype(jnp.float32), b).reshape(b * h, 1)
    y, hf = ssd_scan_heads(xh, dth, Ah, Bh, Ch, chunk=chunk)
    y = jnp.moveaxis(y.reshape(b, h, sp, p), 1, 2)[:, :s]
    state = hf.reshape(b, h, n, p)
    return y.astype(x.dtype), state
