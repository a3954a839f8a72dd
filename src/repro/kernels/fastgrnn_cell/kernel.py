"""Pallas TPU kernels: fused FastGRNN full-window scan and batched single
step (paper Eq. 1-3 + Sec. III-E LUT activations).

MCU -> TPU adaptation (DESIGN.md Sec. 2): on the MSP430 the weights live in
Flash and the ~300 B working set in SRAM; here weights, biases, both LUTs
and the hidden state stay resident in VMEM for the whole kernel.  Both
kernels use the (rows, 128) float32 lane layout: the real H=16, d=3 cell
is padded to 128 lanes, and lanes beyond H/d are zero and inert.  A LUT
is held as a (2, 128) block and read with two lane gathers
(:func:`lut_lookup`); Mosaic lowers no other gather form.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode
from . import qstep

B_TILE = 8
LANES = 128


def lut_block(table) -> jax.Array:
    """A 256-entry LUT as the (2, 128) block :func:`lut_lookup` reads."""
    return jnp.asarray(np.asarray(table, np.float32).reshape(2, LANES))


def lut_lookup(table, v):
    """Nearest-bucket LUT over a (rows, 128) block; ``table`` is the
    (2, 128) value of :func:`lut_block`.  Bit-identical to
    ``qstep.lut_eval_batched``: the same clipped bucket index, moved to
    the edge buckets where the reference overrides the value, then read
    as one lane gather per 128-entry half and a select."""
    idx = jnp.clip(((v - qstep.INPUT_MIN) * qstep.INV_BW).astype(jnp.int32),
                   0, qstep.LUT_SIZE - 1)
    idx = jnp.where(v <= qstep.INPUT_MIN, 0,
                    jnp.where(v >= qstep.INPUT_MAX, qstep.LUT_SIZE - 1, idx))
    lane = idx & (LANES - 1)
    row = lambda r: jnp.broadcast_to(table[r:r + 1], v.shape)
    lo, hi = (jnp.take_along_axis(row(r), lane, axis=1) for r in (0, 1))
    return jnp.where(idx >= LANES, hi, lo)


def _cell_kernel(sig_ref, tanh_ref, x_ref, w_ref, u_ref, b_ref, h_ref,
                 traj_ref, *, T: int):
    """x: (T, B_TILE, Dp); w: (Dp, Hp) = W^T; u: (Hp, Hp) = U^T; b: (4, Hp)
    rows [b_z, b_h, zeta, nu] (the post-sigmoid scalars broadcast over
    lanes); outputs: h (B_TILE, Hp), traj (T, B_TILE, Hp)."""
    w, u = w_ref[...], u_ref[...]
    b_z, b_h, zeta, nu = (b_ref[i:i + 1, :] for i in range(4))
    sig_t, tanh_t = sig_ref[...], tanh_ref[...]

    def step(t, h):
        pre = jnp.dot(x_ref[t], w, preferred_element_type=jnp.float32) \
            + jnp.dot(h, u, preferred_element_type=jnp.float32)
        z = lut_lookup(sig_t, pre + b_z)
        h_tilde = lut_lookup(tanh_t, pre + b_h)
        h_new = (zeta * (1.0 - z) + nu) * h_tilde + z * h
        traj_ref[t] = h_new
        return h_new

    h_ref[...] = jax.lax.fori_loop(0, T, step,
                                   jnp.zeros(h_ref.shape, jnp.float32))


@jax.jit  # detlint: ignore[det-jit-pallas] fixed window shapes (ops.py pads pre-call); resident path builds its own eager-pad wrapper
def fastgrnn_window(sig_lut, tanh_lut, x, w_t, u_t, b):
    """sig_lut/tanh_lut: (2, 128) LUT blocks; x: (T, B, Dp); w_t: (Dp, Hp);
    u_t: (Hp, Hp); b: (4, Hp) rows [b_z, b_h, zeta, nu].  B % B_TILE == 0
    (ops.py pads).  Returns (h, traj)."""
    Tn, B, Dp = x.shape
    Hp = w_t.shape[1]
    full = lambda shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    return pl.pallas_call(
        functools.partial(_cell_kernel, T=Tn),
        grid=(B // B_TILE,),
        in_specs=[
            full(sig_lut.shape), full(tanh_lut.shape),
            pl.BlockSpec((Tn, B_TILE, Dp), lambda i: (0, i, 0)),
            full((Dp, Hp)), full((Hp, Hp)), full(b.shape),
        ],
        out_specs=[
            pl.BlockSpec((B_TILE, Hp), lambda i: (i, 0)),
            pl.BlockSpec((Tn, B_TILE, Hp), lambda i: (0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hp), jnp.float32),
            jax.ShapeDtypeStruct((Tn, B, Hp), jnp.float32),
        ],
        interpret=interpret_mode(),
        name="q15_window",
    )(sig_lut, tanh_lut, x, w_t, u_t, b)


# ---------------------------------------------------------------------------
# Batched single-step kernel (multi-stream streaming inference)
# ---------------------------------------------------------------------------
# One FastGRNN step for a whole batch of independent streams: the serving
# analogue of a fleet of deployed sensors, each slot carrying its own hidden
# state.  Weights arrive as *raw int16 Q15* and are dequantized on use
# inside the kernel (w = f32(Wq) * scale) — the paper's Appendix-B recipe
# executed in VMEM.  The body is qstep.step_batched in the 128-lane layout:
# each matvec is the same ascending-j chain of one multiply and one add per
# real inner index (padded indices never enter it), the LUT is the same
# nearest bucket, and the Q15 activation stores are the same round/clip.


def _matvec_lanes(a_t, x, n: int):
    """``qstep.matvec_batched`` in the lane layout: out[b, i] = sum_j
    A[i, j] * x[b, j] over the n real j, ascending; ``a_t`` is A^T padded
    to (128, 128), so padded output lanes accumulate exact zeros."""
    out = jnp.zeros(x.shape, jnp.float32)
    for j in range(n):
        out = out + x[:, j:j + 1] * a_t[j:j + 1, :]
    return out


def _store(t, scale):
    """``qstep.store_batched`` (Q15 activation-storage fake-quant)."""
    if scale is None:
        return t
    q = jnp.clip(jnp.round(t / scale), -qstep.Q15_MAX - 1, qstep.Q15_MAX)
    return q * scale


def _q15_step_kernel(x_ref, h_ref, mask_ref, sig_ref, tanh_ref, *refs,
                     sw: "qstep.StepWeights", plan, H: int):
    """x, h: (B_TILE, 128); mask: (B_TILE, 1) int32; LUTs (2, 128); refs:
    one int16 A^T block per ``plan`` entry (name, inner dim), then b:
    (2, 128) rows [b_z, b_h], then out (B_TILE, 128)."""
    *w_refs, b_ref, out_ref = refs
    a_t = {n: ref[...].astype(jnp.float32) * np.float32(sw.scales[n])
           for (n, _), ref in zip(plan, w_refs)}
    inner = dict(plan)
    mv = lambda n, v: _matvec_lanes(a_t[n], v, inner[n])
    x, h = x_ref[...], h_ref[...]
    if sw.low_rank:
        pre = mv("W1", mv("W2", x)) + mv("U1", mv("U2", h))
    else:
        pre = mv("W", x) + mv("U", h)
    pre = _store(pre, sw.store_scale("pre"))
    z = _store(lut_lookup(sig_ref[...], pre + b_ref[0:1, :]),
               sw.store_scale("z"))
    h_tilde = _store(lut_lookup(tanh_ref[...], pre + b_ref[1:2, :]),
                     sw.store_scale("h_tilde"))
    h_new = _store((sw.zeta * (1.0 - z) + sw.nu) * h_tilde + z * h,
                   sw.store_scale("h"))
    lane = jax.lax.broadcasted_iota(jnp.int32, h.shape, 1)
    out_ref[...] = jnp.where((mask_ref[...] != 0) & (lane < H), h_new, h)


def _step_plan(sw: "qstep.StepWeights"):
    """(name, inner dim, A^T) per matvec of ``qstep.step_batched``."""
    w = sw.q
    if sw.low_rank:
        return [("W2", w["W2"].shape[0], w["W2"]),
                ("W1", w["W1"].shape[1], w["W1"].T),
                ("U2", w["U2"].shape[0], w["U2"]),
                ("U1", w["U1"].shape[1], w["U1"].T)]
    return [("W", w["W"].shape[1], w["W"].T),
            ("U", w["U"].shape[1], w["U"].T)]


def step_constants(sw: "qstep.StepWeights") -> list[np.ndarray]:
    """The step kernel's deployment constants in the lane layout, in call
    order: both LUT blocks, one int16 A^T per matvec, biases (2, 128)."""
    H = sw.hidden_dim
    pad2 = lambda a: np.pad(a, ((0, LANES - a.shape[0]),
                                (0, LANES - a.shape[1])))
    biases = np.zeros((2, LANES), np.float32)
    biases[0, :H], biases[1, :H] = sw.b_z, sw.b_h
    return [np.asarray(lut_block(sw.sig_lut)),
            np.asarray(lut_block(sw.tanh_lut)),
            *[pad2(np.asarray(a)) for _, _, a in _step_plan(sw)], biases]


def fastgrnn_step_call(sw: "qstep.StepWeights", S: int):
    """The step ``pallas_call`` over S rows (S % B_TILE == 0): ``(x, h,
    mask, *step_constants(sw)) -> h_new`` with x, h (S, 128) f32 and mask
    (S, 1) int32.  Lanes >= H of h_new are zero; rows whose mask is 0
    keep h bit-for-bit."""
    consts = step_constants(sw)
    kernel = functools.partial(
        _q15_step_kernel, sw=sw, H=sw.hidden_dim,
        plan=[(n, k) for n, k, _ in _step_plan(sw)])
    full = lambda a: pl.BlockSpec(a.shape, lambda i: (0, 0))
    rows = lambda w: pl.BlockSpec((B_TILE, w), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(S // B_TILE,),
        in_specs=[rows(LANES), rows(LANES), rows(1), *map(full, consts)],
        out_specs=rows(LANES),
        out_shape=jax.ShapeDtypeStruct((S, LANES), jnp.float32),
        interpret=interpret_mode(),
        name="q15_step",
    )


def make_fastgrnn_step(sw: "qstep.StepWeights", *, device=None):
    """Build the batched single-step callable: the constants are laid out
    ONCE on ``device`` (None = the default device) — they are deployment
    constants and this runs on every 50 Hz tick — and one
    :func:`fastgrnn_step_call` is cached per slot count.  Returns
    ``step(x, h, mask) -> h_new``; ``step.constants`` are the placed
    constants."""
    consts = [jax.device_put(c, device) for c in step_constants(sw)]
    calls: dict[int, "object"] = {}

    def step(x, h, mask):
        S = x.shape[0]
        if S not in calls:
            calls[S] = fastgrnn_step_call(sw, S)
        return calls[S](x, h, mask, *consts)

    step.constants = consts
    return step
