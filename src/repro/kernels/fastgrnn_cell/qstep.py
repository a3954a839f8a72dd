"""Batched single-step Q15 FastGRNN cell math, shared by every backend.

This is the streaming-inference hot path: one FastGRNN step for a whole
batch of independent streams (one hidden state per slot), written once and
parameterized over the array namespace ``xp`` so the identical op sequence
runs as

  * vectorized NumPy        (``xp=numpy`` — the *exact* backend),
  * eager / jit jax.numpy   (``xp=jax.numpy``),

and ``kernel.py`` restates the same op sequence in the 128-lane layout
for the Pallas step (bitwise equal to the NumPy path on a TPU v5e).

Bit-stability contract (paper Sec. IV-D / Table VI, lifted to batch scale):
every function here is the batched image of the scalar reference in
``core/qruntime.py`` — the fixed ascending-j matvec loop, dequantize-on-use
weights, nearest-bucket LUT activations, and the gate combine are the same
scalar IEEE-754 float32 ops applied per stream row.  Under NumPy that makes
each stream bit-identical to ``QRuntime.step``.  Under **jit-compiled** XLA
CPU it does not: XLA's emitter contracts ``a*b + c`` into an FMA (even
through ``lax.optimization_barrier`` / select guards, measured drift ~1e-9
per step), which is why the streaming engine defaults to the NumPy backend
for the agreement contract and offers the jit/Pallas backends for
throughput.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.lut import make_lut, LUT_SIZE, INPUT_MIN, INPUT_MAX
from repro.core.quantization import QuantizedParams, Q15_MAX

INV_BW = LUT_SIZE / (INPUT_MAX - INPUT_MIN)   # exact python float (16.0)

LOW_RANK_NAMES = ("W1", "W2", "U1", "U2")
FULL_RANK_NAMES = ("W", "U")


@dataclasses.dataclass
class StepWeights:
    """Deployment-time constants for the batched step, mirroring
    ``QRuntime.__post_init__``: dequantized f32 weights, raw Q15 tensors +
    scales (for backends that dequantize on use), float biases, post-sigmoid
    zeta/nu scalars, and the two activation LUTs."""
    low_rank: bool
    w: dict[str, np.ndarray]            # dequantized float32 (incl. head_w)
    q: dict[str, np.ndarray]            # raw int16 Q15 tensors
    scales: dict[str, float]            # per-tensor dequant scales
    b_z: np.ndarray
    b_h: np.ndarray
    head_b: np.ndarray
    zeta: np.float32                    # sigmoid(raw), f32 — as deployed
    nu: np.float32
    sig_lut: np.ndarray                 # (256,) f32
    tanh_lut: np.ndarray
    act_scales: dict[str, float] | None = None   # calibrated Q15 act storage
    naive_acts: bool = False                     # naive [-1,1) act storage

    @property
    def input_dim(self) -> int:
        return self.w["W2"].shape[0] if self.low_rank else self.w["W"].shape[1]

    @property
    def hidden_dim(self) -> int:
        return self.b_z.shape[0]

    @property
    def num_classes(self) -> int:
        return self.head_b.shape[0]

    @classmethod
    def from_quantized(cls, qp: QuantizedParams, *,
                       act_scales: dict[str, float] | None = None,
                       naive_acts: bool = False) -> "StepWeights":
        low_rank = "W1" in qp.q or "W1" in qp.fp
        names = list(LOW_RANK_NAMES if low_rank else FULL_RANK_NAMES) + ["head_w"]
        w, q, scales = {}, {}, {}
        for n in names:
            qi = np.asarray(qp.q[n], np.int32)
            s = np.float32(qp.scales[n])
            q[n] = np.asarray(qp.q[n], np.int16)
            scales[n] = float(s)
            w[n] = (qi.astype(np.float32) * s).astype(np.float32)
        f32 = lambda n: np.asarray(qp.fp[n], np.float32)
        return cls(
            low_rank=low_rank, w=w, q=q, scales=scales,
            b_z=f32("b_z"), b_h=f32("b_h"), head_b=f32("head_b"),
            zeta=np.float32(1.0 / (1.0 + np.exp(-float(qp.fp["zeta"])))),
            nu=np.float32(1.0 / (1.0 + np.exp(-float(qp.fp["nu"])))),
            sig_lut=make_lut("sigmoid"), tanh_lut=make_lut("tanh"),
            act_scales=dict(act_scales) if act_scales else None,
            naive_acts=naive_acts,
        )

    def arrays(self, xp) -> dict[str, "object"]:
        """All array constants converted into namespace ``xp`` (f32)."""
        out = {n: xp.asarray(a) for n, a in self.w.items()}
        out.update(b_z=xp.asarray(self.b_z), b_h=xp.asarray(self.b_h),
                   head_b=xp.asarray(self.head_b),
                   sig_lut=xp.asarray(self.sig_lut),
                   tanh_lut=xp.asarray(self.tanh_lut))
        return out

    def store_scale(self, name: str) -> np.float32 | None:
        """Activation-storage scale for ``name`` (Table V modes), or None
        when the tensor stays FP32 (the deployed configuration)."""
        if self.naive_acts:
            return np.float32(1.0 / Q15_MAX)
        if self.act_scales is not None and name in self.act_scales:
            return np.float32(self.act_scales[name])
        return None


# ---------------------------------------------------------------------------
# Generic math (xp = numpy | jax.numpy)
# ---------------------------------------------------------------------------

def matvec_batched(xp, A, x):
    """out[b, i] = sum_j A[i, j] * x[b, j], j ascending.

    The batched image of ``qruntime._matvec``: per row the multiply and the
    accumulate are the same two scalar f32 ops in the same order, so each
    stream is bit-identical to the scalar loop (under a non-contracting
    executor; see module docstring).
    """
    out = xp.zeros((x.shape[0], A.shape[0]), xp.float32)
    for j in range(A.shape[1]):
        out = out + x[:, j:j + 1] * A[:, j][None, :]
    return out


def lut_eval_batched(xp, table, v):
    """Nearest-bucket LUT over (B, H), identical to qruntime._lut_eval_scalar."""
    idx = xp.clip(((v - INPUT_MIN) * INV_BW).astype(xp.int32), 0, LUT_SIZE - 1)
    y = table[idx]
    y = xp.where(v >= INPUT_MAX, table[LUT_SIZE - 1], y)
    y = xp.where(v <= INPUT_MIN, table[0], y)
    return y.astype(xp.float32)


def store_batched(xp, t, scale):
    """Q15 activation-storage fake-quant (qruntime._store); scale may be None."""
    if scale is None:
        return t
    q = xp.clip(xp.round(t / scale), -Q15_MAX - 1, Q15_MAX)
    return (q * scale).astype(xp.float32)


#: Bound-check slack for the tally fast path: the elementwise ``pre + b``
#: sums round in float32, so the (float64) ``max(pre) + max(b)`` bound can
#: undershoot an elementwise result by up to half a float32 ulp.  1e-3 at
#: a threshold of 8.0 is ~1000x that — conservative, never unsound.
_TALLY_SLACK = 1e-3


def tally_step_events(events: dict, pre, z_in, ht_in,
                      bias_ext: tuple | None = None) -> None:
    """Accumulate numeric-health tallies from one NumPy step's already
    materialized intermediates (see :mod:`repro.obs.numerics`).

    ``act.*.idx`` counts LUT boundary hits with the float-path semantic:
    a pre-activation at or beyond ``INPUT_MAX`` / ``INPUT_MIN`` takes the
    ``where``-override branch in :func:`lut_eval_batched` (the qvm's
    integer twin counts the index-clip instead — the two agree except on
    exact-boundary ties, which the float path treats as saturated).
    ``pre`` range is tallied as (vmin, vmax, n, n_over) against the
    optional ``events["pre_limit"]`` amplitude so the engine can feed
    ``NumericsMonitor.note_range`` without re-touching the values.

    Fast path: the ``pre`` min/max this function needs anyway, plus the
    precomputed bias extremes (``bias_ext = (bz_lo, bz_hi, bh_lo,
    bh_hi)``), bound every elementwise count from above — the O(B*H)
    comparisons only run in the rare tick whose bounds approach a
    threshold, so a healthy monitored stream pays two reductions per
    step and nothing else."""
    pmin, pmax = float(pre.min()), float(pre.max())
    if bias_ext is None:
        bias_ext = (0.0, 0.0, 0.0, 0.0)
        near_z = near_ht = True
    else:
        bz_lo, bz_hi, bh_lo, bh_hi = bias_ext
        near_z = (pmax + bz_hi >= INPUT_MAX - _TALLY_SLACK
                  or pmin + bz_lo <= INPUT_MIN + _TALLY_SLACK)
        near_ht = (pmax + bh_hi >= INPUT_MAX - _TALLY_SLACK
                   or pmin + bh_lo <= INPUT_MIN + _TALLY_SLACK)
    if near_z:
        events["act.z.idx"] = events.get("act.z.idx", 0) + int(
            np.count_nonzero(z_in >= INPUT_MAX)
            + np.count_nonzero(z_in <= INPUT_MIN))
    if near_ht:
        events["act.ht.idx"] = events.get("act.ht.idx", 0) + int(
            np.count_nonzero(ht_in >= INPUT_MAX)
            + np.count_nonzero(ht_in <= INPUT_MIN))
    lim = events.get("pre_limit")
    # exact comparisons on pre itself: bounds inside +-lim imply zero over
    n_over = int(np.count_nonzero(np.abs(pre) > lim)) \
        if lim and (pmax > lim or pmin < -lim) else 0
    vmin, vmax, n, over = events.get("pre_range", (0.0, 0.0, 0, 0))
    if n == 0:
        events["pre_range"] = (pmin, pmax, int(pre.size), n_over)
    else:
        events["pre_range"] = (min(vmin, pmin), max(vmax, pmax),
                               n + int(pre.size), over + n_over)


def step_batched(xp, arrs, sw: StepWeights, h, x, events=None):
    """One batched FastGRNN step.  h: (B, H), x: (B, d) -> h_new (B, H).

    Mirrors ``QRuntime.step`` line for line; ``arrs`` is ``sw.arrays(xp)``.
    ``events`` (NumPy path only — pass None under a tracer) is a mutable
    dict that :func:`tally_step_events` fills from the intermediates this
    call materializes anyway, so monitored and unmonitored runs execute
    the same FP op sequence and stay byte-identical.
    """
    if sw.low_rank:
        wx = matvec_batched(xp, arrs["W1"], matvec_batched(xp, arrs["W2"].T, x))
        uh = matvec_batched(xp, arrs["U1"], matvec_batched(xp, arrs["U2"].T, h))
    else:
        wx = matvec_batched(xp, arrs["W"], x)
        uh = matvec_batched(xp, arrs["U"], h)
    pre = store_batched(xp, wx + uh, sw.store_scale("pre"))
    z_in = pre + arrs["b_z"]
    ht_in = pre + arrs["b_h"]
    z = lut_eval_batched(xp, arrs["sig_lut"], z_in)
    h_tilde = lut_eval_batched(xp, arrs["tanh_lut"], ht_in)
    if events is not None:
        ext = events.get("_bias_ext")
        if ext is None:
            ext = events["_bias_ext"] = (
                float(arrs["b_z"].min()), float(arrs["b_z"].max()),
                float(arrs["b_h"].min()), float(arrs["b_h"].max()))
        tally_step_events(events, pre, z_in, ht_in, ext)
    z = store_batched(xp, z, sw.store_scale("z"))
    h_tilde = store_batched(xp, h_tilde, sw.store_scale("h_tilde"))
    h_new = (sw.zeta * (1.0 - z) + sw.nu) * h_tilde + z * h
    return store_batched(xp, h_new.astype(xp.float32), sw.store_scale("h"))


def logits_batched(xp, arrs, sw: StepWeights, h):
    """Classifier head, the batched image of ``qruntime.run_window``'s
    ``_matvec(head_w.T, h) + head_b`` (+ optional Q15 logit storage)."""
    out = matvec_batched(xp, arrs["head_w"].T, h)
    return store_batched(xp, out + arrs["head_b"], sw.store_scale("logits"))
