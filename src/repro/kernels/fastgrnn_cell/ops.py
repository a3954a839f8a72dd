"""jit'd wrappers: FastGRNN params pytree -> padded kernel layout -> run.

Two entry points live here:

  * ``fastgrnn_window_kernel`` — the fused full-window scan (training/eval
    batch path, one kernel launch per 128-sample window);
  * ``Q15StreamStep`` — the batched *single-step* path for multi-stream
    streaming inference (serve/streaming.py), stepping thousands of
    independent hidden states at once from Q15 weights.

Padding to hardware-aligned tiles: H=16, d=3 pads to Hp=Dp=128 lanes; the
zero lanes are inert (zero weights, zero state).  Low-rank factors are
pre-multiplied into effective W^T/U^T once per deployment (the MCU code
does the same factor-order trick at runtime; on TPU the 128x128 effective
matmul is a single MXU op, so pre-multiplying is strictly better)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fastgrnn as fg
from repro.core.lut import make_lut
from repro.obs.transfers import TransferLedger
from . import qstep
from .kernel import (B_TILE, LANES, fastgrnn_window, lut_block,
                     make_fastgrnn_step)


def _pad2(a, r, c):
    return jnp.pad(jnp.asarray(a, jnp.float32),
                   ((0, r - a.shape[0]), (0, c - a.shape[1])))


def _pad1(a, n):
    return jnp.pad(jnp.asarray(a, jnp.float32), (0, n - a.shape[0]))


def fastgrnn_window_kernel(params, xs):
    """xs: (T, B, d) -> (h_final (B, H), traj (T, B, H)) via the Pallas
    kernel, LUT-activated (nearest mode, matching the deployed C engine)."""
    T, B, d = xs.shape
    H = params["b_z"].shape[0]
    W = fg.effective_W(params)      # (H, d)
    U = fg.effective_U(params)      # (H, H)
    zeta = 1.0 / (1.0 + np.exp(-float(params["zeta"])))
    nu = 1.0 / (1.0 + np.exp(-float(params["nu"])))

    bpad = -B % B_TILE
    xs_p = jnp.pad(jnp.asarray(xs, jnp.float32),
                   ((0, 0), (0, bpad), (0, LANES - d)))
    b = jnp.stack([_pad1(params["b_z"], LANES), _pad1(params["b_h"], LANES),
                   jnp.full((LANES,), zeta, jnp.float32),
                   jnp.full((LANES,), nu, jnp.float32)])
    h, traj = fastgrnn_window(
        lut_block(make_lut("sigmoid")), lut_block(make_lut("tanh")),
        xs_p, _pad2(W.T, LANES, LANES), _pad2(U.T, LANES, LANES), b)
    return h[:B, :H], traj[:, :B, :H]


# ---------------------------------------------------------------------------
# Batched single-step entry point (streaming)
# ---------------------------------------------------------------------------

class Q15StreamStep:
    """Batched single-step FastGRNN over Q15 weights: the hot path of the
    multi-stream streaming engine.  ``step(h, x, active)`` advances every
    slot whose ``active`` flag is set by one sample; ``head_logits`` maps
    any subset of slot states to classifier logits (emission time only).

    Backends (selected at construction), one step path each:

      * ``"exact"``  — vectorized NumPy over a host h table.  Guaranteed
        bit-identical per stream to the scalar ``core/qruntime.QRuntime``
        reference: the batched ops are the same scalar IEEE-754 f32 ops per
        row, and NumPy never contracts mul+add into an FMA.  This is the
        agreement-contract backend (paper contribution (i) at batch scale)
        and the CPU default.
      * ``"jit"``    — the same qstep math jit-compiled with XLA.  XLA's
        CPU emitter contracts ``a*b + c`` into FMAs (even through
        ``lax.optimization_barrier``), so hidden states drift ~1e-9/step
        from the reference; argmax predictions still agree in practice.
      * ``"pallas"`` — the ``kernel.make_fastgrnn_step`` Pallas kernel
        (compiled on a TPU, interpreted elsewhere), dequantizing the int16
        weights on use inside the kernel.

    The device backends (jit, pallas) are resident (:attr:`resident`): h
    lives on the device between ticks and :meth:`step_resident` advances
    it; :meth:`step` is the host-in/host-out form of the same call.

    All backends share the single generic op sequence in ``qstep.py``.
    """

    BACKENDS = ("exact", "jit", "pallas")

    def __init__(self, qp_or_sw, *, act_scales=None, naive_acts=False,
                 backend: str = "exact", device=None):
        if backend not in self.BACKENDS:
            raise ValueError(f"backend must be one of {self.BACKENDS}")
        if isinstance(qp_or_sw, qstep.StepWeights):
            self.sw = qp_or_sw
        else:
            self.sw = qstep.StepWeights.from_quantized(
                qp_or_sw, act_scales=act_scales, naive_acts=naive_acts)
        self.backend = backend
        # host<->device byte accounting (always on — plain int adds); the
        # fleet/engine stats() surface this and the zero-copy regression
        # test reads it (see repro.obs.transfers)
        self.transfers = TransferLedger()
        # ``device``: pin the jit/pallas dispatch (weight constants AND the
        # per-tick inputs) to one jax device — the fleet's per-shard
        # placement hook.  None = default device; the exact backend is
        # process-local NumPy and ignores it.
        self.device = device if backend != "exact" else None
        self._np_arrs = self.sw.arrays(np)
        # Numeric-health seam (repro.obs.numerics): when an engine sets
        # this to a mutable dict, the exact backend's gathered step tallies
        # LUT-saturation / pre-range events into it from intermediates it
        # materializes anyway (zero extra FP work, byte-identical output).
        # The resident jit/pallas dispatches are never tallied.
        self.numeric_events = None
        if backend == "jit":
            self._jnp_arrs = self.sw.arrays(jnp)
            if self.device is not None:
                self._jnp_arrs = {k: jax.device_put(v, self.device)
                                  for k, v in self._jnp_arrs.items()}
            self._resident_step = self._build_jit_resident()
        elif backend == "pallas":
            self._pallas_step = make_fastgrnn_step(self.sw,
                                                   device=self.device)
            self._resident_step = self._build_pallas_resident()
        # device-side reset: jitted masked zero (no host h round-trip)
        self._reset_resident = jax.jit(
            lambda h, m: jnp.where(m[:, None], jnp.float32(0.0), h))
        # device-side row gather: one cached executable per row count,
        # where op-by-op fancy indexing dispatches its index arithmetic
        # as separate ops on every call
        self._take_rows = jax.jit(lambda h, idx: h[idx])

    # -- state management ---------------------------------------------------
    @property
    def hidden_dim(self) -> int:
        return self.sw.hidden_dim

    @property
    def input_dim(self) -> int:
        return self.sw.input_dim

    def init_state(self, n_slots: int) -> np.ndarray:
        return np.zeros((n_slots, self.sw.hidden_dim), np.float32)

    def reset(self, h: np.ndarray, mask: np.ndarray) -> np.ndarray:
        """Zero the hidden state of every slot whose mask bit is set."""
        return np.where(np.asarray(mask)[:, None], np.float32(0.0),
                        np.asarray(h)).astype(np.float32)

    def head_logits(self, h: np.ndarray) -> np.ndarray:
        """Classifier logits for every slot state, (S, H) -> (S, C), via the
        fixed-order f32 head matvec (bit-identical to qruntime.run_window)."""
        return qstep.logits_batched(np, self._np_arrs, self.sw,
                                    np.asarray(h, np.float32))

    # -- device-resident state (jit/pallas backends) ------------------------
    # The streaming/fleet engines keep the hidden-state slot table as a jax
    # device array between ticks: ``step_resident`` advances it with an
    # async dispatch (steady-state ticks move zero h bytes across the
    # host/device boundary), and
    # the row-level accessors below pull/patch only the rows the host
    # actually touches (emission, trajectory taps, snapshots, migration).
    # Every boundary crossing is booked in ``self.transfers``.

    @property
    def resident(self) -> bool:
        """Whether h lives on the device between ticks: true for every
        device backend, false for the host-NumPy ``exact``."""
        return self.backend != "exact"

    def init_state_device(self, n_slots: int):
        """Zero-initialized (S, H) resident state (created on device — no
        host upload to account)."""
        z = jnp.zeros((n_slots, self.sw.hidden_dim), jnp.float32)
        return z if self.device is None else jax.device_put(z, self.device)

    def to_device(self, h: np.ndarray):
        """Upload a host (S, H) state table (booked as h-state h2d)."""
        h = np.ascontiguousarray(h, np.float32)
        self.transfers.h2d(h.nbytes, state=True)
        dev = jnp.asarray(h) if self.device is None \
            else jax.device_put(h, self.device)
        return dev

    def to_host(self, h_dev) -> np.ndarray:
        """Download the full resident table (snapshot/debug path)."""
        out = np.array(h_dev, np.float32)
        self.transfers.d2h(out.nbytes, state=True)
        return out

    def rows_to_host(self, h_dev, rows) -> np.ndarray:
        """Pull only ``rows`` of the resident state to host (emission,
        taps, lazy snapshots) — a (k, H) d2h instead of the full table."""
        return self.rows_fetch(self.rows_issue(h_dev, rows))

    def rows_issue(self, h_dev, rows):
        """First half of :meth:`rows_to_host`: copy the row indices to
        the state's device (booked h2d) and issue the (k, H) gather.
        Returns the gathered device array without waiting for it."""
        return self._take_rows(h_dev, self._put(np.asarray(rows, np.int32)))

    def rows_fetch(self, rows_dev) -> np.ndarray:
        """Second half of :meth:`rows_to_host`: wait for the gather (and
        whatever the device runs before it) and copy the rows to the host
        (booked d2h)."""
        out = np.array(rows_dev, np.float32)
        self.transfers.d2h(out.nbytes, state=True)
        return out

    def set_rows_device(self, h_dev, rows, values: np.ndarray):
        """Patch ``rows`` of the resident state with host values (migration
        restore) — a (k, H) h2d instead of re-uploading the table."""
        values = np.ascontiguousarray(values, np.float32)
        self.transfers.h2d(values.nbytes, state=True)
        return h_dev.at[self._put(np.asarray(rows, np.int32))].set(values)

    def reset_device(self, h_dev, mask: np.ndarray):
        """Device-side :meth:`reset` — only the (S,) mask crosses h2d."""
        return self._reset_resident(h_dev, self._put(np.asarray(mask, bool)))

    def concat_device(self, parts):
        """Device-side concat of per-shard h views (fused-tick fallback
        when a shard rebound its state; no boundary crossing)."""
        return jnp.concatenate(parts, axis=0)

    def step_resident(self, h_dev, x: np.ndarray, active: np.ndarray):
        """One masked batched step over the resident state.  Returns the
        NEW device array immediately (async jax dispatch — the caller
        decides when to block); callers must treat ``h_dev`` as consumed
        and adopt the returned array (keeps the contract donation-ready
        for accelerators where donation pays — on CPU it measurably
        doesn't, see ``_build_jit_resident``).  Only x and the active
        mask cross h2d; h never touches the host."""
        return self._resident_step(h_dev,
                                   self._put(np.asarray(x, np.float32)),
                                   self._put(np.asarray(active, bool)))

    def _put(self, a: np.ndarray):
        """One booked host->device copy of a (non-state) operand, to this
        step's device; on the default device jax copies it at first use."""
        self.transfers.h2d(a.nbytes)
        return a if self.device is None else jax.device_put(a, self.device)

    def _build_jit_resident(self):
        # Deliberately NOT donate_argnums=0: buffer donation makes XLA's
        # CPU executable ~3x slower for this kernel (measured 0.20 ms vs
        # 0.066 ms per 1024-row step) AND changes its fusion by ~1 ulp,
        # so donating would cost both throughput and bitwise
        # reproducibility.  The resident path doesn't need it for
        # zero-copy — h stays on device either way; donation would only
        # save the output allocation.
        arrs, sw = self._jnp_arrs, self.sw

        @jax.jit
        def f(h, x, active):
            h_new = qstep.step_batched(jnp, arrs, sw, h, x)
            return jnp.where(active[:, None], h_new, h)

        return f

    def _build_pallas_resident(self):
        # Deliberately NOT wrapped in jax.jit: fusing the pad/slice into
        # the kernel's jit trace changes XLA's FMA contraction per batch
        # shape (~1 ulp between a 16-row dispatch and two 8-row ones),
        # which breaks the fleet's shard-count-invariant bit-identity.
        # Eager pads materialize the exact padded operands and the direct
        # pallas_call is batch-shape-stable, so this path is bitwise equal
        # across batch sizes (and to ``exact`` on a TPU).  The ops still
        # dispatch asynchronously; the trade is losing h-buffer donation
        # (the pallas output allocates regardless).
        pstep = self._pallas_step
        H, d = self.sw.hidden_dim, self.sw.input_dim

        def f(h, x, active):
            S = h.shape[0]
            sp = -S % B_TILE
            h_p = jnp.pad(h, ((0, sp), (0, LANES - H)))
            x_p = jnp.pad(x, ((0, sp), (0, LANES - d)))
            m_p = jnp.pad(active.astype(jnp.int32)[:, None], ((0, sp), (0, 0)))
            return pstep(x_p, h_p, m_p)[:S, :H]

        return f

    def work_per_stream_step(self) -> dict:
        """Model FLOPs and steady-state HBM bytes of one stream-step,
        counted from the cell's shapes (valid on any platform)."""
        sw = self.sw
        H, d = sw.hidden_dim, sw.input_dim
        if sw.low_rank:
            rw, ru = sw.w["W1"].shape[1], sw.w["U1"].shape[1]
            mm = 2 * (d * rw + H * rw + H * ru + H * ru)
        else:
            mm = 2 * H * (d + H)
        # + gate combine and LUT indexing; bytes: x in, h in + out (the
        # weights and LUTs stay in VMEM for the whole dispatch)
        return {"backend": self.backend,
                "model_flops_per_stream_step": int(mm + 10 * H),
                "hbm_bytes_per_stream_step": int(4 * (d + 2 * H))}

    def device_constants(self) -> list:
        """The jax arrays a device backend dispatches against (weights,
        biases, LUTs) — where they live is where the step runs."""
        if self.backend == "jit":
            return list(self._jnp_arrs.values())
        if self.backend == "pallas":
            return list(self._pallas_step.constants)
        return []

    # -- one tick -----------------------------------------------------------
    def step(self, h, x, active):
        """h: (S, H) f32, x: (S, d) f32, active: (S,) bool -> h_new (S, H)
        as a NumPy array.  Slots with ``active=False`` keep their hidden
        state bit-for-bit.  Logits are NOT computed here — the engine only
        needs them at emission time; call :meth:`head_logits` on the
        emitting rows.  A device backend runs :meth:`step_resident` with h
        uploaded and downloaded around it (both booked as h-state)."""
        h = np.asarray(h, np.float32)
        x = np.asarray(x, np.float32)
        active = np.asarray(active, bool)
        if self.resident:
            return self.to_host(self.step_resident(self.to_device(h), x,
                                                   active))
        h_new = qstep.step_batched(np, self._np_arrs, self.sw, h, x)
        return np.where(active[:, None], h_new, h).astype(np.float32)

    # -- scheduler/program adapter ------------------------------------------
    def step_rows(self, h, x, active, rows=None):
        """Slot-program adapter for ``serve/scheduler.SlotScheduler``
        consumers on the exact backend (a resident backend steps with
        :meth:`step_resident`): advance exactly the slots listed in
        ``rows`` (the precomputed ``np.nonzero(active)[0]``; derived here
        if omitted).

        Only those rows are computed — ``step_batched`` is row-independent
        (one fixed-order f32 matvec chain per row), so the gathered
        computation is bit-identical to the masked full-batch step while
        skipping idle slots entirely (partial-occupancy ticks do not pay
        for the whole slot table)."""
        if rows is None:
            rows = np.nonzero(active)[0]
        if rows.size == 0:
            return np.asarray(h, np.float32)
        h = np.asarray(h, np.float32).copy()
        h[rows] = qstep.step_batched(np, self._np_arrs, self.sw,
                                     h[rows], np.asarray(x, np.float32)[rows],
                                     events=self.numeric_events)
        return h

    def tally_numeric_events(self, h, x, rows) -> None:
        """Numeric-health tallies for a fused exact fleet tick, whose group
        kernel stepped a cross-shard batch: recompute one shard's advanced
        rows on the host purely to observe their intermediates
        (``repro.obs.numerics``), discarding the result.  The step itself
        is never modified, so monitored and unmonitored runs stay
        byte-identical by construction; the recompute cost is why
        monitoring defaults off.  A standalone exact engine never needs
        this — ``step_rows`` tallies inline for free."""
        if self.numeric_events is None or rows is None or len(rows) == 0:
            return
        qstep.step_batched(np, self._np_arrs, self.sw,
                           np.asarray(h, np.float32)[np.asarray(rows)],
                           np.asarray(x, np.float32)[np.asarray(rows)],
                           events=self.numeric_events)
