"""Continuous-batching LM serving engine on the shared slot scheduler.

The paper's systems thesis — a tiny stateful cell plus careful scheduler/
runtime work beats bigger budgets (Sec. VI; Saha et al. 2022 call the
runtime the dominant efficiency lever) — applied at LM scale.  This engine
is the LM half of the scheduler/program split (see ``serve/scheduler.py``):
the :class:`~repro.serve.scheduler.SlotScheduler` owns placement (slot
table, pending queue, FIFO admission, recycling, counters) and this module
implements the :class:`~repro.serve.scheduler.SlotProgram` — per-slot KV /
SSM cache rows, preallocated output buffers, and batched sampling.

Design notes
------------
* **True continuous batching**: a finished sequence's KV-cache slot is
  re-prefilled from the pending queue on the next tick — not at window
  boundaries.  The cache is a slot table (``models/transformer.
  init_slot_cache``) with a per-slot fill level ``pos`` (S,); admission
  writes one sequence's prefix into its slot (``prefill_into_slot``) while
  the neighbours keep decoding, and every tick is ONE fixed-shape jit call
  (``decode_step_slotted``) regardless of occupancy.
* **Preallocated output**: generated tokens land in a fixed (S, cap) int32
  buffer at a per-slot cursor — decode cost is O(T), not the O(T^2)
  ``np.concatenate``-per-token of the old loop.
* **Quantized serving**: ``repro.compress.quantize_tree`` (the pass-API
  home of the per-tensor PTQ recipe) produces a Q15/Q7 weight pytree +
  scales.  The
  backbone runs over
  dequantized weights (decode is HBM-bound; int8 weights halve the
  dominant roofline term on real hardware), and the sampling head — the
  one matmul the engine itself owns — runs the *actual* integer weights
  through ``kernels/q15_matmul`` (dequantize-inside-the-kernel), so the
  quantized pytree is load-bearing, not decoration.
* ``admit_policy="all_free"`` recovers the old window-boundary behaviour
  (admit only when every slot is free) — kept as the measurable baseline
  for ``benchmarks/serve_bench.py``.
* **One cache, fixed shapes.**  The cache is donated to every prefill and
  decode call, so the device holds one copy.  With ``max_prompt`` set
  (a deployment's longest prompt), a prompt is padded at its end to the
  next multiple of ``PREFILL_BUCKET`` (or to ``max_prompt``), its slot's
  ``pos`` set to the real length, and every bucket and the decode step are
  compiled when the engine is built: no prompt length compiles while
  serving.  Padding needs a cache of attention only and no MoE capacity
  for the padding to take.
* **Logits stay on the device.**  Each tick pulls only the sampled tokens
  — inside the ``lm.sample`` span, where the host waits for the device —
  plus the rows of requests submitted with ``return_logits``: their
  logits and, for a dropless MoE, the experts chosen for the tokens each
  step consumed (:meth:`drain_step_outputs`).
* **MoE load on the device.**  Tokens per (MoE layer, expert) and experts
  hit per layer call accumulate in device counters (:meth:`moe_stats`),
  prompt padding and idle slots excluded.
"""
from __future__ import annotations

import dataclasses
import itertools
from concurrent.futures import ThreadPoolExecutor
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.compress.tree import dequantize_tree, quantize_tree
from repro.models import transformer as T
from repro.obs import NULL_OBS, Observability
from repro.serve.scheduler import HostProgram, SlotScheduler, TickReport


COMPILE_THREADS = 4         # prefill buckets compiled at once at construction
PREFILL_BUCKET = 512        # prompt lengths padded to its multiples


@dataclasses.dataclass
class ServeConfig:
    max_len: int = 2048             # per-slot KV capacity (prompt + new)
    max_slots: int = 8              # resident batch width (decode batch)
    temperature: float = 0.0        # 0 -> greedy
    eos_id: int = -1                # -1 -> never stop early
    quant_bits: int = 0             # 0 off, 8, 16
    seed: int = 0
    admit_policy: str = "any_free"  # "all_free" = window-boundary baseline
    max_prompt: int = 0             # >0: longest prompt; pad to buckets


@dataclasses.dataclass
class LMRequest:
    """One queued generation: a prompt and a token budget."""
    request_id: str
    tokens: np.ndarray              # (s,) int32 prompt
    max_new: int                    # total tokens to emit (incl. the first)
    extra: dict | None = None       # e.g. vlm patch_embeds, (1, ...) rows
    return_logits: bool = False     # keep each step's logits (and routes)


@dataclasses.dataclass
class StepOutput:
    """One emitted step of a request submitted with ``return_logits``."""
    request_id: str
    step: int                       # 0 = the token sampled at prefill
    token: int
    logits: np.ndarray              # (V,) f32
    routes: np.ndarray | None       # experts of the consumed tokens: step 0
                                    # (prompt, n_moe, k), step k (1, n_moe, k)


@dataclasses.dataclass
class Completion:
    """Event surfaced by :meth:`Engine.tick` when a request leaves a slot."""
    request_id: str
    tokens: np.ndarray              # (n_emitted,) int32
    finished: bool                  # False -> cancelled with partial output


class Engine:
    """Continuous-batching LM engine (prefill-into-slot + slotted decode)."""

    def __init__(self, cfg, params, serve_cfg: ServeConfig | None = None,
                 *, obs: Observability | None = None):
        self.cfg = cfg
        self.scfg = scfg = serve_cfg or ServeConfig()
        # same observability seam as the streaming stack: spans for the
        # two jit'd sections (lm.prefill / lm.decode) plus a per-tick
        # latency histogram and token counter; NULL_OBS keeps every hook
        # a no-op on the default path
        self._obs = NULL_OBS if obs is None else obs
        self._tracer = self._obs.tracer
        if scfg.quant_bits:
            self.qparams, self.scales = quantize_tree(
                params, scfg.quant_bits)
            self.params = dequantize_tree(self.qparams, self.scales)
            # quantized head: logits come from the integer weights via the
            # q15_matmul kernel, so decode/prefill return hidden states.
            # The (K, V) integer head matrix is laid out once here (the
            # tied path would otherwise transpose the whole embed table
            # every tick) and the kernel call is jitted so the pad-to-tile
            # runs compiled.
            self._quant_head = True
            if not cfg.tie_embeddings and "lm_head" in self.qparams:
                head_wq = self.qparams["lm_head"]["w"]
                head_scale = self.scales["lm_head"]["w"]
            else:
                head_wq = jnp.asarray(self.qparams["embed"]["table"]).T
                head_scale = self.scales["embed"]["table"]
            from repro.kernels.q15_matmul.ops import q15_matmul
            self._head_fn = jax.jit(lambda x: q15_matmul(
                x, head_wq, head_scale, out_dtype=jnp.float32))
        else:
            self.params = params
            self.qparams = self.scales = None
            self._quant_head = False
            self._head_fn = None
        S = scfg.max_slots
        self.cache = T.init_slot_cache(cfg, S, scfg.max_len, dtype=cfg.cdtype)
        n_moe = cfg.num_layers - T._n_dense(cfg)
        self._moe_load = {"tokens": jnp.zeros((n_moe, cfg.num_experts), jnp.int32),
                          "hit_prefill": jnp.zeros((), jnp.int32),
                          "hit_decode": jnp.zeros((), jnp.int32)} \
            if cfg.moe_dropless else None
        # detlint: ignore[det-donate-argnums] LM slot cache and MoE counters: the device holds one cache; no bit-exactness contract on this path
        self._decode = jax.jit(self._decode_impl, donate_argnums=(1, 4))
        self._row = jax.jit(lambda x, i: jax.lax.dynamic_index_in_dim(x, i, keepdims=False))
        self._prefills: dict[Any, Any] = {}     # prompt shape -> jitted fn
        self._buckets = self._prefill_buckets()
        self._key = jax.random.PRNGKey(scfg.seed)
        # --- per-slot host state (preallocated; written in place) -------
        self._out = np.zeros((S, scfg.max_len), np.int32)   # token buffer
        self._emitted = np.zeros(S, np.int64)               # out-buffer cursor
        self._budget = np.zeros(S, np.int64)
        self._eos_done = np.zeros(S, bool)
        self._last = np.zeros((S, 1), np.int32)             # next decode input
        self._pos = np.zeros(S, np.int64)                   # cache fill level
        self._watch = np.zeros(S, bool)                     # return_logits
        self._results: dict[str, np.ndarray] = {}
        self._rid_counter = itertools.count()
        self._step_outputs: list[StepOutput] = []
        # telemetry
        self._prefill_count = 0
        self._decode_ticks = 0
        self._tokens_generated = 0
        self._prefill_tokens = 0
        self._prefill_padded = 0
        self._prefill_attended = 0
        self._decode_rows = 0
        self._attended = 0
        self.sched = SlotScheduler(S, HostProgram(self),
                                   admit_policy=scfg.admit_policy,
                                   tracer=self._tracer)
        if self._buckets:
            self._compile_all()

    # ------------------------------------------------------------------
    # Request API
    # ------------------------------------------------------------------
    def submit(self, tokens: np.ndarray, max_new: int, *,
               request_id: str | None = None,
               extra: dict | None = None, return_logits: bool = False) -> str:
        """Queue one prompt for ``max_new`` generated tokens (the first is
        sampled at prefill time, matching ``generate`` semantics).  Returns
        the request id; the sequence prefills into a slot as soon as the
        scheduler places it.  ``return_logits``: keep every step's logits
        (and chosen experts) for :meth:`drain_step_outputs`."""
        tokens = np.asarray(tokens, np.int32)
        if tokens.ndim != 1:
            raise ValueError(f"prompt must be 1-D, got {tokens.shape}")
        if not 1 <= max_new <= self.scfg.max_len:
            raise ValueError(f"max_new must be in [1, {self.scfg.max_len}]")
        n_extra = 0            # vlm patch embeddings occupy cache positions
        if extra and "patch_embeds" in extra:
            n_extra = int(np.asarray(extra["patch_embeds"]).shape[1])
        if self._buckets and (extra or tokens.shape[0] > self._buckets[-1]):
            raise ValueError(f"bucketed prefill takes token prompts of at most "
                             f"{self._buckets[-1]} tokens")
        if tokens.shape[0] + n_extra + max_new - 1 > self.scfg.max_len:
            raise ValueError(
                f"prompt ({tokens.shape[0]} tokens + {n_extra} patch "
                f"positions) + max_new ({max_new}) exceeds "
                f"max_len={self.scfg.max_len}")
        rid = request_id if request_id is not None \
            else f"r{next(self._rid_counter)}"
        self.sched.submit(rid, LMRequest(rid, tokens, int(max_new), extra,
                                         return_logits))
        return rid

    def tick(self) -> list[Completion]:
        """One scheduling round: admit+prefill into free slots, one batched
        decode step over all resident sequences, release finished slots."""
        if not self._obs.enabled:
            return self.sched.tick()
        t0 = self._tracer.open("lm.tick")
        events = self.sched.tick()
        dur_ns = self._tracer.close(t0)
        if self._obs.metrics is not None:
            self._obs.metrics.histogram(
                "lm.tick_us", "LM engine tick latency",
                wallclock=True).observe_ns(dur_ns)
        return events

    def run(self) -> list[Completion]:
        """Tick until every submitted request has completed."""
        events: list[Completion] = []
        while self.sched.has_work():
            events.extend(self.tick())
        return events

    def cancel(self, request_id: str) -> Completion:
        """Withdraw a request.  Resident sequences yield their partial
        tokens; a request still in the pending queue yields an empty
        result — either way :meth:`result` works afterwards, so callers
        need not know whether admission had happened yet."""
        ev = self.sched.cancel(request_id)
        if ev is None:                    # pending: nothing was emitted
            self._results[request_id] = np.zeros((0,), np.int32)
            ev = Completion(request_id, self._results[request_id].copy(),
                            False)
        return ev

    def result(self, request_id: str) -> np.ndarray:
        """Generated tokens of a completed/cancelled request (consumes it)."""
        return self._results.pop(request_id)

    def generate(self, tokens: np.ndarray, max_new: int,
                 extra: dict | None = None) -> np.ndarray:
        """Batch convenience: run (B, s) prompts to completion and return
        (B, max_new) tokens (continuous batching when B > max_slots; rows
        that hit ``eos_id`` early are padded with it)."""
        tokens = np.asarray(tokens, np.int32)
        rids = []
        for i in range(tokens.shape[0]):
            row_extra = None
            if extra:
                row_extra = {k: np.asarray(v)[i:i + 1] for k, v in extra.items()}
            rids.append(self.submit(tokens[i], max_new, extra=row_extra))
        self.run()
        pad = self.scfg.eos_id if self.scfg.eos_id >= 0 else 0
        out = np.full((tokens.shape[0], max_new), pad, np.int32)
        for i, rid in enumerate(rids):
            row = self.result(rid)
            out[i, :row.shape[0]] = row
        return out

    def stats(self) -> dict[str, Any]:
        sched = self.sched.stats()
        return {
            "max_slots": self.scfg.max_slots,
            "active": sched["active"],
            "pending": sched["pending"],
            "occupancy": sched["occupancy"],
            "peak_active": sched["peak_active"],
            "prefills": self._prefill_count,
            "decode_ticks": self._decode_ticks,
            "tokens_generated": self._tokens_generated,
            "prefill_tokens": self._prefill_tokens,
            "prefill_padded_tokens": self._prefill_padded,
            "prefill_attended_positions": self._prefill_attended,
            "decode_rows": self._decode_rows,
            "attended_positions": self._attended,
            "quant_bits": self.scfg.quant_bits,
            # scheduler counters (admissions/recycles/spills/occupancy):
            # shared observability surface with the streaming engine
            "scheduler": sched,
        }

    def moe_stats(self) -> dict[str, Any] | None:
        """Dropless-MoE load so far, pulled from the device (blocking):
        ``expert_tokens`` (n_moe, E) tokens routed per layer and expert;
        ``experts_hit_prefill`` / ``experts_hit_decode`` the sum over
        (prefill or decode call, MoE layer) of experts that received a
        token.  None for other models."""
        if self._moe_load is None:
            return None
        m = self._moe_load
        return {"expert_tokens": np.asarray(m["tokens"]),
                "experts_hit_prefill": int(m["hit_prefill"]),
                "experts_hit_decode": int(m["hit_decode"])}

    def drain_step_outputs(self) -> list[StepOutput]:
        """The steps of ``return_logits`` requests emitted since the last
        drain, in emission order."""
        out, self._step_outputs = self._step_outputs, []
        return out

    def close(self) -> None:
        """Free the engine's device state (cache, counters, executables), so
        the memory is back before the engine object itself is dropped."""
        self.cache = self._moe_load = None
        self._prefills.clear()
        self._decode = None

    # ------------------------------------------------------------------
    # SlotProgram hooks (called by the scheduler via HostProgram)
    # ------------------------------------------------------------------
    def _admit_slot(self, slot: int, request_id: str, req: LMRequest,
                    reset: bool) -> None:
        # No reset_cache_slot here: prefill overwrites the SSM/conv rows
        # entirely and the KV rows up to the prompt length, and everything
        # past ``pos`` is masked out — a recycled slot cannot leak its
        # previous occupant.  (reset_cache_slot exists for callers that
        # want belt-and-braces hygiene; it copies the whole cache.)
        n = req.tokens.shape[0]
        toks = req.tokens
        if self._buckets:
            b = next(b for b in self._buckets if b >= n)
            toks = np.zeros(b, np.int32)
            toks[:n] = req.tokens
        batch = {"tokens": jnp.asarray(toks[None, :])}
        if req.extra:
            batch.update({k: jnp.asarray(v) for k, v in req.extra.items()})
        t0 = self._tracer.open("lm.prefill")
        out, self.cache, routes, self._moe_load = self._prefill_fn(batch)(
            self.params, self.cache, batch, np.int32(slot), np.int32(n),
            self._moe_load)
        self._tracer.close(t0)
        logits = self._head_logits(out) if self._quant_head else out[:, -1, :]
        t0 = self._tracer.open("lm.sample")
        first = self._sample(logits)[0]
        if req.return_logits:
            self._step_outputs.append(StepOutput(
                request_id, 0, int(first), np.asarray(logits[0]),
                None if routes is None else np.asarray(routes[0, :n])))
        self._tracer.close(t0)
        self._out[slot, 0] = first
        self._emitted[slot] = 1
        self._budget[slot] = req.max_new
        self._last[slot, 0] = first
        self._pos[slot] = n + (np.asarray(req.extra["patch_embeds"]).shape[1]
                               if req.extra and "patch_embeds" in req.extra else 0)
        self._watch[slot] = req.return_logits
        self._eos_done[slot] = (self.scfg.eos_id >= 0
                                and first == self.scfg.eos_id)
        self._prefill_count += 1
        self._tokens_generated += 1
        self._prefill_tokens += n
        self._prefill_padded += toks.shape[0]
        self._prefill_attended += n * (n + 1) // 2

    def _advance(self, resident: np.ndarray) -> TickReport:
        need = resident & ~self._eos_done & (self._emitted < self._budget)
        if need.any():
            t0 = self._tracer.open("lm.decode")
            out, self.cache, routes, self._moe_load = self._decode(
                self.params, self.cache, jnp.asarray(self._last),
                jnp.asarray(need), self._moe_load)
            self._tracer.close(t0)
            logits = self._head_logits(out) if self._quant_head \
                else out[:, 0, :]
            rows = np.nonzero(need)[0]
            t0 = self._tracer.open("lm.sample")
            nxt = self._sample(logits)                    # (S,) batched
            watched = rows[self._watch[rows]]
            if watched.size:
                rw = None if routes is None else np.asarray(routes)
                for s in watched:
                    self._step_outputs.append(StepOutput(
                        self.sched.request_at(s), int(self._emitted[s]), int(nxt[s]),
                        np.asarray(self._row(logits, np.int32(s))),
                        None if rw is None else rw[s][None]))
            self._tracer.close(t0)
            self._out[rows, self._emitted[rows]] = nxt[rows]
            self._emitted[rows] += 1
            self._last[rows, 0] = nxt[rows]
            if self.scfg.eos_id >= 0:
                self._eos_done[rows] |= (nxt[rows] == self.scfg.eos_id)
            self._decode_ticks += 1
            self._tokens_generated += int(rows.size)
            self._decode_rows += int(rows.size)
            self._attended += int(np.sum(self._pos[rows] + 1))
            self._pos[rows] += 1
            if self._obs.metrics is not None:
                self._obs.metrics.counter(
                    "lm.tokens_generated",
                    "tokens emitted by decode ticks").inc(int(rows.size))
        finished = resident & (self._eos_done | (self._emitted >= self._budget))
        fin_rows = np.nonzero(finished)[0].tolist()
        events = [Completion(self.sched.request_at(s),
                             self._out[s, :self._emitted[s]].copy(), True)
                  for s in fin_rows]
        return TickReport(events=events, finished=fin_rows,
                          advanced=int(need.sum()))

    def _release_slot(self, slot: int, request_id: str,
                      reason: str) -> Completion | None:
        toks = self._out[slot, :self._emitted[slot]].copy()
        self._results[request_id] = toks
        self._emitted[slot] = 0
        self._budget[slot] = 0
        self._eos_done[slot] = False
        if reason == "cancelled":
            return Completion(request_id, toks, False)
        return None

    # ------------------------------------------------------------------
    # Internals
    # ------------------------------------------------------------------
    def _prefill_buckets(self) -> list[int]:
        top = self.scfg.max_prompt
        if not top:
            return []
        cfg = self.cfg
        if cfg.uses_mamba or cfg.family == "vlm" or (cfg.family == "moe"
                                                     and not cfg.moe_dropless):
            raise ValueError(f"{cfg.name} cannot pad prompts: only token prompts "
                             "over an attention-only cache and no MoE capacity")
        step = min(PREFILL_BUCKET, top)
        return sorted({*range(step, top + 1, step), top})

    def _prefill_impl(self, params, cache, batch, slot, length, load):
        out, cache, routes = T.prefill_into_slot(
            self.cfg, params, cache, batch, slot, length=length,
            return_hidden=self._quant_head, return_routes=True)
        if routes is not None:
            real = jnp.arange(routes.shape[1]) < length
            load = _count_experts(load, routes[0], real, "hit_prefill")
        return out, cache, routes, load

    def _decode_impl(self, params, cache, tokens, active, load):
        out, cache, routes = T.decode_step_slotted(
            self.cfg, params, cache, tokens, active,
            return_hidden=self._quant_head, return_routes=True)
        if routes is not None:
            load = _count_experts(load, routes, active, "hit_decode")
        return out, cache, routes, load

    def _prefill_fn(self, batch):
        """jit'd prefill-into-slot, cached per prompt geometry (the slot
        index and real length are traced arguments, so admission never
        retraces); with buckets, the executable compiled at construction."""
        key = tuple(sorted((k, v.shape) for k, v in batch.items()))
        fn = self._prefills.get(key)
        if fn is None:
            # detlint: ignore[det-donate-argnums] LM slot cache and MoE counters: the device holds one cache; no bit-exactness contract on this path
            fn = jax.jit(self._prefill_impl, donate_argnums=(1, 5))
            self._prefills[key] = fn
        return fn

    def _compile_all(self) -> None:
        """Compile the decode step and every prefill bucket ahead of time
        (lowered in turn, compiled on a few threads)."""
        S, i32 = self.scfg.max_slots, np.int32(0)
        todo = {"decode": self._decode.lower(
                    self.params, self.cache, jax.ShapeDtypeStruct((S, 1), jnp.int32),
                    jax.ShapeDtypeStruct((S,), jnp.bool_), self._moe_load),
                "row": self._row.lower(
                    jax.ShapeDtypeStruct((S, self.cfg.vocab_size), jnp.float32), i32)}
        for b in self._buckets:
            batch = {"tokens": jax.ShapeDtypeStruct((1, b), jnp.int32)}
            todo[(("tokens", (1, b)),)] = self._prefill_fn(batch).lower(
                self.params, self.cache, batch, i32, i32, self._moe_load)
        with ThreadPoolExecutor(COMPILE_THREADS) as pool:
            done = dict(zip(todo, pool.map(lambda lowered: lowered.compile(),
                                           todo.values())))
        self._decode, self._row = done.pop("decode"), done.pop("row")
        self._prefills.update(done)

    def _head_logits(self, hidden) -> jax.Array:
        """Sampling head over the *integer* quantized weights via the
        q15_matmul kernel (dequantize-inside-the-kernel) — the previously
        dead ``qparams``/``scales`` doing real work.  hidden: (n, 1, D) or
        (n, s, D); uses the last position.  -> (n, V) f32."""
        return self._head_fn(hidden[:, -1, :].astype(jnp.float32))

    def _sample(self, logits) -> np.ndarray:
        """(n, V) -> (n,) int32, greedy or temperature (batched)."""
        if self.scfg.temperature <= 0:
            return np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        self._key, k = jax.random.split(self._key)
        return np.asarray(jax.random.categorical(
            k, logits / self.scfg.temperature), np.int32)


def _count_experts(load, routes, valid, hit):
    """Add routed tokens per (MoE layer, expert) and the experts hit in
    each layer to the device counters.  routes: (n, n_moe, k); valid: (n,)."""
    onehot = jax.nn.one_hot(routes, load["tokens"].shape[1], dtype=jnp.int32)
    per = jnp.einsum("nlke,n->le", onehot, valid.astype(jnp.int32))
    return {**load, "tokens": load["tokens"] + per,
            hit: load[hit] + jnp.sum(per > 0).astype(jnp.int32)}
