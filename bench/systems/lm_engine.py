"""The system under test for LM configurations: the program's continuous-
batching ``Engine`` (``serve/engine.py`` on ``serve/scheduler.SlotScheduler``)
serving the configuration's architecture, as the program registers it, at
the configuration's depth.

The engine takes the benchmark's weights as they are (the reference draws
them in the program's layout), holds one slot cache of ``slots`` rows of
``max_position_embeddings`` positions, pads prompts to the engine's
prefill buckets up to the configuration's ``max_prompt`` and compiles
every bucket in set-up.  Requests the
generator marks as checked are submitted with ``return_logits``; their
steps are emitted with the program's token as the choice, which also
carries the experts the program chose for the tokens that step consumed.

``bits`` (the control) maps the embedding, attention, dense-MLP, shared-
expert and head weights to the program's per-tensor integer PTQ
(``repro.compress.quantize_tree``) and serves them dequantized, next to
the originals the harness keeps; the routed experts stay bf16, since a
second copy of them does not fit beside the cache.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

#: Configuration keys and the program's ModelConfig fields they set.
SHAPE_KEYS = {"hidden_size": "d_model", "num_attention_heads": "num_heads",
              "intermediate_size": "d_ff", "moe_intermediate_size": "moe_d_ff",
              "vocab_size": "vocab_size", "kv_lora_rank": "kv_lora_rank",
              "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim",
              "v_head_dim": "v_head_dim", "n_routed_experts": "num_experts",
              "num_experts_per_tok": "top_k", "n_shared_experts": "n_shared_experts",
              "first_k_dense_replace": "first_k_dense_replace", "rope_theta": "rope_theta",
              "rms_norm_eps": "norm_eps", "routed_scaling_factor": "routed_scaling_factor",
              "norm_topk_prob": "norm_topk_prob", "scoring_func": "router_scoring"}


class Choice(int):
    """An emitted token that also carries the experts chosen for the tokens
    its step consumed, (tokens, MoE layers, k)."""
    routes: np.ndarray | None

    def __new__(cls, token: int, routes):
        out = super().__new__(cls, token)
        out.routes = routes
        return out


def model_config(cfg: dict):
    """The program's registered architecture (``serving.arch``: its family,
    attention and MLP kinds) at the configuration's sizes and depth."""
    import repro.configs as C
    serve = cfg["serving"]
    return dataclasses.replace(
        C.get(serve["arch"]), num_layers=cfg["num_hidden_layers"],
        compute_dtype=serve["compute_dtype"], remat=False,
        **{attr: cfg[key] for key, attr in SHAPE_KEYS.items()})


def quantized(params: dict, bits: int) -> dict:
    """Every weight but the routed experts through the program's PTQ at
    ``bits``, dequantized to bf16; the routed experts (and the routers,
    which are f32 vectors of scores) shared with ``params``."""
    from repro.compress.tree import dequantize_tree, quantize_tree

    def q(tree):
        return dequantize_tree(*quantize_tree(tree, bits))
    out = {**params, "embed": q(params["embed"]), "lm_head": q(params["lm_head"])}
    for group in ("dense", "blocks"):
        sub = dict(params[group])
        sub["attn"] = q(sub["attn"])
        if "mlp" in sub:
            sub["mlp"] = q(sub["mlp"])
        if "moe" in sub:
            sub["moe"] = {**sub["moe"], "shared": q(sub["moe"]["shared"])}
        out[group] = sub
    return out


class System:
    def __init__(self, cfg: dict, params: dict, *, slots: int, ring: int,
                 bits: int | None = None, trace: bool = False):
        from repro.obs import Observability, Tracer
        from repro.serve.engine import Engine, ServeConfig
        serve = cfg["serving"]
        if bits:
            params = quantized(params, bits)
        self.tracer = Tracer() if trace else None
        self.engine = Engine(model_config(cfg), params, ServeConfig(
            max_len=cfg["max_position_embeddings"], max_slots=slots,
            max_prompt=serve["max_prompt"]),
            obs=Observability(tracer=self.tracer) if trace else None)

    # -- what the generator calls -------------------------------------------
    def attach(self, ids: list) -> np.ndarray:
        return np.zeros(len(ids), np.int64)

    def submit(self, request_id: str, prompt: np.ndarray, max_new: int, checked: bool):
        self.engine.submit(prompt, max_new, request_id=request_id, return_logits=checked)

    def free_slots(self) -> int:
        """Slots that the next tick's admission can fill."""
        sched = self.engine.sched
        return sched.max_slots - sched.n_active

    def pending(self) -> int:
        return self.engine.sched.n_pending

    # -- what the harness calls ---------------------------------------------
    def step(self) -> list:
        """One engine tick; the checked requests' steps as one batch
        (request ids, steps, logits, choices)."""
        self.engine.tick()
        outs = self.engine.drain_step_outputs()
        if not outs:
            return []
        return [([o.request_id for o in outs], [o.step for o in outs],
                 np.stack([o.logits for o in outs]),
                 [Choice(o.token, o.routes) for o in outs])]

    @staticmethod
    def sync() -> None:
        jax.block_until_ready(jax.live_arrays())

    def counters(self) -> dict:
        st = self.engine.stats()
        out = {"stream_steps": st["tokens_generated"], "ticks": st["scheduler"]["ticks"],
               "prefills": st["prefills"], "decode_steps": st["decode_ticks"],
               **{k: st[k] for k in ("prefill_tokens", "prefill_padded_tokens",
                                     "prefill_attended_positions", "decode_rows",
                                     "attended_positions")}}
        moe = self.engine.moe_stats()
        if moe is not None:
            out.update({f"moe.{k}": v for k, v in moe.items()})
        return out

    def span_totals(self) -> dict:
        return self.tracer.totals_s() if self.tracer is not None else {}

    def close(self) -> None:
        self.engine.close()
        self.engine = None
