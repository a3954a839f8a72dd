"""Mixture-of-Experts layer: top-k routing, capacity-factor token gather,
expert parallelism (EP) over the ``model`` mesh axis.

Design (production-oriented, collective-explicit):

  * Activations between blocks are replicated over ``model`` (the TP
    convention after an o-proj/FFN-out psum) and sharded over
    (pod, data) on batch.
  * Each model shard owns E_loc = E/tp experts.  Dispatch is a purely LOCAL
    capacity-limited gather (sort-free ranking via one-hot cumsum over the
    shard's own experts), expert FFN is a dense (E_loc, C, D) einsum, and
    combine is a local scatter-add followed by one ``psum`` over ``model``
    — the same single all-reduce a TP FFN block would pay.  No giant
    (n, E, C) one-hot dispatch tensors, no all-to-all, FLOPs = expert FLOPs
    (keeps the roofline compute term honest).
  * ``moe_apply_local`` is the single code path: under ``shard_map`` it sees
    the device-local expert slice and psums; on a single device it sees all
    experts and the psum is a no-op (axis absent -> skipped).

Aux losses: Switch load-balance + router z-loss, computed from local
routing statistics (averaged over data shards by the outer loss mean).

``moe_dropless`` is the DeepSeek-V3 layer (sigmoid scoring, ``noaux_tc``
top-k on bias-corrected scores, normalised and scaled weights, shared
experts): every token reaches all its experts, grouped by expert and
computed with ``jax.lax.ragged_dot``, so the expert FLOPs are those of the
routed tokens and nothing is dropped at any batch size.  A scan over its
layers keeps the routed experts' weights out of the sliced blocks
(``split_experts``) and hands the whole stack to each layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import layers as L


def moe_init(key, d_model: int, d_ff: int, num_experts: int, kind: str = "swiglu",
             dtype=jnp.float32, *, n_shared: int = 0, dropless: bool = False):
    ks = jax.random.split(key, 5)
    def ex(k, d_in, d_out):
        return L.truncated_normal(k, (num_experts, d_in, d_out),
                                  1.0 / (d_in ** 0.5), dtype)
    p = {"router": L.dense_init(ks[0], d_model, num_experts, dtype=jnp.float32),
         "w_in": ex(ks[1], d_model, d_ff),
         "w_out": ex(ks[2], d_ff, d_model)}
    if kind in ("swiglu", "geglu"):
        p["w_gate"] = ex(ks[3], d_model, d_ff)
    if dropless:                    # noaux_tc: e_score_correction_bias
        p["e_bias"] = jnp.zeros((num_experts,), jnp.float32)
    if n_shared:
        p["shared"] = L.mlp_init(ks[4], d_model, n_shared * d_ff, kind, dtype=dtype)
    return p


def capacity(n_tokens: int, top_k: int, num_experts: int, cf: float) -> int:
    return int(max(top_k, round(cf * n_tokens * top_k / num_experts)))


def moe_apply_local(p_local, x, *, num_experts_global: int, expert_offset,
                    top_k: int, capacity_factor: float = 1.25,
                    kind: str = "swiglu", model_axis: str | None = None,
                    compute_dtype=jnp.bfloat16):
    """x: (B, S, D) local tokens (replicated over ``model``).

    ``p_local``: expert weights with local leading dim E_loc; the router is
    over the GLOBAL expert count.  ``expert_offset``: first global expert id
    owned by this shard (traced value under shard_map).
    """
    b, s, d = x.shape
    e_loc = p_local["w_in"].shape[0]
    n = b * s
    xt = x.reshape(n, d)

    gate_logits = L.dense_apply(p_local["router"], xt.astype(jnp.float32),
                                compute_dtype=jnp.float32)          # (n, E)
    probs = jax.nn.softmax(gate_logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)               # (n, k)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9, None)

    # aux losses (global-expert statistics, local tokens)
    me = probs.mean(0)
    ce = jnp.zeros((num_experts_global,)).at[gate_idx.reshape(-1)].add(
        1.0 / (n * top_k), mode="drop")
    aux_loss = num_experts_global * jnp.sum(me * ce)
    z_loss = jnp.mean(jnp.square(jax.scipy.special.logsumexp(gate_logits, -1)))

    # ---- local capacity-limited gather for the shard's own experts ------
    cap = capacity(n, top_k, num_experts_global, capacity_factor)
    flat_expert = gate_idx.reshape(-1)                              # (n*k,)
    flat_weight = gate_vals.reshape(-1)
    flat_token = jnp.repeat(jnp.arange(n, dtype=jnp.int32), top_k)
    rel = flat_expert - expert_offset                               # (n*k,)
    mine = (rel >= 0) & (rel < e_loc)
    onehot = jax.nn.one_hot(jnp.where(mine, rel, e_loc), e_loc + 1,
                            dtype=jnp.int32)[:, :e_loc]             # (n*k, E_loc)
    rank = jnp.cumsum(onehot, axis=0) - onehot                      # slot within expert
    slot = jnp.sum(rank * onehot, axis=1)                           # (n*k,)
    keep = mine & (slot < cap)
    # scatter (expert, slot) -> token id / weight; OOB entries dropped
    e_sel = jnp.where(keep, rel, e_loc)                             # e_loc = OOB row
    idx = jnp.zeros((e_loc + 1, cap), jnp.int32).at[e_sel, slot].set(
        flat_token, mode="drop")[:e_loc]
    wgt = jnp.zeros((e_loc + 1, cap), jnp.float32).at[e_sel, slot].set(
        jnp.where(keep, flat_weight, 0.0), mode="drop")[:e_loc]
    filled = jnp.zeros((e_loc + 1, cap), jnp.bool_).at[e_sel, slot].set(
        keep, mode="drop")[:e_loc]

    xe = jnp.take(xt, idx, axis=0).astype(compute_dtype)            # (E_loc, C, D)
    xe = xe * filled[..., None].astype(compute_dtype)
    h = jnp.einsum("ecd,edf->ecf", xe, p_local["w_in"].astype(compute_dtype),
                   preferred_element_type=compute_dtype)
    if kind in ("swiglu", "geglu"):
        act = jax.nn.silu if kind == "swiglu" else jax.nn.gelu
        g = jnp.einsum("ecd,edf->ecf", xe, p_local["w_gate"].astype(compute_dtype),
                       preferred_element_type=compute_dtype)
        h = act(g) * h
    elif kind == "relu2":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h)
    ye = jnp.einsum("ecf,efd->ecd", h, p_local["w_out"].astype(compute_dtype),
                    preferred_element_type=compute_dtype)
    ye = ye * wgt[..., None].astype(compute_dtype)
    y = jnp.zeros((n, d), compute_dtype).at[idx.reshape(-1)].add(
        ye.reshape(-1, d), mode="drop")
    if model_axis is not None:
        y = jax.lax.psum(y, model_axis)
        aux_loss = jax.lax.pmean(aux_loss, model_axis)
        z_loss = jax.lax.pmean(z_loss, model_axis)
    return y.reshape(b, s, d).astype(x.dtype), {"aux_loss": aux_loss,
                                                "router_z_loss": z_loss}


def moe_apply(p, x, *, top_k: int, capacity_factor: float = 1.25,
              kind: str = "swiglu", compute_dtype=jnp.bfloat16):
    """Single-device path (all experts local) — used by smoke tests and as
    the oracle for the sharded path."""
    e = p["w_in"].shape[0]
    return moe_apply_local(
        p, x, num_experts_global=e, expert_offset=jnp.int32(0), top_k=top_k,
        capacity_factor=capacity_factor, kind=kind, model_axis=None,
        compute_dtype=compute_dtype)


def route_sigmoid(p, x, *, top_k: int, norm_topk_prob: bool = True,
                  scaling: float = 1.0):
    """DeepSeek-V3 ``noaux_tc`` routing with one group.  x: (n, D) ->
    (weights (n, k) f32, expert ids (n, k) int32): the top k of
    ``sigmoid(x W_r) + bias`` in f32; the weights are the chosen scores
    without the bias, normalised to sum 1, times ``scaling``."""
    scores = jax.nn.sigmoid(L.dense_apply(p["router"], x.astype(jnp.float32),
                                          compute_dtype=jnp.float32))
    _, ids = jax.lax.top_k(scores + p["e_bias"], top_k)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    if norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * scaling, ids.astype(jnp.int32)


EXPERT_KEYS = ("w_in", "w_gate", "w_out")


def split_experts(cfg, blocks):
    """(blocks without the routed experts' weights, those weights stacked
    over every MoE layer) for a scan over dropless MoE layers: sliced per
    layer by the scan, each layer's experts would be copied whole, since
    the grouped matmul cannot read a slice in place.  (blocks, None) where
    the blocks hold no dropless MoE."""
    moe = blocks.get("moe")
    if moe is None or not cfg.moe_dropless:
        return blocks, None
    rest = dict(blocks, moe={k: v for k, v in moe.items() if k not in EXPERT_KEYS})
    return rest, {k: moe[k] for k in EXPERT_KEYS if k in moe}


def moe_dropless(p, x, cfg, experts, layer, *, compute_dtype=jnp.bfloat16):
    """Dropless DeepSeek-V3 MoE.  x: (B, S, D) -> (y (B, S, D), expert ids
    (B, S, k)).  ``p``: the layer's router, correction bias and shared
    experts; ``experts``: the routed experts of every MoE layer stacked
    (L, E, ., .), this layer's at index ``layer``.  The n*k (token,
    expert) pairs are sorted by expert and run through ``ragged_dot`` per
    projection over all L*E groups, the other layers' groups empty, so no
    layer's weights are copied out of the stack; the shared experts see
    every token."""
    b, s, d = x.shape
    k = cfg.top_k
    nl, e = experts["w_in"].shape[:2]
    xt = x.reshape(b * s, d)
    w, ids = route_sigmoid(p, xt, top_k=k, norm_topk_prob=cfg.norm_topk_prob,
                           scaling=cfg.routed_scaling_factor)
    cd = compute_dtype
    with jax.named_scope("moe_experts"):
        flat = ids.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((nl * e,), jnp.int32), jnp.bincount(flat, length=e).astype(jnp.int32),
            (layer * e,))
        xs = jnp.take(xt.astype(cd), order // k, axis=0)
        rd = lambda a, name: jax.lax.ragged_dot(
            a, experts[name].reshape((nl * e,) + experts[name].shape[2:]).astype(cd),
            sizes, preferred_element_type=jnp.float32)
        h = rd(xs, "w_in")
        if cfg.mlp_kind in ("swiglu", "geglu"):
            act = jax.nn.silu if cfg.mlp_kind == "swiglu" else jax.nn.gelu
            h = act(rd(xs, "w_gate")) * h
        elif cfg.mlp_kind == "relu2":
            h = jnp.square(jax.nn.relu(h))
        else:
            h = jax.nn.gelu(h)
        ys = rd(h.astype(cd), "w_out")
        yk = jnp.take(ys, jnp.argsort(order), axis=0).reshape(b * s, k, d)
        y = jnp.einsum("nkd,nk->nd", yk, w).astype(cd)
    if "shared" in p:
        y = y + L.mlp_apply(p["shared"], xt, cfg.mlp_kind, compute_dtype=cd)
    return y.reshape(b, s, d).astype(x.dtype), ids.reshape(b, s, k)
