"""Unified LM assembly for all assigned families:

  dense | moe   : [ln -> GQA attn -> ln -> MLP/MoE] x L   (scan over layers)
  ssm           : [ln -> mamba2]  x L                      (scan over layers)
  hybrid(zamba2): mamba2 backbone + ONE shared attn+MLP block applied every
                  ``attn_every`` layers (weight reuse across depth)
  audio         : encoder-only (bidirectional) + frame-classification head;
                  frontend STUB: inputs are precomputed frame embeddings
  vlm           : dense decoder; frontend STUB: precomputed patch embeddings
                  prepended to the text embeddings

All forward passes are pure functions of (cfg, params, batch); layers are
stacked (leading L axis) and driven by jax.lax.scan with optional remat —
this keeps HLO size O(1) in depth, which matters for the 96-layer/340B
dry-run compile.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from . import layers as L
from . import attention as A
from . import moe as M
from . import mamba2 as S


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _block_init(cfg, key, dense=False):
    ks = jax.random.split(key, 4)
    dt = cfg.pdtype
    if cfg.family == "ssm":
        return {"ln": L.rmsnorm_init(cfg.d_model, dt),
                "mamba": S.mamba_init(ks[0], cfg, dt)}
    if cfg.family == "hybrid":
        return {"ln": L.rmsnorm_init(cfg.d_model, dt),
                "mamba": S.mamba_init(ks[0], cfg, dt)}
    attn = A.mla_init(ks[0], cfg, dt) if cfg.uses_mla else A.attn_init(
        ks[0], cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
        qkv_bias=cfg.qkv_bias, dtype=dt)
    p = {"ln1": L.rmsnorm_init(cfg.d_model, dt), "attn": attn,
         "ln2": L.rmsnorm_init(cfg.d_model, dt)}
    if cfg.family == "moe" and not dense:
        p["moe"] = M.moe_init(ks[1], cfg.d_model, cfg.expert_d_ff, cfg.num_experts,
                              cfg.mlp_kind, dt, n_shared=cfg.n_shared_experts,
                              dropless=cfg.moe_dropless)
    else:
        p["mlp"] = L.mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_kind,
                              rank=cfg.lsq_rank, dtype=dt)
    return p


def _n_dense(cfg) -> int:
    """Leading dense layers ahead of the MoE stack (``params["dense"]``)."""
    return cfg.first_k_dense_replace if cfg.family == "moe" else 0


def init(cfg, key) -> dict[str, Any]:
    ks = jax.random.split(key, 6)
    p: dict[str, Any] = {}
    if cfg.family != "audio":
        p["embed"] = L.embed_init(ks[0], cfg.vocab_size, cfg.d_model, cfg.pdtype)
    # stacked per-layer params: the leading dense layers, then the rest
    kd = _n_dense(cfg)
    if kd:
        p["dense"] = jax.vmap(lambda k: _block_init(cfg, k, dense=True))(
            jax.random.split(ks[5], kd))
    layer_keys = jax.random.split(ks[1], cfg.num_layers - kd)
    p["blocks"] = jax.vmap(lambda k: _block_init(cfg, k))(layer_keys)
    if cfg.family == "hybrid":
        p["shared"] = {
            "ln1": L.rmsnorm_init(cfg.d_model, cfg.pdtype),
            "attn": A.attn_init(ks[2], cfg.d_model, cfg.num_heads,
                                cfg.num_kv_heads, cfg.head_dim, dtype=cfg.pdtype),
            "ln2": L.rmsnorm_init(cfg.d_model, cfg.pdtype),
            "mlp": L.mlp_init(ks[3], cfg.d_model, cfg.d_ff, cfg.mlp_kind,
                              dtype=cfg.pdtype),
        }
    p["final_norm"] = L.rmsnorm_init(cfg.d_model, cfg.pdtype)
    if cfg.family == "audio":
        p["lm_head"] = L.dense_init(ks[4], cfg.d_model, cfg.vocab_size,
                                    bias=True, dtype=cfg.pdtype)
    elif not cfg.tie_embeddings:
        p["lm_head"] = L.dense_init(ks[4], cfg.d_model, cfg.vocab_size,
                                    dtype=cfg.pdtype)
    return p


# ---------------------------------------------------------------------------
# Blocks (full-sequence)
# ---------------------------------------------------------------------------

def _ffn(cfg, bp, y, *, capacity_factor=None, experts=None, layer=None):
    """The block's MLP half: dense MLP, dropless sigmoid MoE (its routed
    experts the stack ``experts`` at ``layer``, see ``moe.split_experts``)
    or softmax MoE with a capacity (``cfg.capacity_factor`` unless given).
    -> (out, aux, expert ids (B, S, k) or None)."""
    zeros = {"aux_loss": jnp.zeros(()), "router_z_loss": jnp.zeros(())}
    if "moe" not in bp:
        return L.mlp_apply(bp["mlp"], y, cfg.mlp_kind, compute_dtype=cfg.cdtype), zeros, None
    if cfg.moe_dropless:
        m, ids = M.moe_dropless(bp["moe"], y, cfg, experts, layer,
                                compute_dtype=cfg.cdtype)
        return m, zeros, ids
    m, aux = M.moe_apply(bp["moe"], y, top_k=cfg.top_k,
                         capacity_factor=capacity_factor or cfg.capacity_factor,
                         kind=cfg.mlp_kind, compute_dtype=cfg.cdtype)
    return m, aux, None


def _attn_block(cfg, bp, x, positions, *, window=None, emit_cache=False,
                experts=None, layer=None):
    """-> (x, aux, the layer's cache entry (k, v) or MLA latent, expert ids)."""
    h = L.rmsnorm_apply(bp["ln1"], x, cfg.norm_eps)
    if cfg.uses_mla:
        h, kv = A.mla_apply(bp["attn"], h, positions, cfg, compute_dtype=cfg.cdtype)
    else:
        h, kv = A.attn_apply(bp["attn"], h, positions, cfg, causal=cfg.causal,
                             window=window, compute_dtype=cfg.cdtype)
    x = x + h
    m, aux, ids = _ffn(cfg, bp, L.rmsnorm_apply(bp["ln2"], x, cfg.norm_eps),
                       experts=experts, layer=layer)
    return x + m, aux, (kv if emit_cache else None), ids


def _mamba_block(cfg, bp, x):
    out = S.mamba_apply(bp["mamba"], L.rmsnorm_apply(bp["ln"], x, cfg.norm_eps),
                        cfg, chunk=cfg.ssd_chunk, compute_dtype=cfg.cdtype)
    y, state = out
    return x + y, state


def _shared_block(cfg, sp, x, positions, *, window=None):
    h, kv = A.attn_apply(sp["attn"], L.rmsnorm_apply(sp["ln1"], x, cfg.norm_eps),
                         positions, cfg, causal=True, window=window,
                         compute_dtype=cfg.cdtype)
    x = x + h
    m = L.mlp_apply(sp["mlp"], L.rmsnorm_apply(sp["ln2"], x, cfg.norm_eps),
                    cfg.mlp_kind, compute_dtype=cfg.cdtype)
    return x + m, kv


# ---------------------------------------------------------------------------
# Backbone forward (training / prefill)
# ---------------------------------------------------------------------------

def _embed_inputs(cfg, params, batch):
    """-> (x (B,S',D), positions (B,S'), text_offset)."""
    if cfg.family == "audio":
        x = batch["frames"].astype(cfg.cdtype)
        b, s = x.shape[:2]
        return x, jnp.broadcast_to(jnp.arange(s)[None], (b, s)), 0
    x = L.embed_apply(params["embed"], batch["tokens"], cfg.cdtype)
    if cfg.family == "vlm":
        patches = batch["patch_embeds"].astype(cfg.cdtype)
        x = jnp.concatenate([patches, x], axis=1)
        off = patches.shape[1]
    else:
        off = 0
    b, s = x.shape[:2]
    return x, jnp.broadcast_to(jnp.arange(s)[None], (b, s)), off


def _seq_specs(cfg, mesh):
    from jax.sharding import PartitionSpec as P
    baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = baxes if len(baxes) > 1 else (baxes[0] if baxes else None)
    return bspec, P(bspec, "model", None)


def _seq_scan_mamba(cfg, mesh, blocks, x):
    """Sequence-parallel scan over mamba blocks via shard_map
    (context-parallel SSD — see models/mamba2.mamba_apply_seq)."""
    from jax.sharding import PartitionSpec as P
    bspec, xspec = _seq_specs(cfg, mesh)

    def local(blocks_loc, x_loc):
        def body(carry, bp):
            h = L.rmsnorm_apply(bp["ln"], carry, cfg.norm_eps)
            y, st = S.mamba_apply_seq(bp["mamba"], h, cfg,
                                      chunk=cfg.ssd_chunk,
                                      compute_dtype=cfg.cdtype)
            return carry + y, st
        body = jax.checkpoint(body) if cfg.remat else body
        return jax.lax.scan(body, x_loc, blocks_loc)

    pspec = jax.tree.map(lambda _: P(), blocks)
    d_inner, pdim, nh, g, n = S.mamba_dims(cfg)
    out_state_spec = {"ssm": P(None, bspec, None, None, None),
                      "conv": {"x": P(None, bspec, None, None),
                               "B": P(None, bspec, None, None),
                               "C": P(None, bspec, None, None)}}
    fn = jax.shard_map(local, mesh=mesh, in_specs=(pspec, xspec),
                       out_specs=(xspec, out_state_spec), check_vma=False)
    return fn(blocks, x)


def _seq_scan_dense(cfg, mesh, blocks, x):
    """Megatron-style sequence parallelism for dense/vlm/audio blocks via
    shard_map (EXPERIMENTS.md Sec. Perf D):

      * residual stream sequence-sharded over `model` — norms/residuals
        local, NO per-layer TP all-reduce;
      * per block: all-gather(x) [bf16] -> TP attention (local Q heads,
        KV local when divisible, else replicated-computed) + TP MLP ->
        partial outputs reduce-scattered back to sequence shards [bf16].
        2 AG + 2 RS per layer replaces 2 all-reduces, halving wire bytes
        AND forcing bf16 (XLA otherwise reduces the f32 dot outputs);
      * explicit ZeRO: weights arrive FSDP-sharded over `data` and are
        all-gathered per layer INSIDE the scan; AD transposes that gather
        into a reduce-scatter of the gradients (ZeRO-2 semantics).
    """
    from jax.sharding import PartitionSpec as P
    bspec, xspec = _seq_specs(cfg, mesh)
    tp = int(mesh.shape["model"])
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    h_loc = H // tp
    kv_shardable = (KV % tp == 0)
    has_data = "data" in mesh.axis_names

    def gather_w(w, axis=0):  # explicit FSDP gather over `data`
        if has_data:
            return jax.lax.all_gather(w, "data", axis=axis, tiled=True)
        return w

    def local(blocks_loc, x_loc):
        nsh = jax.lax.axis_size("model")
        me = jax.lax.axis_index("model")
        b, s_loc, d = x_loc.shape
        s = s_loc * nsh
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

        def body(carry, bp):
            x = carry
            # --- attention (TP over heads, full sequence) --------------
            h_ln = L.rmsnorm_apply(bp["ln1"], x, cfg.norm_eps)
            g = jax.lax.all_gather(h_ln, "model", axis=1, tiled=True)
            cd = cfg.cdtype
            wq = gather_w(bp["attn"]["q"]["w"]).astype(cd)
            q = (g.astype(cd) @ wq).reshape(b, s, h_loc, hd)
            wk = gather_w(bp["attn"]["k"]["w"]).astype(cd)
            wv = gather_w(bp["attn"]["v"]["w"]).astype(cd)
            k = (g.astype(cd) @ wk)
            v = (g.astype(cd) @ wv)
            if kv_shardable:
                kv_loc = KV // tp
                k = k.reshape(b, s, kv_loc, hd)
                v = v.reshape(b, s, kv_loc, hd)
                rep = h_loc // kv_loc
            else:  # replicated KV compute (KV small, e.g. GQA kv=8)
                k = k.reshape(b, s, KV, hd)
                v = v.reshape(b, s, KV, hd)
                # map local q heads to their kv groups
                qh = me * h_loc + jnp.arange(h_loc)
                kv_idx = qh * KV // H
                k = jnp.take(k, kv_idx, axis=2)
                v = jnp.take(v, kv_idx, axis=2)
                rep = 1
            q = L.apply_rope(q, positions, cfg.rope_theta)
            k = L.apply_rope(k, positions, cfg.rope_theta)
            kr = A._repeat_kv(k, rep)
            vr = A._repeat_kv(v, rep)
            o = A.chunked_attention(q, kr, vr, cfg.causal, None)
            wo = gather_w(bp["attn"]["o"]["w"], axis=1).astype(cd)
            partial = o.reshape(b, s, h_loc * hd) @ wo          # (b,S,D) partial
            attn_out = jax.lax.psum_scatter(partial, "model",
                                            scatter_dimension=1, tiled=True)
            x = x + attn_out.astype(x.dtype)
            # --- MLP (TP over d_ff, full sequence) ---------------------
            h2 = L.rmsnorm_apply(bp["ln2"], x, cfg.norm_eps)
            g2 = jax.lax.all_gather(h2, "model", axis=1, tiled=True).astype(cd)
            w_in = gather_w(bp["mlp"]["w_in"]["w"]).astype(cd)
            hmid = g2 @ w_in
            if cfg.mlp_kind in ("swiglu", "geglu"):
                act = jax.nn.silu if cfg.mlp_kind == "swiglu" else jax.nn.gelu
                w_g = gather_w(bp["mlp"]["w_gate"]["w"]).astype(cd)
                hmid = act(g2 @ w_g) * hmid
            elif cfg.mlp_kind == "relu2":
                hmid = jnp.square(jax.nn.relu(hmid))
            else:
                hmid = jax.nn.gelu(hmid)
            w_out = gather_w(bp["mlp"]["w_out"]["w"], axis=1).astype(cd)
            partial2 = hmid @ w_out
            mlp_out = jax.lax.psum_scatter(partial2, "model",
                                           scatter_dimension=1, tiled=True)
            return x + mlp_out.astype(x.dtype), None

        body = jax.checkpoint(body) if cfg.remat else body
        x_loc, _ = jax.lax.scan(body, x_loc, blocks_loc)
        return x_loc

    # in_specs: weights FSDP over data (dim0 after the stacked L dim) and
    # TP over model on their output/input dim per Megatron convention
    d_ax = "data" if has_data else None

    def wspec(path_leaf):
        tokens, leaf = path_leaf
        name = tokens[-1]
        if "attn" in tokens:
            proj = tokens[tokens.index("attn") + 1]
            if proj == "q" and name == "w":
                return P(None, d_ax, "model")
            if proj in ("k", "v") and name == "w":
                return P(None, d_ax, "model" if kv_shardable else None)
            if proj == "o" and name == "w":
                return P(None, "model", d_ax)
        if "mlp" in tokens:
            proj = tokens[tokens.index("mlp") + 1]
            if proj in ("w_in", "w_gate") and name == "w":
                return P(None, d_ax, "model")
            if proj == "w_out" and name == "w":
                return P(None, "model", d_ax)
        return P(*([None] * leaf.ndim))

    import re as _re
    flat, treedef = jax.tree_util.tree_flatten_with_path(blocks)
    specs = []
    for path, leaf in flat:
        tokens = _re.findall(r"\['([^']+)'\]", jax.tree_util.keystr(path))
        specs.append(wspec((tokens, leaf)))
    pspec = jax.tree_util.tree_unflatten(treedef, specs)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(pspec, xspec),
                       out_specs=xspec, check_vma=False)
    return fn(blocks, x)


def _stacked_forward(cfg, params, x, positions, *, window=None, mesh=None,
                     seq_parallel=False):
    """scan over homogeneous stacked blocks.  Returns (x, aux, caches)."""
    aux0 = {"aux_loss": jnp.zeros(()), "router_z_loss": jnp.zeros(())}

    if seq_parallel and cfg.family in ("dense", "vlm", "audio"):
        x = _seq_scan_dense(cfg, mesh, params["blocks"], x)
        return x, aux0, {"k": None, "v": None}

    if cfg.family in ("ssm",):
        if seq_parallel:
            x, states = _seq_scan_mamba(cfg, mesh, params["blocks"], x)
            return x, aux0, {"ssm": states["ssm"], "conv": states["conv"]}
        def body(carry, bp):
            y, state = _mamba_block(cfg, bp, carry)
            return y, state
        body = jax.checkpoint(body) if cfg.remat else body
        x, states = jax.lax.scan(body, x, params["blocks"])
        return x, aux0, {"ssm": states["ssm"], "conv": states["conv"]}

    if cfg.family == "hybrid":
        return _hybrid_forward(cfg, params, x, positions, window=window,
                               mesh=mesh, seq_parallel=seq_parallel)

    def run(x, aux, blocks):
        blocks, experts = M.split_experts(cfg, blocks)

        def body(carry, xs):
            x, aux = carry
            bp, li = xs
            y, a, kv, ids = _attn_block(cfg, bp, x, positions, window=window,
                                        emit_cache=True, experts=experts, layer=li)
            aux = {k: aux[k] + a[k] for k in aux}
            return (y, aux), (kv, ids)
        body = jax.checkpoint(body) if cfg.remat else body
        n = jax.tree.leaves(blocks)[0].shape[0]
        return jax.lax.scan(body, (x, aux), (blocks, jnp.arange(n)))

    kvs = []
    if "dense" in params:
        (x, aux0), (kv, _) = run(x, aux0, params["dense"])
        kvs.append(kv)
    (x, aux), (kv, routes) = run(x, aux0, params["blocks"])
    kvs = jax.tree.map(lambda *a: jnp.concatenate(a, 0), *kvs, kv)
    if cfg.uses_mla:
        return x, aux, {"lat": kvs, "routes": routes}
    return x, aux, {"k": kvs[0], "v": kvs[1]}


def _hybrid_groups(cfg):
    n_full = cfg.num_layers // cfg.attn_every
    tail = cfg.num_layers - n_full * cfg.attn_every
    return n_full, tail


def _hybrid_forward(cfg, params, x, positions, *, window=None, mesh=None,
                    seq_parallel=False):
    n_full, tail = _hybrid_groups(cfg)
    per = cfg.attn_every
    aux0 = {"aux_loss": jnp.zeros(()), "router_z_loss": jnp.zeros(())}

    def mbody(carry, bp):
        y, state = _mamba_block(cfg, bp, carry)
        return y, state
    mbody = jax.checkpoint(mbody) if cfg.remat else mbody

    def run_group(x, sl):
        if seq_parallel:
            return _seq_scan_mamba(cfg, mesh, sl, x)
        return jax.lax.scan(mbody, x, sl)

    states, kvs = [], []
    for gi in range(n_full):
        sl = jax.tree.map(lambda a: a[gi * per:(gi + 1) * per], params["blocks"])
        x, st = run_group(x, sl)
        states.append(st)
        x, kv = _shared_block(cfg, params["shared"], x, positions, window=window)
        if seq_parallel:  # keep the residual stream sequence-sharded
            from jax.sharding import NamedSharding
            _, xspec = _seq_specs(cfg, mesh)
            x = jax.lax.with_sharding_constraint(x, NamedSharding(mesh, xspec))
        kvs.append(kv)
    if tail:
        sl = jax.tree.map(lambda a: a[n_full * per:], params["blocks"])
        x, st = run_group(x, sl)
        states.append(st)
    stacked_states = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *states)
    caches = {
        "ssm": stacked_states["ssm"],
        "conv": stacked_states["conv"],
        "k": jnp.stack([k for k, _ in kvs]) if kvs else None,
        "v": jnp.stack([v for _, v in kvs]) if kvs else None,
    }
    return x, aux0, caches


def backbone(cfg, params, batch, *, window=None, mesh=None,
             seq_parallel=False):
    """-> (final normed hidden states, aux, caches, vlm text offset)."""
    x, positions, off = _embed_inputs(cfg, params, batch)
    x, aux, caches = _stacked_forward(cfg, params, x, positions,
                                      window=window, mesh=mesh,
                                      seq_parallel=seq_parallel)
    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    return x, aux, caches, off


def forward(cfg, params, batch, *, window=None, emit_caches=False,
            mesh=None, seq_parallel=False):
    """-> (logits f32, aux, caches)."""
    x, aux, caches, off = backbone(cfg, params, batch, window=window,
                                   mesh=mesh, seq_parallel=seq_parallel)
    if cfg.family == "audio" or not cfg.tie_embeddings:
        logits = L.dense_apply(params["lm_head"], x, compute_dtype=cfg.cdtype)
        logits = logits.astype(jnp.float32)
    else:
        logits = L.unembed_apply(params["embed"], x, cfg.cdtype)
    if cfg.family == "vlm" and off:
        logits = logits[:, off:]
    return logits, aux, (caches if emit_caches else None)


def train_loss(cfg, params, batch, mesh=None, seq_parallel=False):
    """CE via the vocab-parallel shard_map path when a mesh is given
    (see models/losses.py for why GSPMD needs the help).  Under sequence
    parallelism the vocab stays replicated and CE is position-local, so
    the plain path is already optimal."""
    from . import losses
    x, aux, _, off = backbone(cfg, params, batch, mesh=mesh,
                              seq_parallel=seq_parallel)
    if seq_parallel and cfg.uses_mamba:
        mesh = None  # vocab replicated in the ssm seq mode: plain CE
    if cfg.family == "vlm" and off:
        x = x[:, off:]
    if cfg.family == "audio":
        # classifier head has a bias and tiny vocab: plain path
        logits = L.dense_apply(params["lm_head"], x, compute_dtype=cfg.cdtype
                               ).astype(jnp.float32)
        loss = losses.plain_ce(logits, batch["labels"], cfg.z_loss)
    else:
        tied = cfg.tie_embeddings
        w = params["embed"]["table"] if tied else params["lm_head"]["w"]
        loss = losses.vocab_parallel_ce(x, w, batch["labels"], mesh=mesh,
                                        tied=tied, z_loss=cfg.z_loss,
                                        compute_dtype=cfg.cdtype)
    total = loss + cfg.aux_loss_weight * (aux["aux_loss"] + aux["router_z_loss"])
    return total, {"ce": loss, **aux}


# ---------------------------------------------------------------------------
# Serving: prefill + single-token decode with KV / SSM caches
# ---------------------------------------------------------------------------

def init_cache(cfg, batch_size: int, max_len: int, dtype=jnp.bfloat16):
    c: dict[str, Any] = {"len": jnp.zeros((), jnp.int32)}
    Lr = cfg.num_layers
    if cfg.uses_mla:          # the latent row [c_kv | k_pe] of each position
        c.update(_latent_cache(cfg, Lr, batch_size, max_len, dtype))
    elif cfg.family in ("dense", "moe", "vlm", "audio"):
        c["k"] = jnp.zeros((Lr, batch_size, max_len, cfg.num_kv_heads, cfg.head_dim), dtype)
        c["v"] = jnp.zeros_like(c["k"])
    elif cfg.family == "ssm":
        d_inner, pdim, nh, g, n = S.mamba_dims(cfg)
        c["ssm"] = jnp.zeros((Lr, batch_size, nh, n, pdim), jnp.float32)
        c["conv"] = _conv_cache(cfg, Lr, batch_size, dtype)
    elif cfg.family == "hybrid":
        d_inner, pdim, nh, g, n = S.mamba_dims(cfg)
        n_full, _ = _hybrid_groups(cfg)
        c["ssm"] = jnp.zeros((Lr, batch_size, nh, n, pdim), jnp.float32)
        c["conv"] = _conv_cache(cfg, Lr, batch_size, dtype)
        c["k"] = jnp.zeros((n_full, batch_size, max_len, cfg.num_kv_heads, cfg.head_dim), dtype)
        c["v"] = jnp.zeros_like(c["k"])
    return c


def _latent_cache(cfg, Lr, batch_size, max_len, dtype):
    """MLA's cache: c_kv in ``ckv`` (L, B, T, r) and k_pe in ``kpe``
    (L, B, T/2, 2 rope), row u holding position u in its first half and
    u + T/2 in its second — r + rope values a position, with neither
    array's minor dimension padded to the chip's 128 lanes (a 576-wide
    row would be, or would be laid out positions-minor, which an in-place
    row write has to copy whole)."""
    if max_len % 2:
        raise ValueError(f"an MLA cache needs an even max_len, got {max_len}")
    r, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    return {"ckv": jnp.zeros((Lr, batch_size, max_len, r), dtype),
            "kpe": jnp.zeros((Lr, batch_size, max_len // 2, 2 * rope), dtype)}


def _store_latent(cache, lat, slot):
    """Write the forward's latent rows ``lat`` (L, b, n, r + rope) of
    positions 0..n-1 into batch rows slot..slot+b-1 of ``cache``."""
    r, h = cache["ckv"].shape[-1], cache["kpe"].shape[2]
    n, dt = lat.shape[2], cache["ckv"].dtype
    c = dict(cache)
    c["ckv"] = jax.lax.dynamic_update_slice(cache["ckv"], lat[..., :r].astype(dt),
                                            (0, slot, 0, 0))
    kp = lat[..., r:].astype(dt)
    c["kpe"] = jax.lax.dynamic_update_slice(cache["kpe"], kp[:, :, :h], (0, slot, 0, 0))
    if n > h:
        c["kpe"] = jax.lax.dynamic_update_slice(c["kpe"], kp[:, :, h:],
                                                (0, slot, 0, kp.shape[-1]))
    return c


def _conv_cache(cfg, Lr, batch_size, dtype):
    d_inner, pdim, nh, g, n = S.mamba_dims(cfg)
    w = S.CONV_W - 1
    return {"x": jnp.zeros((Lr, batch_size, w, d_inner), dtype),
            "B": jnp.zeros((Lr, batch_size, w, g * n), dtype),
            "C": jnp.zeros((Lr, batch_size, w, g * n), dtype)}


def prefill(cfg, params, batch, max_len: int | None = None, *, window=None,
            mesh=None, seq_parallel=False):
    """Full-sequence forward emitting caches sized to max_len."""
    logits, _, caches = forward(cfg, params, batch, window=window,
                                emit_caches=True, mesh=mesh,
                                seq_parallel=seq_parallel)
    b = logits.shape[0]
    s = (batch["tokens"] if "tokens" in batch else batch["frames"]).shape[1]
    if cfg.family == "vlm":
        s += batch["patch_embeds"].shape[1]
    max_len = max_len or s
    cache = init_cache(cfg, b, max_len, dtype=cfg.cdtype)
    if caches.get("lat") is not None:
        cache = _store_latent(cache, caches["lat"], 0)
    if caches.get("k") is not None:
        cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], caches["k"].astype(cache["k"].dtype), (0, 0, 0, 0, 0))
        cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], caches["v"].astype(cache["v"].dtype), (0, 0, 0, 0, 0))
    if caches.get("ssm") is not None:
        cache["ssm"] = caches["ssm"].astype(cache["ssm"].dtype)
        cache["conv"] = jax.tree.map(lambda dst, src: src.astype(dst.dtype),
                                     cache["conv"], caches["conv"])
    cache["len"] = jnp.asarray(s, jnp.int32)
    return logits, cache


def decode_step(cfg, params, cache, tokens, *, window=None, mesh=None,
                splitkv=False):
    """tokens: (B, 1) int32 -> (logits (B,1,V) f32, updated cache).
    ``splitkv`` (with ``mesh``): flash-decoding over a sequence-sharded
    KV cache (attention.attn_decode_splitkv)."""
    x = L.embed_apply(params["embed"], tokens, cfg.cdtype)
    clen = cache["len"]

    if cfg.uses_mla:
        b = tokens.shape[0]
        x, cache, _ = _mla_decode(cfg, params, cache, x,
                                  jnp.full((b,), clen, jnp.int32), jnp.ones((b,), bool))
        cache = dict(cache, len=clen + 1)

    elif cfg.family in ("dense", "moe", "vlm"):
        def body(x, xs):
            bp, ck, cv = xs
            h = L.rmsnorm_apply(bp["ln1"], x, cfg.norm_eps)
            if splitkv:
                h, nk, nv = A.attn_decode_splitkv(
                    bp["attn"], h, ck, cv, clen, cfg, mesh=mesh,
                    window=window, compute_dtype=cfg.cdtype)
            else:
                h, nk, nv = A.attn_decode(bp["attn"], h, ck, cv, clen, cfg,
                                          window=window, compute_dtype=cfg.cdtype)
            x = x + h
            y = L.rmsnorm_apply(bp["ln2"], x, cfg.norm_eps)
            if cfg.family == "moe":
                # serving must never drop a token: capacity covers the
                # worst case (all tokens routed to one expert).
                m, _ = M.moe_apply(bp["moe"], y, top_k=cfg.top_k,
                                   capacity_factor=cfg.num_experts / cfg.top_k,
                                   kind=cfg.mlp_kind, compute_dtype=cfg.cdtype)
            else:
                m = L.mlp_apply(bp["mlp"], y, cfg.mlp_kind, compute_dtype=cfg.cdtype)
            return x + m, (nk, nv)
        x, (nk, nv) = jax.lax.scan(body, x, (params["blocks"], cache["k"], cache["v"]))
        cache = dict(cache, k=nk, v=nv, len=clen + 1)

    elif cfg.family == "ssm":
        def body(x, xs):
            bp, conv, ssm = xs
            h = L.rmsnorm_apply(bp["ln"], x, cfg.norm_eps)
            y, nconv, nssm = S.mamba_decode(bp["mamba"], h, conv, ssm, cfg,
                                            compute_dtype=cfg.cdtype)
            return x + y, (nconv, nssm)
        x, (nconv, nssm) = jax.lax.scan(body, x, (params["blocks"], cache["conv"], cache["ssm"]))
        cache = dict(cache, conv=nconv, ssm=nssm, len=clen + 1)

    elif cfg.family == "hybrid":
        n_full, tail = _hybrid_groups(cfg)
        per = cfg.attn_every
        def body(x, xs):
            bp, conv, ssm = xs
            h = L.rmsnorm_apply(bp["ln"], x, cfg.norm_eps)
            y, nconv, nssm = S.mamba_decode(bp["mamba"], h, conv, ssm, cfg,
                                            compute_dtype=cfg.cdtype)
            return x + y, (nconv, nssm)
        convs, ssms, ks, vs = [], [], [], []
        sp = params["shared"]
        for gi in range(n_full):
            sl = lambda a, g=gi: a[g * per:(g + 1) * per]
            x, (nc, ns) = jax.lax.scan(
                body, x, (jax.tree.map(sl, params["blocks"]),
                          jax.tree.map(sl, cache["conv"]), sl(cache["ssm"])))
            convs.append(nc); ssms.append(ns)
            h = L.rmsnorm_apply(sp["ln1"], x, cfg.norm_eps)
            h, nk, nv = A.attn_decode(sp["attn"], h, cache["k"][gi], cache["v"][gi],
                                      clen, cfg, window=window, compute_dtype=cfg.cdtype)
            x = x + h
            x = x + L.mlp_apply(sp["mlp"], L.rmsnorm_apply(sp["ln2"], x, cfg.norm_eps),
                                cfg.mlp_kind, compute_dtype=cfg.cdtype)
            ks.append(nk); vs.append(nv)
        if tail:
            sl = lambda a: a[n_full * per:]
            x, (nc, ns) = jax.lax.scan(
                body, x, (jax.tree.map(sl, params["blocks"]),
                          jax.tree.map(sl, cache["conv"]), sl(cache["ssm"])))
            convs.append(nc); ssms.append(ns)
        cache = dict(cache,
                     conv=jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *convs),
                     ssm=jnp.concatenate(ssms, 0),
                     k=jnp.stack(ks), v=jnp.stack(vs), len=clen + 1)
    else:
        raise ValueError(f"no decode path for family {cfg.family!r}")

    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    return _head(cfg, params, x), cache


# ---------------------------------------------------------------------------
# Slotted caches: per-slot fill levels for continuous batching
# (serve/engine.py rides serve/scheduler.SlotScheduler over these)
# ---------------------------------------------------------------------------

def init_slot_cache(cfg, n_slots: int, max_len: int, dtype=jnp.bfloat16):
    """Slot-table decode cache: identical per-family layout to
    :func:`init_cache`, but with a per-slot fill level ``cache["pos"]``
    ((S,) int32) instead of the single shared ``cache["len"]`` — the
    state layout that lets a finished sequence's slot be re-prefilled
    while its neighbours keep decoding."""
    c = init_cache(cfg, n_slots, max_len, dtype)
    del c["len"]
    c["pos"] = jnp.zeros((n_slots,), jnp.int32)
    return c


def reset_cache_slot(cfg, cache, slot: int):
    """Zero one slot's rows of a slotted cache (recycling hygiene: the SSM
    carry is additive and MUST be cleared; KV is cleared too so stale keys
    can never leak past an off-by-one in the position mask)."""
    c = dict(cache)
    c["pos"] = cache["pos"].at[slot].set(0)
    for name in ("k", "v", "ssm", "ckv", "kpe"):
        if cache.get(name) is not None:
            c[name] = cache[name].at[:, slot].set(0)
    if cache.get("conv") is not None:
        c["conv"] = jax.tree.map(lambda a: a.at[:, slot].set(0), cache["conv"])
    return c


def prefill_into_slot(cfg, params, cache, batch, slot, *, length=None,
                      window=None, return_hidden=False, return_routes=False):
    """Prefill ONE sequence (leading batch dim 1) and write its caches into
    row ``slot`` of a slotted cache — the admission half of continuous
    batching: a freed slot is re-prefilled without touching the other
    residents.  ``slot`` may be a traced scalar, so the whole function jits
    once per prompt length.  ``length`` (traced, optional): the prompt's
    real length when ``batch`` is padded at its end to a length bucket; the
    slot's ``pos`` is set to it, and what lies past it is never attended.
    Returns ``(logits (1, 1, V) f32 of the last real position, new cache)``
    — the head runs at that position only — or its final normed hidden
    state ``(1, 1, D)`` with ``return_hidden=True`` (quantized-head serving
    applies its own head).  ``return_routes`` adds the chosen experts of
    every position at each MoE layer, ``(1, s, n_moe, k)`` (MLA MoE)."""
    x, _, caches, _ = backbone(cfg, params, batch, window=window)
    s = x.shape[1]                       # includes vlm patch positions
    n = jnp.asarray(s if length is None else length, jnp.int32)
    x = jax.lax.dynamic_slice_in_dim(x, n - 1, 1, axis=1)
    out = x if return_hidden else _head(cfg, params, x)
    slot = jnp.asarray(slot, jnp.int32)
    c = dict(cache)
    for name in ("k", "v"):
        if caches.get(name) is not None:
            new = caches[name].astype(cache[name].dtype)
            c[name] = jax.lax.dynamic_update_slice(
                cache[name], new, (0, slot) + (0,) * (new.ndim - 2))
    if caches.get("lat") is not None:
        c = _store_latent(c, caches["lat"], slot)
    if caches.get("ssm") is not None:
        c["ssm"] = jax.lax.dynamic_update_slice(
            cache["ssm"], caches["ssm"].astype(cache["ssm"].dtype),
            (0, slot, 0, 0, 0))
        c["conv"] = jax.tree.map(
            lambda dst, src: jax.lax.dynamic_update_slice(
                dst, src.astype(dst.dtype), (0, slot, 0, 0)),
            cache["conv"], caches["conv"])
    c["pos"] = jax.lax.dynamic_update_slice(cache["pos"], n[None], (slot,))
    if return_routes:
        routes = caches.get("routes")
        return out, c, None if routes is None else jnp.moveaxis(routes, 0, 2)
    return out, c


def _head(cfg, params, x):
    """Final normed hidden states -> f32 logits (untied head or tied embed)."""
    if not cfg.tie_embeddings and "lm_head" in params:
        return L.dense_apply(params["lm_head"], x,
                             compute_dtype=cfg.cdtype).astype(jnp.float32)
    return L.unembed_apply(params["embed"], x, cfg.cdtype)


def _mla_decode(cfg, params, cache, x, pos, active):
    """One token per row through the dense layers and the scanned MoE stack
    over the latent cache (``_latent_cache``).  Each active row writes its
    new latent row in place at ``pos[b]`` (inactive rows write nothing),
    then attends over 0..pos[b].  The cache rides in the scan's carry, so
    under donation the device holds one cache.  -> (x, cache, expert ids
    (n_moe, B, 1, k) or None)."""
    cd, r, kd = cfg.cdtype, cfg.kv_lora_rank, _n_dense(cfg)
    rows = jnp.arange(x.shape[0])
    wpos = jnp.where(active, pos, cache["ckv"].shape[2])     # past the end: dropped
    h = cache["kpe"].shape[2]

    def layer(x, ckv, kpe, bp, li, experts):
        y = L.rmsnorm_apply(bp["ln1"], x, cfg.norm_eps)
        q_nope, q_pe, new = A.mla_qkv(bp["attn"], y, pos[:, None], cfg, compute_dtype=cd)
        new = new[:, 0].astype(ckv.dtype)
        ckv = ckv.at[li, rows, wpos].set(new[:, :r], mode="drop", unique_indices=True)
        # k_pe lands in its row's half: row wpos % h, lanes from (wpos // h) rope
        at = jnp.stack([jnp.full_like(rows, li), rows, wpos % h,
                        (wpos // h) * cfg.qk_rope_head_dim], axis=-1)
        kpe = jax.lax.scatter(kpe, at, new[:, r:], jax.lax.ScatterDimensionNumbers(
            update_window_dims=(1,), inserted_window_dims=(0, 1, 2),
            scatter_dims_to_operand_dims=(0, 1, 2, 3)),
            unique_indices=True, mode=jax.lax.GatherScatterMode.FILL_OR_DROP)
        x = x + A.mla_decode_attend(bp["attn"], q_nope, q_pe, ckv, kpe, li, pos, cfg,
                                    compute_dtype=cd)
        m, _, ids = _ffn(cfg, bp, L.rmsnorm_apply(bp["ln2"], x, cfg.norm_eps),
                         capacity_factor=cfg.num_experts / max(cfg.top_k, 1),
                         experts=experts, layer=li - kd)
        return x + m, ckv, kpe, ids

    def scan(carry, blocks, first):
        blocks, experts = M.split_experts(cfg, blocks)

        def body(carry, xs):
            x, ckv, kpe = carry
            bp, li = xs
            x, ckv, kpe, ids = layer(x, ckv, kpe, bp, li, experts)
            return (x, ckv, kpe), ids
        n = jax.tree.leaves(blocks)[0].shape[0]
        return jax.lax.scan(body, carry, (blocks, first + jnp.arange(n)))

    carry = (x, cache["ckv"], cache["kpe"])
    if kd:
        carry, _ = scan(carry, params["dense"], 0)
    carry, ids = scan(carry, params["blocks"], kd)
    x, ckv, kpe = carry
    return x, dict(cache, ckv=ckv, kpe=kpe), ids


def decode_step_slotted(cfg, params, cache, tokens, active=None, *,
                        window=None, return_hidden=False, return_routes=False):
    """One decode tick over a slotted cache.  tokens: (S, 1) int32 ->
    ``(logits (S, 1, V) f32, new cache)`` — or final normed hidden states
    ``(S, 1, D)`` with ``return_hidden=True``.  ``return_routes`` adds each
    row's chosen experts at every MoE layer, ``(S, n_moe, k)`` (MLA MoE;
    else None).

    Unlike :func:`decode_step`, every slot advances at its own
    ``cache["pos"][b]``: row ``b`` writes its K/V (or SSM update) at its
    own position and attends over its own prefix.  ``active``: (S,) bool —
    inactive slots (free, or awaiting admission) keep cache AND ``pos``
    bit-for-bit; their outputs are computed-and-discarded so the tick stays
    one fixed-shape jit call regardless of occupancy."""
    x = L.embed_apply(params["embed"], tokens, cfg.cdtype)
    pos = cache["pos"]
    if active is None:
        active = jnp.ones((tokens.shape[0],), bool)
    active = active.astype(bool)
    routes = None

    if cfg.uses_mla:
        x, cache, ids = _mla_decode(cfg, params, cache, x, pos, active)
        routes = None if ids is None else jnp.moveaxis(ids[:, :, 0], 0, 1)

    elif cfg.family in ("dense", "moe", "vlm"):
        def body(x, xs):
            bp, ck, cv = xs
            h = L.rmsnorm_apply(bp["ln1"], x, cfg.norm_eps)
            h, nk, nv = A.attn_decode_slotted(
                bp["attn"], h, ck, cv, pos, cfg, active=active,
                window=window, compute_dtype=cfg.cdtype)
            x = x + h
            y = L.rmsnorm_apply(bp["ln2"], x, cfg.norm_eps)
            if cfg.family == "moe":
                m, _ = M.moe_apply(bp["moe"], y, top_k=cfg.top_k,
                                   capacity_factor=cfg.num_experts / cfg.top_k,
                                   kind=cfg.mlp_kind, compute_dtype=cfg.cdtype)
            else:
                m = L.mlp_apply(bp["mlp"], y, cfg.mlp_kind,
                                compute_dtype=cfg.cdtype)
            return x + m, (nk, nv)
        x, (nk, nv) = jax.lax.scan(body, x,
                                   (params["blocks"], cache["k"], cache["v"]))
        cache = dict(cache, k=nk, v=nv)

    elif cfg.family == "ssm":
        def body(x, xs):
            bp, conv, ssm = xs
            h = L.rmsnorm_apply(bp["ln"], x, cfg.norm_eps)
            y, nconv, nssm = S.mamba_decode(bp["mamba"], h, conv, ssm, cfg,
                                            compute_dtype=cfg.cdtype)
            nconv = jax.tree.map(
                lambda new, old: jnp.where(active[:, None, None], new, old),
                nconv, conv)
            nssm = jnp.where(active[:, None, None, None], nssm, ssm)
            return x + y, (nconv, nssm)
        x, (nconv, nssm) = jax.lax.scan(
            body, x, (params["blocks"], cache["conv"], cache["ssm"]))
        cache = dict(cache, conv=nconv, ssm=nssm)

    elif cfg.family == "hybrid":
        n_full, tail = _hybrid_groups(cfg)
        per = cfg.attn_every
        def body(x, xs):
            bp, conv, ssm = xs
            h = L.rmsnorm_apply(bp["ln"], x, cfg.norm_eps)
            y, nconv, nssm = S.mamba_decode(bp["mamba"], h, conv, ssm, cfg,
                                            compute_dtype=cfg.cdtype)
            nconv = jax.tree.map(
                lambda new, old: jnp.where(active[:, None, None], new, old),
                nconv, conv)
            nssm = jnp.where(active[:, None, None, None], nssm, ssm)
            return x + y, (nconv, nssm)
        convs, ssms, ks, vs = [], [], [], []
        sp = params["shared"]
        for gi in range(n_full):
            sl = lambda a, g=gi: a[g * per:(g + 1) * per]
            x, (nc, ns) = jax.lax.scan(
                body, x, (jax.tree.map(sl, params["blocks"]),
                          jax.tree.map(sl, cache["conv"]), sl(cache["ssm"])))
            convs.append(nc); ssms.append(ns)
            h = L.rmsnorm_apply(sp["ln1"], x, cfg.norm_eps)
            h, nk, nv = A.attn_decode_slotted(
                sp["attn"], h, cache["k"][gi], cache["v"][gi], pos, cfg,
                active=active, window=window, compute_dtype=cfg.cdtype)
            x = x + h
            x = x + L.mlp_apply(sp["mlp"],
                                L.rmsnorm_apply(sp["ln2"], x, cfg.norm_eps),
                                cfg.mlp_kind, compute_dtype=cfg.cdtype)
            ks.append(nk); vs.append(nv)
        if tail:
            sl = lambda a: a[n_full * per:]
            x, (nc, ns) = jax.lax.scan(
                body, x, (jax.tree.map(sl, params["blocks"]),
                          jax.tree.map(sl, cache["conv"]), sl(cache["ssm"])))
            convs.append(nc); ssms.append(ns)
        cache = dict(cache,
                     conv=jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *convs),
                     ssm=jnp.concatenate(ssms, 0),
                     k=jnp.stack(ks), v=jnp.stack(vs))
    else:
        raise ValueError(f"no slotted decode path for family {cfg.family!r}")

    cache["pos"] = pos + active.astype(jnp.int32)
    x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    out = x if return_hidden else _head(cfg, params, x)
    return (out, cache, routes) if return_routes else (out, cache)
