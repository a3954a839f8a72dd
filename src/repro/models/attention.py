"""Grouped-query attention: training (full/sliding-window causal or
bidirectional) and serving (prefill -> KV cache -> single-token decode).

Sharding notes (see launch/sharding.py): QKV/O projections are TP-sharded on
the flattened head dim; per-head activations get an explicit
sharding_constraint on the head axis only when num_heads % tp == 0 —
otherwise heads stay as XLA lays them out (GSPMD resharding), which is the
documented fallback for minitron (24H) and qwen2 (12H) at tp=16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import layers as L


def attn_init(key, d_model: int, num_heads: int, num_kv_heads: int,
              head_dim: int, *, qkv_bias: bool = False, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    return {
        "q": L.dense_init(ks[0], d_model, num_heads * head_dim, bias=qkv_bias, dtype=dtype),
        "k": L.dense_init(ks[1], d_model, num_kv_heads * head_dim, bias=qkv_bias, dtype=dtype),
        "v": L.dense_init(ks[2], d_model, num_kv_heads * head_dim, bias=qkv_bias, dtype=dtype),
        "o": L.dense_init(ks[3], num_heads * head_dim, d_model, bias=False, dtype=dtype),
    }


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def _repeat_kv(k, n_rep: int):
    """(B, S, KV, hd) -> (B, S, KV*n_rep, hd) by repetition (GQA)."""
    if n_rep == 1:
        return k
    b, s, kv, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, hd)).reshape(b, s, kv * n_rep, hd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def chunked_attention(q, k, v, causal: bool = True, window: int | None = None,
                      q_chunk: int = 512, k_chunk: int = 1024):
    """Flash-style attention: online softmax over KV chunks, never
    materializing the (Sq, Sk) score matrix.  Memory per device drops from
    O(S^2) to O(S * k_chunk) — the fix that makes the 32k-prefill and
    4k-train cells fit HBM (see EXPERIMENTS.md Sec. Dry-run).

    custom_vjp: the backward recomputes score blocks chunk-by-chunk
    (saving only (q, k, v, out, lse)), exactly like the FlashAttention-2
    backward — without it, jax.lax.scan AD would stash O(S^2/chunk)
    per-step residuals and reintroduce the memory cliff.

    q: (B, Sq, H, hd); k,v: (B, Sk, H, hd) -> (B, Sq, H, hd).
    """
    out, _ = _flash_fwd(q, k, v, causal, window, q_chunk, k_chunk)
    return out


def _flash_fwd(q, k, v, causal, window, q_chunk, k_chunk):
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    qc = min(q_chunk, sq)
    kc = min(k_chunk, sk)
    # pad to multiples
    qpad, kpad = (-sq) % qc, (-sk) % kc
    if qpad:
        q = jnp.pad(q, ((0, 0), (0, qpad), (0, 0), (0, 0)))
    if kpad:
        k = jnp.pad(k, ((0, 0), (0, kpad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, kpad), (0, 0), (0, 0)))
    nq, nk = (sq + qpad) // qc, (sk + kpad) // kc
    scale = hd ** -0.5
    qb = q.reshape(b, nq, qc, h, hd)
    kb = k.reshape(b, nk, kc, h, hd)
    vb = v.reshape(b, nk, kc, h, hd)

    def q_block(qi, qx):
        # qx: (b, qc, h, hd); online softmax over kv chunks
        def kv_step(carry, kj):
            m, l, acc = carry
            kx = jax.lax.dynamic_index_in_dim(kb, kj, 1, keepdims=False)
            vx = jax.lax.dynamic_index_in_dim(vb, kj, 1, keepdims=False)
            s = jnp.einsum("bqhd,bkhd->bhqk", qx, kx,
                           preferred_element_type=jnp.float32) * scale
            # additive (qc,kc) bias, NOT a boolean select on the full
            # (b,h,qc,kc) block: XLA hoists/widens per-step pred masks into
            # O(S^2) buffers (observed: 12.9 GB of pred[...] in the 4k-train
            # HLO).  A small f32 bias fuses into the add.  Fully-masked
            # chunks self-correct through the online-softmax `corr` factor.
            qpos = qi * qc + jnp.arange(qc)[:, None]
            kpos = kj * kc + jnp.arange(kc)[None, :]
            msk = kpos < sk
            if causal:
                msk &= kpos <= qpos
            if window is not None:
                msk &= kpos > qpos - window
            s = s + jnp.where(msk, 0.0, -1e30)[None, None]
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = corr * l + p.sum(-1)
            acc_new = corr[..., None] * acc + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(vx.dtype), vx).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, qc), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, qc), jnp.float32)
        a0 = jnp.zeros((b, h, qc, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))            # (b, h, qc)
        return jnp.moveaxis(out, 1, 2), lse                 # (b, qc, h, hd)

    def outer(_, qi):
        o, lse = q_block(qi, jax.lax.dynamic_index_in_dim(qb, qi, 1, keepdims=False))
        return None, (o, lse)

    _, (blocks, lses) = jax.lax.scan(outer, None, jnp.arange(nq))
    out = jnp.moveaxis(blocks, 0, 1).reshape(b, nq * qc, h, hd)[:, :sq]
    lse = jnp.moveaxis(lses, 0, 2).reshape(b, h, nq * qc)[..., :sq]  # (b,h,Sq)
    return out.astype(q.dtype), lse


def _flash_fwd_vjp(q, k, v, causal, window, q_chunk, k_chunk):
    out, lse = _flash_fwd(q, k, v, causal, window, q_chunk, k_chunk)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, q_chunk, k_chunk, res, dout):
    """FlashAttention-2-style backward: one scan over KV chunks; per chunk
    the full-Q score block (Sq x kc) is recomputed from (q, lse)."""
    q, k, v, out, lse = res
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    kc = min(k_chunk, sk)
    kpad = (-sk) % kc
    if kpad:
        k = jnp.pad(k, ((0, 0), (0, kpad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, kpad), (0, 0), (0, 0)))
    nk = (sk + kpad) // kc
    scale = hd ** -0.5
    kb = k.reshape(b, nk, kc, h, hd)
    vb = v.reshape(b, nk, kc, h, hd)
    doutf = dout.astype(jnp.float32)
    delta = jnp.einsum("bqhd,bqhd->bhq", doutf, out.astype(jnp.float32))
    qpos = jnp.arange(sq)[:, None]

    def kv_step(dq_acc, kj):
        kx = jax.lax.dynamic_index_in_dim(kb, kj, 1, keepdims=False)
        vx = jax.lax.dynamic_index_in_dim(vb, kj, 1, keepdims=False)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kx,
                       preferred_element_type=jnp.float32) * scale
        kpos = kj * kc + jnp.arange(kc)[None, :]
        msk = kpos < sk
        if causal:
            msk = msk & (kpos <= qpos)
        if window is not None:
            msk = msk & (kpos > qpos - window)
        s = s + jnp.where(msk, 0.0, -1e30)[None, None]   # (Sq,kc) bias only
        p = jnp.exp(s - lse[..., None])                  # masked -> exp(-1e30)=0
        dv = jnp.einsum("bhqk,bqhd->bkhd", p, doutf)
        dp = jnp.einsum("bqhd,bkhd->bhqk", doutf, vx.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * scale
        dq_blk = jnp.einsum("bhqk,bkhd->bqhd", ds, kx.astype(jnp.float32))
        dk = jnp.einsum("bhqk,bqhd->bkhd", ds, q.astype(jnp.float32))
        return dq_acc + dq_blk, (dk, dv)

    dq0 = jnp.zeros((b, sq, h, hd), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(kv_step, dq0, jnp.arange(nk))
    dk = jnp.moveaxis(dks, 0, 1).reshape(b, nk * kc, h, hd)[:, :sk]
    dv = jnp.moveaxis(dvs, 0, 1).reshape(b, nk * kc, h, hd)[:, :sk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


chunked_attention.defvjp(_flash_fwd_vjp, _flash_bwd)


def attention_scores(q, k, v, *, causal: bool, window: int | None = None,
                     q_offset: int = 0, kv_len_mask=None):
    """q: (B, Sq, H, hd); k,v: (B, Sk, H, hd).  Returns (B, Sq, H, hd).

    ``q_offset``: absolute position of q[0] (decode: offset = cache length).
    ``kv_len_mask``: optional (B, Sk) bool of valid cache slots.
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    scale = hd ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    qpos = q_offset + jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    if kv_len_mask is not None:
        logits = jnp.where(kv_len_mask[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


CHUNKED_THRESHOLD = 2048  # use flash-style path for S >= this


def attn_apply(p, x, positions, cfg, *, causal=True, window=None,
               compute_dtype=jnp.bfloat16):
    """Full-sequence attention (training / prefill). x: (B, S, D)."""
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = _split_heads(L.dense_apply(p["q"], x, compute_dtype=compute_dtype), H, hd)
    k = _split_heads(L.dense_apply(p["k"], x, compute_dtype=compute_dtype), KV, hd)
    v = _split_heads(L.dense_apply(p["v"], x, compute_dtype=compute_dtype), KV, hd)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    kr, vr = _repeat_kv(k, H // KV), _repeat_kv(v, H // KV)
    if x.shape[1] >= CHUNKED_THRESHOLD:
        o = chunked_attention(q, kr, vr, causal, window)
    else:
        o = attention_scores(q, kr, vr, causal=causal, window=window)
    o = L.dense_apply(p["o"], o.reshape(x.shape[:-1] + (H * hd,)),
                      compute_dtype=compute_dtype)
    return o, (k, v)  # caller may keep (k, v) as the prefill cache


def attn_decode_splitkv(p, x, cache_k, cache_v, cache_len, cfg, *, mesh,
                        window=None, compute_dtype=jnp.bfloat16):
    """Flash-decoding for KV-head counts that do not divide tp: the cache
    shards its SEQUENCE dim over ``model`` (zero padding, balanced memory);
    each shard attends over its local span and the partials merge with a
    log-sum-exp psum of (m, l, acc) — (B,H)+(B,H,hd) sized, ~100 KB — per
    layer, instead of GSPMD's involuntary full-cache rematerialization
    (measured 22 GB/device/step on nemotron decode_32k; EXPERIMENTS.md
    Sec. Perf C2).  The new token's K/V is written by the owning shard.
    """
    from jax.sharding import PartitionSpec as P
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s_max = cache_k.shape[1]
    b = x.shape[0]
    pos = jnp.full((b, 1), cache_len, jnp.int32)
    q = _split_heads(L.dense_apply(p["q"], x, compute_dtype=compute_dtype), H, hd)
    k = _split_heads(L.dense_apply(p["k"], x, compute_dtype=compute_dtype), KV, hd)
    v = _split_heads(L.dense_apply(p["v"], x, compute_dtype=compute_dtype), KV, hd)
    q = L.apply_rope(q, pos, cfg.rope_theta)
    k = L.apply_rope(k, pos, cfg.rope_theta)

    baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = baxes if len(baxes) > 1 else (baxes[0] if baxes else None)
    cspec = P(bspec, "model", None, None)

    def local(qx, kx, vx, ck, cv, clen):
        nsh = jax.lax.axis_size("model")
        me = jax.lax.axis_index("model")
        s_loc = ck.shape[1]
        # write the new token into the owning shard's span
        lpos = clen - me * s_loc
        owner = (lpos >= 0) & (lpos < s_loc)
        lp = jnp.clip(lpos, 0, s_loc - 1)
        ck_new = jax.lax.dynamic_update_slice(ck, kx.astype(ck.dtype),
                                              (0, lp, 0, 0))
        cv_new = jax.lax.dynamic_update_slice(cv, vx.astype(cv.dtype),
                                              (0, lp, 0, 0))
        ck_new = jnp.where(owner, ck_new, ck)
        cv_new = jnp.where(owner, cv_new, cv)
        # local span attention (all heads, local keys)
        kr = _repeat_kv(ck_new.astype(compute_dtype), H // KV)
        vr = _repeat_kv(cv_new.astype(compute_dtype), H // KV)
        s = jnp.einsum("bqhd,bkhd->bhqk", qx, kr,
                       preferred_element_type=jnp.float32) * (hd ** -0.5)
        gpos = me * s_loc + jnp.arange(s_loc)
        valid = gpos <= clen
        if window is not None:
            valid &= gpos > clen - window
        s = s + jnp.where(valid, 0.0, -1e30)[None, None, None, :]
        m_loc = jnp.max(s, axis=-1)                       # (b,h,1)
        p_ = jnp.exp(s - m_loc[..., None])
        l_loc = jnp.sum(p_, axis=-1)
        acc = jnp.einsum("bhqk,bkhd->bhqd", p_.astype(vr.dtype), vr
                         ).astype(jnp.float32)
        # LSE merge across the sequence shards (tiny)
        m = jax.lax.pmax(m_loc, "model")
        corr = jnp.exp(m_loc - m)
        l = jax.lax.psum(corr * l_loc, "model")
        acc = jax.lax.psum(corr[..., None] * acc, "model")
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return jnp.moveaxis(out, 1, 2).astype(compute_dtype), ck_new, cv_new

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec), P(bspec), P(bspec), cspec, cspec, P()),
        out_specs=(P(bspec), cspec, cspec), check_vma=False)
    o, new_k, new_v = fn(q, k, v, cache_k, cache_v, cache_len)
    o = L.dense_apply(p["o"], o.reshape(b, 1, H * hd),
                      compute_dtype=compute_dtype)
    return o, new_k, new_v


def attn_decode_slotted(p, x, cache_k, cache_v, pos, cfg, *, active=None,
                        window=None, compute_dtype=jnp.bfloat16):
    """Per-slot single-token decode (continuous batching).  x: (B, 1, D);
    cache_k/v: (B, S_max, KV, hd); ``pos``: (B,) int32 — each row's own
    cache fill level, so sequences admitted at different times decode in
    one batch.  The new token's K/V lands at ``pos[b]`` via a one-hot
    select (a per-row ``dynamic_update_slice`` is not expressible; the
    select writes the same bytes) and row ``b`` attends over its own
    prefix ``0..pos[b]``.  ``active``: optional (B,) bool — inactive rows
    write nothing (cache bit-for-bit preserved; their outputs are
    discarded by the caller).  Returns (out, new_k, new_v)."""
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s_max = cache_k.shape[1]
    q = _split_heads(L.dense_apply(p["q"], x, compute_dtype=compute_dtype), H, hd)
    k = _split_heads(L.dense_apply(p["k"], x, compute_dtype=compute_dtype), KV, hd)
    v = _split_heads(L.dense_apply(p["v"], x, compute_dtype=compute_dtype), KV, hd)
    pos = pos.astype(jnp.int32)
    q = L.apply_rope(q, pos[:, None], cfg.rope_theta)
    k = L.apply_rope(k, pos[:, None], cfg.rope_theta)
    write = jnp.arange(s_max)[None, :] == pos[:, None]          # (B, S_max)
    if active is not None:
        write &= active[:, None]
    m = write[:, :, None, None]
    new_k = jnp.where(m, k.astype(cache_k.dtype), cache_k)
    new_v = jnp.where(m, v.astype(cache_v.dtype), cache_v)
    valid = jnp.arange(s_max)[None, :] <= pos[:, None]
    if window is not None:
        valid &= jnp.arange(s_max)[None, :] > (pos[:, None] - window)
    kr = _repeat_kv(new_k.astype(compute_dtype), H // KV)
    vr = _repeat_kv(new_v.astype(compute_dtype), H // KV)
    o = attention_scores(q, kr, vr, causal=False, q_offset=0,
                         kv_len_mask=valid)
    o = L.dense_apply(p["o"], o.reshape(x.shape[:-1] + (H * hd,)),
                      compute_dtype=compute_dtype)
    return o, new_k, new_v


def attn_decode(p, x, cache_k, cache_v, cache_len, cfg, *,
                window=None, compute_dtype=jnp.bfloat16):
    """Single-token decode.  x: (B, 1, D); cache_k/v: (B, S_max, KV, hd);
    cache_len: scalar int32 — current fill level.  Returns (out, new_k,
    new_v).  The cache is updated in place via dynamic_update_slice (callers
    donate the cache buffers so XLA aliases them)."""
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s_max = cache_k.shape[1]
    pos = jnp.full((x.shape[0], 1), cache_len, jnp.int32)
    q = _split_heads(L.dense_apply(p["q"], x, compute_dtype=compute_dtype), H, hd)
    k = _split_heads(L.dense_apply(p["k"], x, compute_dtype=compute_dtype), KV, hd)
    v = _split_heads(L.dense_apply(p["v"], x, compute_dtype=compute_dtype), KV, hd)
    q = L.apply_rope(q, pos, cfg.rope_theta)
    k = L.apply_rope(k, pos, cfg.rope_theta)
    new_k = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype), (0, cache_len, 0, 0))
    new_v = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype), (0, cache_len, 0, 0))
    valid = (jnp.arange(s_max) <= cache_len)[None, :]
    if window is not None:
        valid = valid & (jnp.arange(s_max) > cache_len - window)[None, :]
    kr = _repeat_kv(new_k.astype(compute_dtype), H // KV)
    vr = _repeat_kv(new_v.astype(compute_dtype), H // KV)
    o = attention_scores(q, kr, vr, causal=False, q_offset=0,
                         kv_len_mask=jnp.broadcast_to(valid, (x.shape[0], s_max)))
    o = L.dense_apply(p["o"], o.reshape(x.shape[:-1] + (H * hd,)),
                      compute_dtype=compute_dtype)
    return o, new_k, new_v


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2/V3 MLA with q_lora_rank null)
# ---------------------------------------------------------------------------
#
#   q            = x W_q                       -> [q_nope | q_pe] per head
#   [c_kv | k_pe] = x W_kva;  c_kv = RMSNorm(c_kv)
#   [k_nope | v] = c_kv W_kvb                  per head
#   RoPE on q_pe and the head-shared k_pe (interleaved pairs); scale
#   (nope + rope)^-0.5.  Full sequences (prefill, training) use this form;
#   decode absorbs W_UK into the query and W_UV into the output and reads
#   only the cached latent row [c_kv | k_pe] of each position.

MLA_KV_NORM_EPS = 1e-6      # DeepSeek-V3 builds kv_a_layernorm at its default eps
MLA_BLOCK = 512             # query block of full-sequence attention


def mla_init(key, cfg, dtype=jnp.float32):
    H, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "q": L.dense_init(ks[0], cfg.d_model, H * (nope + rope), dtype=dtype),
        "kv_a": L.dense_init(ks[1], cfg.d_model, r + rope, dtype=dtype),
        "kv_norm": L.rmsnorm_init(r, dtype),
        "kv_b": L.dense_init(ks[2], r, H * (nope + vd), dtype=dtype),
        "o": L.dense_init(ks[3], H * vd, cfg.d_model, dtype=dtype),
    }


def rope_interleaved(x, positions, theta: float):
    """RoPE over interleaved pairs (x[2i], x[2i+1]), rotated by
    pos * theta^(-2i/d): the pairing DeepSeek-V3's modeling code makes by
    de-interleaving before its rotate-half.  x: (..., S, H, d);
    positions: (..., S)."""
    d = x.shape[-1]
    ang = positions[..., None].astype(jnp.float32) * L.rope_freqs(d, theta)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    x1, x2 = xf[..., 0], xf[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def mla_qkv(p, x, positions, cfg, *, compute_dtype=jnp.bfloat16):
    """-> (q_nope (B,S,H,nope), q_pe roped (B,S,H,rope), latent (B,S,r+rope)):
    the latent row is what the cache keeps, the normed c_kv beside the
    roped k_pe."""
    H, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = _split_heads(L.dense_apply(p["q"], x, compute_dtype=compute_dtype),
                     H, nope + rope)
    q_pe = rope_interleaved(q[..., nope:], positions, cfg.rope_theta)
    kv = L.dense_apply(p["kv_a"], x, compute_dtype=compute_dtype)
    c = L.rmsnorm_apply(p["kv_norm"], kv[..., :r], MLA_KV_NORM_EPS)
    k_pe = rope_interleaved(kv[..., None, r:], positions, cfg.rope_theta)[..., 0, :]
    return q[..., :nope], q_pe, jnp.concatenate([c, k_pe], axis=-1)


def _causal_blocked(q, k, v, block: int = MLA_BLOCK):
    """Causal attention in query blocks, so the (S, S) scores never exist
    whole.  q, k: (B, S, H, dq); v: (B, S, H, dv) -> (B, S, H, dv)."""
    b, s, h, _ = q.shape
    if s <= block:
        return attention_scores(q, k, v, causal=True)
    n = -(-s // block)
    qp = jnp.pad(q, ((0, 0), (0, n * block - s), (0, 0), (0, 0)))
    qb = jnp.moveaxis(qp.reshape(b, n, block, h, q.shape[-1]), 1, 0)

    def body(_, xs):
        i, qx = xs
        return None, attention_scores(qx, k, v, causal=True, q_offset=i * block)
    _, out = jax.lax.scan(body, None, (jnp.arange(n), qb))
    return jnp.moveaxis(out, 0, 1).reshape(b, n * block, h, v.shape[-1])[:, :s]


def mla_apply(p, x, positions, cfg, *, compute_dtype=jnp.bfloat16):
    """Full-sequence causal MLA in the published (non-absorbed) form.
    x: (B, S, D) -> (out (B, S, D), latent (B, S, r+rope) for the cache)."""
    H, r = cfg.num_heads, cfg.kv_lora_rank
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    q_nope, q_pe, lat = mla_qkv(p, x, positions, cfg, compute_dtype=compute_dtype)
    kv = _split_heads(L.dense_apply(p["kv_b"], lat[..., :r],
                                    compute_dtype=compute_dtype), H, nope + vd)
    k_pe = jnp.broadcast_to(lat[..., None, r:], q_pe.shape)
    q = jnp.concatenate([q_nope, q_pe.astype(q_nope.dtype)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], k_pe.astype(kv.dtype)], axis=-1)
    o = _causal_blocked(q, k, kv[..., nope:])
    o = L.dense_apply(p["o"], o.reshape(x.shape[:-1] + (H * vd,)),
                      compute_dtype=compute_dtype)
    return o, lat


def mla_decode_attend(p, q_nope, q_pe, ckv, kpe, layer, pos, cfg, *,
                      compute_dtype=jnp.bfloat16):
    """Absorbed single-token MLA over a latent cache.  q_nope/q_pe:
    (B, 1, H, .) of the new tokens; ``ckv`` (L, B, T, r) and ``kpe``
    (L, B, T/2, 2 rope): the cache with the new rows already written (see
    ``kernels/mla_decode``); ``layer``: the cache layer; ``pos``: (B,) —
    row b attends over positions 0..pos[b].  W_UK folds into the query and
    W_UV into the output, so only the attended latent rows are read."""
    from repro.kernels.mla_decode import mla_decode_attention
    H, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    cd = compute_dtype
    w = p["kv_b"]["w"].astype(cd).reshape(r, H, nope + vd)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0].astype(cd), w[..., :nope],
                       preferred_element_type=jnp.float32).astype(cd)
    o_lat = mla_decode_attention(q_lat, q_pe[:, 0].astype(cd), ckv, kpe, layer, pos,
                                 scale=(nope + rope) ** -0.5)
    o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(cd), w[..., nope:],
                   preferred_element_type=jnp.float32).astype(cd)
    return L.dense_apply(p["o"], o.reshape(o.shape[0], 1, H * vd),
                         compute_dtype=cd)
