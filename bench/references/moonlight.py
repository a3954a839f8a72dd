"""Plain float32 reference of Moonlight-16B-A3B (DeepSeek-V3 block), in
``jax.numpy`` under ``jax.default_matmul_precision("highest")``, written
from the published equations (the model's ``config.json`` and DeepSeek-V3's
modeling code) and independent of the program.

Per layer, with x the residual stream of one sequence (n, 2048):

* attention (MLA, ``q_lora_rank`` null): q = RMSNorm(x) W_q split into
  q_nope (128) and q_pe (64) per head; [c_kv | k_pe] = RMSNorm(x) W_kva,
  c_kv = RMSNorm(c_kv); [k_nope | v] = c_kv W_kvb per head; RoPE with theta
  50,000 on q_pe and the head-shared k_pe, laid out as DeepSeek-V3 does
  (interleaved pairs de-interleaved, then rotate-half); softmax((q_nope
  k_nope + q_pe k_pe) / sqrt(192)) causal, times v, then W_o;
* layer 0: SwiGLU of width 11,264; layers 1-: MoE with sigmoid scores
  s = sigmoid(h W_r), the top 6 of s + bias (``noaux_tc``, one group),
  weights the chosen s normalised to sum 1 times 2.446, each chosen
  expert a SwiGLU of width 1,408, plus the shared SwiGLU (2 experts, width
  2,816) on every token;
* final RMSNorm and the untied head.

Departures, each deliberate:

* Routing follows the program's choice where the program reports one and
  it is admissible: at each (position, MoE layer) the reference takes the
  program's expert set if no chosen biased score of its own lies more than
  ``check.route_delta`` below an unchosen one.  A bf16 residual stream
  flips near-ties of the top-6 boundary, and a flip moves that token's
  logits by a large fraction of their spread; following the program keeps
  the logit comparison about arithmetic, and the admissibility test keeps
  a wrong set from passing.  A row at or after an inadmissible position is
  NaN, which fails the check.  Without reported routes it routes itself.
* The weights are bf16, drawn from the seed on the device in the program's
  own layout (so the program takes the same arrays, without a copy); each
  is upcast to float32 where it is used.
* Attention runs in query blocks of 512 and the sequence is padded at its
  end to a multiple of 512 (causal, so no real position sees the padding);
  experts run over the tokens routed to them, padded to a power of two
  with zero weight.  Neither changes the result beyond rounding.
* kv_a_layernorm uses eps 1e-6, the default its module is built with in
  DeepSeek-V3's modeling code; the other norms use ``rms_norm_eps``.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

BLOCK = 512
KV_NORM_EPS = 1e-6
F32 = jnp.float32

#: Readings of the last ``Reference.logits`` call, for setting limits:
#: positions x MoE layers compared, how many of them the program routed
#: differently from the reference's own top-k, and the widest inversion
#: (largest unchosen minus smallest chosen biased score; <= 0 when none).
LAST_STATS: dict = {}


def shapes(cfg: dict) -> dict:
    """The configuration's sizes under short names."""
    return dict(D=cfg["hidden_size"], V=cfg["vocab_size"], L=cfg["num_hidden_layers"],
                kd=cfg["first_k_dense_replace"], H=cfg["num_attention_heads"],
                r=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
                rope=cfg["qk_rope_head_dim"], vd=cfg["v_head_dim"],
                F=cfg["intermediate_size"], Fe=cfg["moe_intermediate_size"],
                E=cfg["n_routed_experts"], k=cfg["num_experts_per_tok"],
                ns=cfg["n_shared_experts"])


def param_shapes(cfg: dict) -> dict:
    """{path: (shape, dtype, std)} of every weight, in the program's layout:
    stacked leading layer dims for the dense layers ("dense") and the MoE
    layers ("blocks"); std 0 marks a norm scale (ones) or the bias."""
    s = shapes(cfg)
    D, H, r, nope, rope, vd = s["D"], s["H"], s["r"], s["nope"], s["rope"], s["vd"]
    bf, f32 = "bfloat16", "float32"

    a = cfg["init"]["attn_score_std"]

    def attn(n):
        return {("attn", "q", "w"): ((n, D, H * (nope + rope)), bf, a * D ** -0.5),
                ("attn", "kv_a", "w"): ((n, D, r + rope), bf, D ** -0.5),
                ("attn", "kv_norm", "scale"): ((n, r), bf, 0.0),
                ("attn", "kv_b", "w"): ((n, r, H * (nope + vd)), bf, r ** -0.5),
                ("attn", "o", "w"): ((n, H * vd, D), bf, (H * vd) ** -0.5),
                ("ln1", "scale"): ((n, D), bf, 0.0), ("ln2", "scale"): ((n, D), bf, 0.0)}

    def mlp(n, F, pre):
        return {pre + ("w_gate", "w"): ((n, D, F), bf, D ** -0.5),
                pre + ("w_in", "w"): ((n, D, F), bf, D ** -0.5),
                pre + ("w_out", "w"): ((n, F, D), bf, F ** -0.5)}

    kd, nm, E, Fe = s["kd"], s["L"] - s["kd"], s["E"], s["Fe"]
    out = {("embed", "table"): ((s["V"], D), bf, 1.0),
           ("final_norm", "scale"): ((D,), bf, 0.0),
           ("lm_head", "w"): ((D, s["V"]), bf, D ** -0.5)}
    for key, val in {**attn(kd), **mlp(kd, s["F"], ("mlp",))}.items():
        out[("dense",) + key] = val
    moe = {("moe", "router", "w"): ((nm, D, E), f32, D ** -0.5),
           ("moe", "e_bias"): ((nm, E), f32, -1.0),
           ("moe", "w_gate"): ((nm, E, D, Fe), bf, D ** -0.5),
           ("moe", "w_in"): ((nm, E, D, Fe), bf, D ** -0.5),
           ("moe", "w_out"): ((nm, E, Fe, D), bf, Fe ** -0.5),
           **mlp(nm, s["ns"] * Fe, ("moe", "shared"))}
    for key, val in {**attn(nm), **moe}.items():
        out[("blocks",) + key] = val
    return out


def make_params(cfg: dict, seed_seq) -> dict:
    """Weights random from the seed, drawn on the device: N(0, 1/d_in) for
    every projection but W_q, which ``init.attn_score_std`` scales (the
    scores' std), N(0, 1) for the embedding, ones for norm scales and
    N(0, e_bias_std^2) for the routers' correction biases."""
    bias_std = cfg["init"]["e_bias_std"]
    root = jax.random.key(int(seed_seq.generate_state(1, np.uint32)[0]))
    tree: dict = {}
    for i, (path, (shape, dtype, std)) in enumerate(sorted(param_shapes(cfg).items())):
        if std == 0.0:
            leaf = jnp.ones(shape, dtype)
        else:
            leaf = _normal(jax.random.fold_in(root, i), shape, jnp.dtype(dtype),
                           bias_std if std < 0 else std)
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = leaf
    return tree


def _normal(key, shape, dtype, std):
    return jax.jit(lambda k: (std * jax.random.normal(k, shape, F32)).astype(dtype))(key)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale.astype(F32)


def _rope(x, pos, theta):
    """DeepSeek-V3's apply_rotary_pos_emb on one tensor: de-interleave the
    pairs, then rotate-half with cos/sin of cat(freqs, freqs)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos[:, None].astype(F32) * inv[None, :]
    emb = jnp.concatenate([ang, ang], -1)[:, None, :]          # (n, 1, d)
    x = x.reshape(x.shape[:-1] + (d // 2, 2)).swapaxes(-1, -2).reshape(x.shape)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def _swiglu(x, wg, wi, wo):
    return (jax.nn.silu(x @ wg.astype(F32)) * (x @ wi.astype(F32))) @ wo.astype(F32)


class Reference:
    def __init__(self, cfg: dict, params: dict):
        self.s = s = shapes(cfg)
        self.params = params
        self.eps = cfg["rms_norm_eps"]
        self.theta = float(cfg["rope_theta"])
        self.scaling = cfg["routed_scaling_factor"]
        self.norm_topk = cfg["norm_topk_prob"]
        self.delta = cfg["check"]["route_delta"]
        eps, theta = self.eps, self.theta

        def attention(p, x, pos):
            H, r, nope, rope = s["H"], s["r"], s["nope"], s["rope"]
            n = x.shape[0]
            h = _rms(x, p["ln1"]["scale"], eps)
            q = (h @ p["attn"]["q"]["w"].astype(F32)).reshape(n, H, nope + rope)
            kva = h @ p["attn"]["kv_a"]["w"].astype(F32)
            c = _rms(kva[:, :r], p["attn"]["kv_norm"]["scale"], KV_NORM_EPS)
            kv = (c @ p["attn"]["kv_b"]["w"].astype(F32)).reshape(n, H, nope + s["vd"])
            q_pe = _rope(q[..., nope:], pos, theta)
            k_pe = _rope(kva[:, None, r:], pos, theta)[:, 0]
            k_nope, v = kv[..., :nope], kv[..., nope:]

            def block(_, qi):
                sl = lambda a: jax.lax.dynamic_slice_in_dim(a, qi * BLOCK, BLOCK)
                sc = (jnp.einsum("qhd,khd->hqk", sl(q[..., :nope]), k_nope)
                      + jnp.einsum("qhd,kd->hqk", sl(q_pe), k_pe)) * (nope + rope) ** -0.5
                qpos = qi * BLOCK + jnp.arange(BLOCK)
                sc = jnp.where(jnp.arange(n)[None, None, :] <= qpos[None, :, None], sc, -jnp.inf)
                return None, jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
            _, o = jax.lax.scan(block, None, jnp.arange(n // BLOCK))
            o = o.reshape(n, H * s["vd"])
            return x + o @ p["attn"]["o"]["w"].astype(F32)

        def router(p, x):
            h = _rms(x, p["ln2"]["scale"], eps)
            sc = jax.nn.sigmoid(h @ p["moe"]["router"]["w"].astype(F32))
            return h, sc, sc + p["moe"]["e_bias"]

        def dense_mlp(p, x):
            h = _rms(x, p["ln2"]["scale"], eps)
            m = p["mlp"]
            return x + _swiglu(h, m["w_gate"]["w"], m["w_in"]["w"], m["w_out"]["w"])

        def experts(h, wg, wi, wo):            # (G, C, D) through G experts
            a = jnp.einsum("gcd,gdf->gcf", h, wg.astype(F32))
            b = jnp.einsum("gcd,gdf->gcf", h, wi.astype(F32))
            return jnp.einsum("gcf,gfd->gcd", jax.nn.silu(a) * b, wo.astype(F32))

        def head(x, rows, final, w):
            return _rms(x[rows], final, eps) @ w.astype(F32)

        self._attention = jax.jit(attention)
        self._router = jax.jit(router)
        self._dense_mlp = jax.jit(dense_mlp)
        self._experts = jax.jit(experts)
        self._head = jax.jit(head)
        self._shared = jax.jit(lambda h, m: _swiglu(
            h, m["w_gate"]["w"], m["w_in"]["w"], m["w_out"]["w"]))

    # -- the forward --------------------------------------------------------
    def _moe(self, p, x, n, routes, stats):
        """x (N, D) padded; the first n rows are real.  -> (x + MoE(x),
        first inadmissible position or n)."""
        s = self.s
        h, sc, biased = self._router(p, x)
        sc, biased = np.asarray(sc[:n], np.float64), np.asarray(biased[:n], np.float64)
        own = np.argsort(-biased, axis=1, kind="stable")[:, :s["k"]]
        chosen = own if routes is None else np.asarray(routes, np.int64)
        taken = np.zeros_like(biased, bool)
        np.put_along_axis(taken, chosen, True, axis=1)
        low = np.where(taken, biased, np.inf).min(1)
        high = np.where(taken, -np.inf, biased).max(1)
        stats["positions"] += n
        stats["flips"] += int((np.sort(chosen, 1) != np.sort(own, 1)).any(1).sum())
        stats["max_inversion"] = max(stats["max_inversion"], float(np.max(high - low)))
        bad = np.nonzero(high - low > self.delta)[0]
        w = np.take_along_axis(sc, chosen, 1)
        if self.norm_topk:
            w = w / (w.sum(1, keepdims=True) + 1e-20)
        w = w * self.scaling
        tok = np.repeat(np.arange(n), s["k"])
        flat_e, flat_w = chosen.reshape(-1), w.reshape(-1)
        order = np.argsort(flat_e, kind="stable")
        counts = np.bincount(flat_e, minlength=s["E"])
        cap = 1 << max(int(counts.max()) - 1, 7).bit_length()
        idx = np.zeros((s["E"], cap), np.int64)
        wt = np.zeros((s["E"], cap), np.float32)
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        for e in np.nonzero(counts)[0]:
            sel = order[starts[e]:starts[e] + counts[e]]
            idx[e, :counts[e]], wt[e, :counts[e]] = tok[sel], flat_w[sel]
        y = jnp.zeros((x.shape[0], s["D"]), F32)
        m = p["moe"]
        G = 8
        for g in range(0, s["E"], G):
            ye = self._experts(h[idx[g:g + G]], m["w_gate"][g:g + G], m["w_in"][g:g + G],
                               m["w_out"][g:g + G])
            y = y.at[idx[g:g + G].reshape(-1)].add(
                (ye * wt[g:g + G, :, None]).reshape(-1, s["D"]))
        y = y + self._shared(h, m["shared"])
        return x + y, int(bad[0]) if bad.size else n

    def forward(self, tokens: np.ndarray, routes: np.ndarray | None, rows: np.ndarray,
                stats: dict) -> np.ndarray:
        """Logits (len(rows), V) at positions ``rows`` of one sequence, NaN at
        and after its first inadmissible route.  routes: (n, n_moe, k)."""
        s, p = self.s, self.params
        n = len(tokens)
        N = -(-n // BLOCK) * BLOCK
        with jax.default_matmul_precision("highest"):
            x = p["embed"]["table"][jnp.asarray(np.pad(tokens, (0, N - n)))].astype(F32)
            pos = jnp.arange(N)
            bad = n
            for i in range(s["L"]):
                group, j = ("dense", i) if i < s["kd"] else ("blocks", i - s["kd"])
                lp = jax.tree.map(lambda a: a[j], p[group])
                x = self._attention(lp, x, pos)
                if group == "dense":
                    x = self._dense_mlp(lp, x)
                else:
                    x, b = self._moe(lp, x, n, None if routes is None else routes[:, j], stats)
                    bad = min(bad, b)
            padded = np.zeros(1 << max(len(rows) - 1, 0).bit_length(), np.int64)
            padded[:len(rows)] = rows
            out = np.array(self._head(x, jnp.asarray(padded), p["final_norm"]["scale"],
                                        p["lm_head"]["w"]))[:len(rows)]
        out[np.asarray(rows) >= bad] = np.nan
        return out

    def logits(self, inputs: list) -> list:
        """Each input is {"request", "tokens", "routes"}: a prefix of its
        request's longest input.  One forward per request answers all of
        that request's inputs (the row at each input's last position)."""
        stats = {"positions": 0, "flips": 0, "max_inversion": -np.inf}
        by_req: dict = {}
        for i, inp in enumerate(inputs):
            by_req.setdefault(inp["request"], []).append(i)
        out: list = [None] * len(inputs)
        for members in by_req.values():
            longest = max(members, key=lambda i: len(inputs[i]["tokens"]))
            toks = np.asarray(inputs[longest]["tokens"], np.int64)
            routes = inputs[longest]["routes"]
            for i in members:
                t = np.asarray(inputs[i]["tokens"])
                if not np.array_equal(t, toks[:len(t)]):
                    raise ValueError(f"input {i} is not a prefix of its request's longest")
            rows = np.array([len(inputs[i]["tokens"]) - 1 for i in members])
            got = self.forward(toks, routes, rows, stats)
            for j, i in enumerate(members):
                out[i] = got[j]
        LAST_STATS.clear()
        LAST_STATS.update(stats)
        return out
