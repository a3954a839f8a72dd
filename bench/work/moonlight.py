"""Operations and bytes of a Moonlight (DeepSeek-V3 block) window, from the
configuration's widths and the window's counters, as the real work needs
them: attended positions (not the cache's length) and routed tokens (not
padded capacity or padded prompt positions).

* ``flops``: the model's FLOPs of every token the window prefilled or
  decoded (2 per multiply-add): the body's projections and experts for each
  token, causal attention over the positions each token attends (prefill
  in the published form, decode in the absorbed form the program runs),
  and the head at each emitted token (the last prompt position and every
  decoded row).
* ``mla_decode``: the absorbed decode attention's kernel: per decode row,
  layer and attended position, the score over the 576-wide latent row and
  the weighted sum of its 512-wide c_kv; bytes are the attended latent
  rows (bf16).  W_UK and W_UV, outside the kernel, are in ``flops`` only.
* ``moe_experts``: the routed experts' grouped matmuls: three projections
  per routed (token, expert) pair; bytes are the weights of every expert a
  call hit, once per call and layer, plus each pair's input and output
  rows (bf16).  Prefill and decode are given apart, since one is bound by
  compute and the other by bandwidth.

``hbm_bytes`` is the sum of the two kernels' bytes.
"""
from __future__ import annotations

BF16 = 2


def _w(m: dict) -> dict:
    D, H = m["hidden_size"], m["num_attention_heads"]
    r, nope, rope, vd = (m["kv_lora_rank"], m["qk_nope_head_dim"],
                         m["qk_rope_head_dim"], m["v_head_dim"])
    attn = D * H * (nope + rope) + D * (r + rope) + r * H * (nope + vd) + H * vd * D
    return dict(D=D, H=H, r=r, nope=nope, rope=rope, vd=vd, attn=attn,
                L=m["num_hidden_layers"], kd=m["first_k_dense_replace"],
                dense=3 * D * m["intermediate_size"],
                expert=3 * D * m["moe_intermediate_size"],
                shared=3 * D * m["n_shared_experts"] * m["moe_intermediate_size"],
                router=D * m["n_routed_experts"], k=m["num_experts_per_tok"],
                V=m["vocab_size"])


def count(model: dict, counters: dict) -> dict:
    w = _w(model)
    nm = w["L"] - w["kd"]
    pre_tok = counters["prefill_tokens"]
    rows = counters["decode_rows"]
    tokens = pre_tok + rows
    per_token = (w["L"] * w["attn"] + w["kd"] * w["dense"]
                 + nm * (w["router"] + w["shared"] + w["k"] * w["expert"]))
    # attention: prefill q.k (nope + rope) and p.v per head and attended
    # position; decode absorbed: scores and sum over 576-wide latent rows,
    # plus W_UK and W_UV per row
    pre_attn = 2 * w["H"] * (w["nope"] + w["rope"] + w["vd"]) * w["L"] \
        * counters["prefill_attended_positions"]
    lat = w["r"] + w["rope"]
    att = w["L"] * counters["attended_positions"]
    mla = {"flops": 2 * w["H"] * (lat + w["r"]) * att, "hbm_bytes": BF16 * lat * att}
    absorb = w["L"] * rows * 2 * w["H"] * w["r"] * (w["nope"] + w["vd"])
    head = 2 * w["D"] * w["V"] * (counters["prefills"] + rows)
    flops = 2 * per_token * tokens + pre_attn + mla["flops"] + absorb + head

    def experts(n_tok, hits):
        pairs = n_tok * nm * w["k"]
        return {"flops": 2 * w["expert"] * pairs,
                "hbm_bytes": BF16 * (w["expert"] * hits + 2 * w["D"] * pairs)}
    moe = {"prefill": experts(pre_tok, counters.get("moe.experts_hit_prefill", 0)),
           "decode": experts(rows, counters.get("moe.experts_hit_decode", 0))}
    return {"flops": flops, "hbm_bytes": mla["hbm_bytes"] + sum(
                p["hbm_bytes"] for p in moe.values()),
            "mla_decode": mla, "moe_experts": moe}
