"""Sizes, bytes and operations of the DeepSeek-V3 block (multi-head latent
attention, a leading dense layer, expert layers of routed and shared
experts) as one chip of an expert-parallel group serves it, computed from
the configuration's shapes and the engine's expert counters alone.

Counts are of the work the model needs, in the form the serving step
computes it: prefill attends naively (the latent rows expanded to
per-head K and V), decode in absorbed form over the latent rows; routed
expert work is counted per token-expert pair routed to a held expert
(``moe/held_pairs``), so experts held elsewhere and pairs they take are
not counted here.
"""

from __future__ import annotations


def sizes(cfg: dict) -> dict:
    """The configuration's sizes under the names used here, from the
    source config's keys at the top level of its file."""
    ep = cfg["expert_parallel"]
    return {
        "n_layers": cfg["num_hidden_layers"],
        "n_dense": cfg["first_k_dense_replace"],
        "d_model": cfg["hidden_size"], "n_heads": cfg["num_attention_heads"],
        "kv_lora_rank": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"], "d_ff": cfg["intermediate_size"],
        "expert_ff": cfg["moe_intermediate_size"],
        "shared_ff": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        "n_experts": ep["n_routed_experts"],
        "experts_held": cfg["n_routed_experts"],
        "expert_offset": ep["held_first"], "top_k": cfg["num_experts_per_tok"],
        "routed_scaling": cfg["routed_scaling_factor"],
        "vocab": cfg["vocab_size"],
        "rope_theta": cfg["rope_theta"], "norm_eps": cfg["rms_norm_eps"],
    }


def attn_params(m: dict) -> int:
    """Matmul parameters of one MLA block: W_q, W_kva, W_kvb, W_o."""
    d, H, r = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    return (d * H * (m["nope"] + m["rope"]) + d * (r + m["rope"])
            + r * H * (m["nope"] + m["v"]) + H * m["v"] * d)


def expert_params(m: dict) -> int:
    """One routed expert (gate, up, down)."""
    return 3 * m["d_model"] * m["expert_ff"]


def weight_params(m: dict) -> dict:
    """Parameters held here by part (norm scales left out, under 0.01 %)."""
    d, n_moe = m["d_model"], m["n_layers"] - m["n_dense"]
    return {
        "attention": m["n_layers"] * attn_params(m),
        "dense": m["n_dense"] * 3 * d * m["d_ff"],
        "routed": n_moe * m["experts_held"] * expert_params(m),
        "shared": n_moe * 3 * d * m["shared_ff"],
        "router": n_moe * (d + 1) * m["n_experts"],
        "embed": m["vocab"] * d,
        "head": m["vocab"] * d,
    }


def latent_width(m: dict) -> int:
    return m["kv_lora_rank"] + m["rope"]


def decode_step_bytes(m: dict, batch: int, keys: int,
                      experts_hit_per_step: float, wbytes: int = 2,
                      cbytes: int = 2) -> float:
    """Least HBM bytes of one decode step: every weight held here read once
    but the embedding (a decode step reads ``batch`` of its rows), less
    the held experts no token of the step chose, plus every sequence's
    ``keys`` resident latent rows in every layer."""
    w = weight_params(m)
    n_moe = m["n_layers"] - m["n_dense"]
    unchosen = n_moe * m["experts_held"] - experts_hit_per_step
    weights = (sum(w.values()) - w["embed"]
               - unchosen * expert_params(m)) * wbytes
    pool = m["n_layers"] * batch * keys * latent_width(m) * cbytes
    return weights + pool


def decode_step_flops(m: dict, batch: int, keys: int,
                      held_pairs_per_step: float) -> float:
    """Model FLOPs of one decode step in absorbed form: per token the MLA
    projections, the query's absorption into the latent (nope x r a head),
    scores over ``keys`` latent rows (r + rope) and the weighted latent
    sum (r), the output's W_UV; the leading layers' MLP; the shared
    experts and router; the routed pairs held here; the head."""
    d, H, r = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    n_moe = m["n_layers"] - m["n_dense"]
    attn = (d * H * (m["nope"] + m["rope"]) + d * (r + m["rope"])
            + H * m["nope"] * r + H * keys * (latent_width(m) + r)
            + H * r * m["v"] + H * m["v"] * d)
    per_token = (m["n_layers"] * attn + m["n_dense"] * 3 * d * m["d_ff"]
                 + n_moe * (3 * d * m["shared_ff"] + d * m["n_experts"])
                 + d * m["vocab"])
    return 2 * (batch * per_token
                + held_pairs_per_step * expert_params(m))


def prefill_flops(m: dict, batch: int, seq: int, held_pairs: float) -> float:
    """Model FLOPs of one prefill: per token the MLA projections and the
    expansion of its latent row to per-head K and V; causal attention
    (each query over itself and the keys before it, qk nope + rope wide, v
    wide); the leading layers' MLP; shared experts and router; the routed
    pairs held here (``held_pairs``, all layers); the head at the last
    position only."""
    d, H = m["d_model"], m["n_heads"]
    n_moe = m["n_layers"] - m["n_dense"]
    pairs = seq * (seq + 1) // 2
    attn_scores = H * (m["nope"] + m["rope"] + m["v"]) * pairs
    per_seq = (seq * (m["n_layers"] * attn_params(m)
                      + m["n_dense"] * 3 * d * m["d_ff"]
                      + n_moe * (3 * d * m["shared_ff"] + d * m["n_experts"]))
               + m["n_layers"] * attn_scores + d * m["vocab"])
    return 2 * (batch * per_seq + held_pairs * expert_params(m))
