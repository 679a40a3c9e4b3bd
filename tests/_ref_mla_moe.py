"""Plain float32 reference of the DeepSeek-V3 block (Moonlight-16B-A3B):
multi-head latent attention and a routed + shared expert layer, on one
chip's share of the experts.  No cache, no kernels, no batching tricks.

For a residual ``x`` at position ``t``: ``h = x + MLA(RMSNorm(x))``,
``y = h + FFN(RMSNorm(h))``; the FFN is a SwiGLU in the leading layers
and the expert layer in the rest; a final RMSNorm and an untied head.  A
norm's gain is ``1 + s`` for the stored scale ``s``.

MLA, naive form: ``q = x W_q`` gives per head ``[q_nope | q_pe]``;
``[c | k_pe] = x W_kva``, ``c <- RMSNorm_kv(c)``; ``q_pe`` and ``k_pe``
(one per token, shared by the heads) are rotated at their positions; per
head ``[k_nope | v] = c W_kvb``; scores ``(q_nope.k_nope + q_pe.k_pe) /
sqrt(nope + rope)``, causal softmax, ``o = concat_h sum_u p v``,
``out = o W_o``.  Rotary pairs are half-split (``i``, ``i + rope/2``); the
published code rotates adjacent pairs, the same map under a fixed
permutation of the rope columns of ``W_q`` and ``W_kva``.

Expert layer: float32 router logits, ``sigma = sigmoid``, top-k of
``sigma + bias`` (the bias picks, it does not weigh), gates
``sigma_k / sum_sel sigma * scaling``; every held expert runs on every
token and counts where the token selected it; the shared experts are a
SwiGLU on every token.

The parameter tree is the program's (``repro.models.model``): lead layer
``l0``, scanned layers stacked under ``u0``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def norm(x, s, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + s)


def rope(x, pos, theta):
    """x (..., S, n, d) rotated at ``pos`` (S,), half-split pairs."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def mla(lp, x, cfg):
    """Causal MLA over x (B, S, d), naive (per-head K and V expanded)."""
    B, S, _ = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    pos = jnp.arange(S)
    q = (x @ lp["wq"]).reshape(B, S, H, dn + dr)
    q_nope, q_pe = q[..., :dn], rope(q[..., dn:], pos, cfg.rope_theta)
    ckv = x @ lp["w_kva"]
    c = norm(ckv[..., :r], lp["kv_norm"], cfg.norm_eps)
    k_pe = rope(ckv[..., None, r:], pos, cfg.rope_theta)  # (B, S, 1, dr)
    kv = (c @ lp["w_kvb"]).reshape(B, S, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    s = (jnp.einsum("bthn,buhn->bhtu", q_nope, k_nope)
         + jnp.einsum("bthr,bur->bhtu", q_pe, k_pe[:, :, 0]))
    s = s / math.sqrt(dn + dr)
    s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :], s,
                  -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhtu,buhv->bthv", p, v).reshape(B, S, H * dv)
    return o @ lp["wo"]


def route(lp, x, cfg):
    """(gates (B, S, K), expert ids (B, S, K)) over all experts."""
    scores = jax.nn.sigmoid(x @ lp["w_router"])
    _, idx = jax.lax.top_k(scores + lp["router_bias"], cfg.top_k)
    g = jnp.take_along_axis(scores, idx, axis=-1)
    return g / g.sum(-1, keepdims=True) * cfg.routed_scaling, idx


def routed(lp, x, cfg, offset, held):
    """The routed output of experts ``offset .. offset + held - 1`` (the
    leaves hold exactly those), dense: every held expert on every token,
    weighted where the token selected it."""
    g, idx = route(lp, x, cfg)
    out = jnp.zeros_like(x)
    for j in range(held):
        w = jnp.where(idx == offset + j, g, 0.0).sum(-1)  # (B, S)
        y = swiglu(x, lp["w_gate"][j], lp["w_up"][j], lp["w_down"][j])
        out = out + w[..., None] * y
    return out


def shared(lp, x):
    return swiglu(x, lp["shared_gate"], lp["shared_up"], lp["shared_down"])


def expert_layer(lp, x, cfg):
    """The held experts' share plus the shared experts."""
    return (routed(lp, x, cfg, cfg.expert_offset, cfg.experts_held)
            + shared(lp, x))


def layer(lp, x, cfg, kind):
    h = x + mla(lp, norm(x, lp["ln1"], cfg.norm_eps), cfg)
    z = norm(h, lp["ln2"], cfg.norm_eps)
    if kind == "mla_moe":
        return h + expert_layer(lp, z, cfg)
    return h + swiglu(z, lp["w_gate"], lp["w_up"], lp["w_down"])


def forward(params, cfg, tokens) -> np.ndarray:
    """Logits (B, S, vocab) of every position of ``tokens`` (B, S)."""
    params = _f32(params)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)]
        for i, kind in enumerate(cfg.lead):
            x = layer(params[f"l{i}"], x, cfg, kind)
        for i in range(cfg.n_repeats):
            x = layer(jax.tree.map(lambda a: a[i], params["u0"]), x, cfg,
                      cfg.pattern[0])
        x = norm(x, params["final_norm"], cfg.norm_eps)
        return np.asarray(x @ params["unembed"].T)[..., : cfg.vocab]
