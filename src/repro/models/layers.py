"""Model layer library (pure JAX, mesh-agnostic via logical sharding).

Conventions:
  * activations  (B, S, D) in ``cfg.dtype`` (bf16); softmax/reductions fp32;
  * parameters stored with FLATTENED feature dims (``n_heads*head_dim``) so
    jit-boundary shardings always divide the 16-way mesh axes (DESIGN.md §4);
  * every hot intermediate is annotated with ``logical_shard``.

Attention is a chunked flash-style scan (running max/denominator) so the
32k-prefill cells never materialize (S, S) scores; the scan body is wrapped in
``jax.checkpoint`` so the backward recomputes chunk scores (flash semantics).
The *baseline* schedule is rectangular with causal block masking (masked
blocks still burn FLOPs — visible in the roofline and attacked in the §Perf
hillclimb).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.sharding.specs import logical_shard

Params = Dict[str, Any]

# ---------------------------------------------------------------------------
# norms / embeddings / mlp
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))).astype(dt)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq  # (B,S,half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def sinusoidal_positions(positions: jax.Array, d: int) -> jax.Array:
    """(B, S) -> (B, S, d) fixed sinusoidal embedding (whisper stub)."""
    half = d // 2
    freq = jnp.exp(-math.log(10_000.0) * jnp.arange(half, dtype=jnp.float32) / max(half - 1, 1))
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def mlp(params: Params, x: jax.Array, act: str) -> jax.Array:
    if act == "swiglu":
        h = jnp.einsum("bsd,df->bsf", x, params["w_gate"])
        u = jnp.einsum("bsd,df->bsf", x, params["w_up"])
        h = logical_shard(h, "act_batch", "act_seq", "act_feat")
        u = logical_shard(u, "act_batch", "act_seq", "act_feat")
        h = jax.nn.silu(h) * u
    else:  # gelu
        h = jnp.einsum("bsd,df->bsf", x, params["w_up"])
        h = logical_shard(h, "act_batch", "act_seq", "act_feat")
        h = jax.nn.gelu(h)
    out = jnp.einsum("bsf,fd->bsd", h, params["w_down"])
    return logical_shard(out, "act_batch", "act_res_seq", "act_embed")


# ---------------------------------------------------------------------------
# flash-style chunked attention (training / prefill)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _attn_chunk(qc, kc, vc, qpos, kpos, scale, causal, window):
    """One (q-chunk, kv-chunk) tile. qc: (B,cq,KVH,G,hd); kc/vc: (B,ck,KVH,hd).
    Returns (scores_exp, m, l-partial) pieces via running-softmax update —
    implemented inline in the caller's carry update."""
    s = jnp.einsum("bqkgh,bckh->bkgqc", qc, kc).astype(jnp.float32) * scale
    mask = kpos[None, :] <= qpos[:, None] if causal else jnp.ones(
        (qpos.shape[0], kpos.shape[0]), dtype=bool
    )
    if window:
        mask &= (qpos[:, None] - kpos[None, :]) < window
    mask &= (kpos >= 0)[None, :]  # invalid / padded kv positions
    return jnp.where(mask[None, None, None], s, NEG_INF)


def flash_attention(
    q: jax.Array,  # (B, Sq, KVH, G, hd)
    k: jax.Array,  # (B, Skv, KVH, hd)
    v: jax.Array,  # (B, Skv, KVH, hd)
    *,
    q_positions: jax.Array,  # (Sq,) int32
    kv_positions: jax.Array,  # (Skv,) int32 (-1 = invalid)
    causal: bool,
    window: int = 0,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    scale: Optional[float] = None,  # default 1/sqrt(q's head width)
) -> jax.Array:
    """Chunked softmax attention with running (m, l, acc). Rectangular
    schedule + block masking (baseline; see module docstring).  ``v`` may
    be narrower than ``q`` and ``k`` (MLA: qk 192, v 128); the output
    takes v's width."""
    B, Sq, KVH, G, hd = q.shape
    hdv = v.shape[-1]
    Skv = k.shape[1]
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    # pad to chunk multiples
    pq = (-Sq) % q_chunk
    pk = (-Skv) % kv_chunk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, (0, pq), constant_values=2**30)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, (0, pk), constant_values=-1)
    nq, nk = q.shape[1] // q_chunk, k.shape[1] // kv_chunk
    scale = 1.0 / math.sqrt(hd) if scale is None else scale

    qs = q.reshape(B, nq, q_chunk, KVH, G, hd).transpose(1, 0, 2, 3, 4, 5)
    ks = k.reshape(B, nk, kv_chunk, KVH, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, kv_chunk, KVH, hdv).transpose(1, 0, 2, 3, 4)
    qps = q_positions.reshape(nq, q_chunk)
    kps = kv_positions.reshape(nk, kv_chunk)

    @jax.checkpoint
    def kv_step(carry, inp):
        m, l, acc, qc, qpos = carry
        kc, vc, kpos = inp
        s = _attn_chunk(qc, kc, vc, qpos, kpos, scale, causal, window)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bkgqc,bckh->bkgqh", p.astype(vc.dtype), vc)
        acc = acc * corr[..., None].astype(acc.dtype) + pv.astype(jnp.float32)
        return (m_new, l, acc, qc, qpos), None

    def q_step(_, inp):
        qc, qpos = inp
        qc = logical_shard(qc, "act_batch", "act_seq", "act_kv_heads", None, None)
        m0 = jnp.full((B, KVH, G, q_chunk), NEG_INF, dtype=jnp.float32)
        l0 = jnp.zeros((B, KVH, G, q_chunk), dtype=jnp.float32)
        a0 = jnp.zeros((B, KVH, G, q_chunk, hdv), dtype=jnp.float32)
        (m, l, acc, _, _), _ = jax.lax.scan(
            kv_step, (m0, l0, a0, qc, qpos), (ks, vs, kps)
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.transpose(0, 3, 1, 2, 4)  # (B, cq, KVH, G, hdv)

    _, outs = jax.lax.scan(q_step, None, (qs, qps))  # (nq, B, cq, KVH, G, hdv)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * q_chunk, KVH, G, hdv)
    return out[:, :Sq].astype(q.dtype)


def flash_attention_balanced(
    q: jax.Array,  # (B, Sq, KVH, G, hd)
    k: jax.Array,  # (B, Skv, KVH, hd)
    v: jax.Array,
    *,
    q_positions: jax.Array,
    kv_positions: jax.Array,
    chunk: int = 512,
) -> jax.Array:
    """§Perf hillclimb: BALANCED causal schedule — exact causal FLOPs.

    The rectangular baseline scans every (q-chunk, kv-chunk) pair and masks
    half of them (2x attention waste).  Here q-chunk i is paired with chunk
    n-1-i; member A needs kv chunks 0..i (i+1 of them), member B needs
    0..n-1-i (n-i), so every PAIR needs exactly n+1 kv-chunk steps — a
    static-shape scan doing n(n+1)/2 total chunk matmuls instead of n².
    Requires self-attention layout (Sq == Skv, causal); falls back to the
    rectangular path otherwise via the caller (``flash_attention``)."""
    B, Sq, KVH, G, hd = q.shape
    assert k.shape[1] == Sq, "balanced schedule is for self-attention"
    pad = (-Sq) % (2 * chunk)
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, (0, pad), constant_values=2**30)
        kv_positions = jnp.pad(kv_positions, (0, pad), constant_values=-1)
    S = q.shape[1]
    n = S // chunk
    scale = 1.0 / math.sqrt(hd)

    qs = q.reshape(B, n, chunk, KVH, G, hd).transpose(1, 0, 2, 3, 4, 5)
    ks = k.reshape(B, n, chunk, KVH, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, n, chunk, KVH, hd).transpose(1, 0, 2, 3, 4)
    qps = q_positions.reshape(n, chunk)
    kps = kv_positions.reshape(n, chunk)

    def pair_step(_, u):
        # members: A = chunk u, B = chunk n-1-u
        qa, qb = qs[u], qs[n - 1 - u]
        pa, pb = qps[u], qps[n - 1 - u]

        def init():
            m = jnp.full((B, KVH, G, chunk), NEG_INF, jnp.float32)
            l = jnp.zeros((B, KVH, G, chunk), jnp.float32)
            a = jnp.zeros((B, KVH, G, chunk, hd), jnp.float32)
            return m, l, a

        @jax.checkpoint
        def kv_step(carry, t):
            (ma, la, aa), (mb, lb, ab) = carry
            is_a = t <= u
            kv_idx = jnp.where(is_a, t, t - (u + 1))
            kc = jax.lax.dynamic_index_in_dim(ks, kv_idx, 0, keepdims=False)
            vc = jax.lax.dynamic_index_in_dim(vs, kv_idx, 0, keepdims=False)
            kpos = jax.lax.dynamic_index_in_dim(kps, kv_idx, 0, keepdims=False)
            qc = jnp.where(is_a, qa, qb)
            qpos = jnp.where(is_a, pa, pb)
            s = _attn_chunk(qc, kc, vc, qpos, kpos, scale, True, 0)
            m_old = jnp.where(is_a, ma, mb)
            l_old = jnp.where(is_a, la, lb)
            a_old = jnp.where(is_a, aa, ab)
            m_new = jnp.maximum(m_old, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_old - m_new)
            l_new = l_old * corr + p.sum(axis=-1)
            pv = jnp.einsum("bkgqc,bckh->bkgqh", p.astype(vc.dtype), vc)
            a_new = a_old * corr[..., None] + pv.astype(jnp.float32)
            new_a = tuple(jnp.where(is_a, nw, od) for nw, od in
                          zip((m_new, l_new, a_new), (ma, la, aa)))
            new_b = tuple(jnp.where(is_a, od, nw) for nw, od in
                          zip((m_new, l_new, a_new), (mb, lb, ab)))
            return (new_a, new_b), None

        ((ma, la, aa), (mb, lb, ab)), _ = jax.lax.scan(
            kv_step, (init(), init()), jnp.arange(n + 1, dtype=jnp.int32))
        oa = (aa / jnp.maximum(la, 1e-30)[..., None]).transpose(0, 3, 1, 2, 4)
        ob = (ab / jnp.maximum(lb, 1e-30)[..., None]).transpose(0, 3, 1, 2, 4)
        return None, (oa, ob)

    _, (outs_a, outs_b) = jax.lax.scan(
        pair_step, None, jnp.arange(n // 2, dtype=jnp.int32))
    # reassemble: pair u produced chunks u (A) and n-1-u (B)
    out = jnp.concatenate([outs_a, outs_b[::-1]], axis=0)  # (n, B, c, ...)
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, KVH, G, hd)
    return out[:, :Sq].astype(q.dtype)


# ---------------------------------------------------------------------------
# attention block (train / prefill / decode)
# ---------------------------------------------------------------------------


def _project_qkv(params: Params, x: jax.Array, cfg) -> Tuple[jax.Array, ...]:
    B, S, _ = x.shape
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"])
    k = jnp.einsum("bsd,dh->bsh", x, params["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, params["wv"])
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = logical_shard(q, "act_batch", "act_seq", "act_feat")
    k = logical_shard(k, "act_batch", "act_seq", "act_feat")
    v = logical_shard(v, "act_batch", "act_seq", "act_feat")
    q = q.reshape(B, S, KVH, H // KVH, hd)
    k = k.reshape(B, S, KVH, hd)
    v = v.reshape(B, S, KVH, hd)
    return q, k, v


def attention(
    params: Params,
    x: jax.Array,
    cfg,
    *,
    positions: jax.Array,  # (S,)
    causal: bool = True,
    window: int = 0,
    use_rope: bool = True,
    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,  # cross-attn
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Full-sequence attention (train / prefill).  Returns (out, (k, v)) so
    prefill can persist the KV cache."""
    B, S, _ = x.shape
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = _project_qkv(params, x, cfg)
    if kv_override is not None:
        k, v = kv_override
        kv_pos = jnp.arange(k.shape[1], dtype=jnp.int32)
    else:
        kv_pos = positions
    if use_rope and kv_override is None:
        q = rope(q.reshape(B, S, H, hd), positions[None], cfg.rope_theta).reshape(
            B, S, KVH, H // KVH, hd
        )
        k = rope(k, positions[None], cfg.rope_theta)
    balanced = (
        getattr(cfg, "attention_schedule", "rect") == "balanced"
        and causal and not window and kv_override is None and S == k.shape[1]
        and S >= 2 * 512
    )
    if balanced:
        out = flash_attention_balanced(
            q, k, v, q_positions=positions, kv_positions=kv_pos)
    else:
        out = flash_attention(
            q, k, v,
            q_positions=positions,
            kv_positions=kv_pos,
            causal=causal,
            window=window,
        )
    out = out.reshape(B, S, H * hd)
    out = logical_shard(out, "act_batch", "act_seq", "act_feat")
    proj = jnp.einsum("bsh,hd->bsd", out, params["wo"])
    return logical_shard(proj, "act_batch", "act_res_seq", "act_embed"), (k, v)


def decode_kv_row(
    params: Params, x: jax.Array, cfg, *, position: jax.Array, use_rope: bool = True
) -> Tuple[jax.Array, jax.Array]:
    """New token's (k, v) rows, RoPE'd at ``position``. x: (B, 1, D) ->
    (B, 1, kvd) each."""
    B = x.shape[0]
    KVH, hd = cfg.n_kv_heads, cfg.head_dim
    k_new = jnp.einsum("bsd,dh->bsh", x, params["wk"])
    v_new = jnp.einsum("bsd,dh->bsh", x, params["wv"])
    if cfg.qkv_bias:
        k_new, v_new = k_new + params["bk"], v_new + params["bv"]
    if use_rope:
        pos = jnp.full((B, 1), position, dtype=jnp.int32)
        k_new = rope(k_new.reshape(B, 1, KVH, hd), pos, cfg.rope_theta).reshape(
            B, 1, KVH * hd
        )
    return k_new, v_new


def decode_attend(
    params: Params,
    x: jax.Array,  # (B, 1, D)
    cfg,
    *,
    position: jax.Array,  # scalar int32: index of the current token
    k_cache: jax.Array,  # (B, T, kvd) flat — ALREADY containing the new row
    v_cache: jax.Array,
    kv_positions: jax.Array,  # (B, T) int32, -1 = empty slot
    use_rope: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """One-token attention over a (B, T, kv_flat) cache.  Returns (out,
    attn_mass (B, T)) — the per-row softmax mass feeding the AWRP scorer."""
    B = x.shape[0]
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"])
    if cfg.qkv_bias:
        q = q + params["bq"]
    q = q.reshape(B, 1, H, hd)
    if use_rope:
        pos = jnp.full((B, 1), position, dtype=jnp.int32)
        q = rope(q, pos, cfg.rope_theta)
    q = q.reshape(B, 1, KVH, H // KVH, hd)
    kc = k_cache.reshape(B, -1, KVH, hd)
    vc = v_cache.reshape(B, -1, KVH, hd)
    kc = logical_shard(kc, "act_batch", "act_pages", "act_kv_heads", None)
    vc = logical_shard(vc, "act_batch", "act_pages", "act_kv_heads", None)
    s = jnp.einsum("bqkgh,btkh->bkgqt", q, kc).astype(jnp.float32)
    s *= 1.0 / math.sqrt(hd)
    valid = kv_positions >= 0  # (B, T)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqt,btkh->bqkgh", p.astype(vc.dtype), vc)
    out = out.reshape(B, 1, H * hd)
    out = logical_shard(out, "act_batch", "act_seq", "act_feat")
    proj = jnp.einsum("bsh,hd->bsd", out, params["wo"])
    attn_mass = p.sum(axis=(1, 2, 3))  # (B, T)
    return proj, attn_mass


def decode_q(
    params: Params, x: jax.Array, cfg, *, position: jax.Array,
    use_rope: bool = True
) -> jax.Array:
    """The query half of ``decode_attend`` alone — (B, 1, D) ->
    (B, KVH, G, hd) grouped queries, RoPE'd at ``position`` — for the fused
    policy-attention kernel path where attention itself happens in-kernel
    (``paged_kv.fused_decode_step``)."""
    B = x.shape[0]
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"])
    if cfg.qkv_bias:
        q = q + params["bq"]
    q = q.reshape(B, 1, H, hd)
    if use_rope:
        pos = jnp.full((B, 1), position, dtype=jnp.int32)
        q = rope(q, pos, cfg.rope_theta)
    return q.reshape(B, KVH, H // KVH, hd)


def decode_project_out(params: Params, out: jax.Array, cfg) -> jax.Array:
    """The output half of ``decode_attend`` alone — kernel attention output
    (B, KVH, G, hd) -> (B, 1, D) via the ``wo`` projection, with the same
    logical sharding annotations as the unfused path."""
    B = out.shape[0]
    H, hd = cfg.n_heads, cfg.head_dim
    out = out.reshape(B, 1, H * hd)
    out = logical_shard(out, "act_batch", "act_seq", "act_feat")
    proj = jnp.einsum("bsh,hd->bsd", out, params["wo"])
    return proj


# ---------------------------------------------------------------------------
# multi-head latent attention (MLA, DeepSeek-V2 §2.1)
# ---------------------------------------------------------------------------

#: ``jax.named_scope``s of the serving step's MLA and MoE parts, the
#: scopes ``obs.profiling.op_scopes`` maps a compiled step's ops to
SERVE_SCOPES = ("mla_attend", "moe_route", "moe_experts", "moe_shared")


def mla_latent(params: Params, x: jax.Array, cfg, positions: jax.Array
               ) -> jax.Array:
    """The row MLA caches per token, shared by every head: x (B, S, D) ->
    (B, S, r + rope) = [RMSNorm(c) | k_pe roped at ``positions`` (B, S)]."""
    r = cfg.kv_lora_rank
    ckv = jnp.einsum("bsd,dc->bsc", x, params["w_kva"])
    c = rmsnorm(ckv[..., :r], params["kv_norm"], cfg.norm_eps)
    k_pe = rope(ckv[..., None, r:], positions, cfg.rope_theta)[:, :, 0]
    return jnp.concatenate([c, k_pe.astype(c.dtype)], axis=-1)


def _mla_q(params: Params, x: jax.Array, cfg, positions: jax.Array):
    """Per-head queries (B, S, H, nope) and roped (B, S, H, rope)."""
    B, S, _ = x.shape
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"])
    q = q.reshape(B, S, cfg.n_heads, dn + dr)
    return q[..., :dn], rope(q[..., dn:], positions, cfg.rope_theta)


def _mla_scale(cfg) -> float:
    return 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def mla_attention(params: Params, x: jax.Array, cfg, *,
                  positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Causal MLA over a whole prompt (train / prefill), naive form: the
    latent rows expanded to per-head K (nope | shared k_pe) and V.  Returns
    (out, latent rows (B, S, r + rope)) so prefill can seed the cache."""
    B, S, _ = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    with jax.named_scope("mla_attend"):
        q_nope, q_pe = _mla_q(params, x, cfg, positions[None])
        lat = mla_latent(params, x, cfg, positions[None])
        kv = jnp.einsum("bsc,ch->bsh", lat[..., :r], params["w_kvb"])
        kv = kv.reshape(B, S, H, dn + dv)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(lat[:, :, None, r:], (B, S, H, dr))],
            axis=-1)
        q = jnp.concatenate([q_nope, q_pe.astype(q_nope.dtype)], axis=-1)
        out = flash_attention(
            q[:, :, :, None], k, kv[..., dn:], q_positions=positions,
            kv_positions=positions, causal=True, scale=_mla_scale(cfg))
        out = logical_shard(out.reshape(B, S, H * dv),
                            "act_batch", "act_seq", "act_feat")
        proj = jnp.einsum("bsh,hd->bsd", out, params["wo"])
    return logical_shard(proj, "act_batch", "act_res_seq", "act_embed"), lat


def mla_decode_attend(
    params: Params,
    x: jax.Array,  # (B, 1, D)
    cfg,
    *,
    position: jax.Array,  # scalar int32
    cache: jax.Array,  # (B, T, r + rope) latent rows, the new one included
    kv_positions: jax.Array,  # (B, T) int32, -1 = empty slot
) -> Tuple[jax.Array, jax.Array]:
    """One-token MLA in absorbed form over latent rows: the query's nope
    part is taken into the latent space (q_nope W_UK^T), scores are read
    against the rows themselves and the output projected from the latent
    (W_UV), so the cache is never expanded per head.  Returns (out,
    attn_mass (B, T)) — the mass summed over heads feeds the AWRP scorer."""
    B = x.shape[0]
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    with jax.named_scope("mla_attend"):
        q_nope, q_pe = _mla_q(params, x, cfg,
                              jnp.full((B, 1), position, jnp.int32))
        w_kvb = params["w_kvb"].reshape(r, H, dn + dv)
        q_lat = jnp.einsum("bhn,chn->bhc", q_nope[:, 0], w_kvb[..., :dn])
        qc = jnp.concatenate([q_lat, q_pe[:, 0].astype(q_lat.dtype)], axis=-1)
        s = jnp.einsum("bhc,btc->bht", qc.astype(cache.dtype), cache,
                       preferred_element_type=jnp.float32) * _mla_scale(cfg)
        s = jnp.where((kv_positions >= 0)[:, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bht,btc->bhc", p.astype(cache.dtype),
                           cache[..., :r])
        o = jnp.einsum("bhc,chv->bhv", o_lat, w_kvb[..., dn:])
        o = logical_shard(o.reshape(B, 1, H * dv),
                          "act_batch", "act_seq", "act_feat")
        proj = jnp.einsum("bsh,hd->bsd", o.astype(x.dtype), params["wo"])
    return proj, p.sum(axis=1)


# ---------------------------------------------------------------------------
# MoE (sort-based dispatch, static capacity — GSPMD-friendly)
# ---------------------------------------------------------------------------


def route(params: Params, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """Top-k routing over all ``cfg.n_experts``: (gates (B, S, K) float32,
    expert ids (B, S, K)).

    ``softmax``: top-k of the softmax, renormalised.  ``sigmoid``
    (DeepSeek-V3, ``noaux_tc``): float32 logits, sigmoid scores, top-k of
    score + the correction bias (selection only), the chosen scores
    divided by their sum, times ``routed_scaling``."""
    K = cfg.top_k
    if cfg.router_scoring == "sigmoid":
        logits = jnp.einsum(
            "bsd,de->bse", x.astype(jnp.float32),
            params["w_router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        _, expert_idx = jax.lax.top_k(
            scores + params["router_bias"].astype(jnp.float32), K)
        gate = jnp.take_along_axis(scores, expert_idx, axis=-1)
        gate = gate / gate.sum(-1, keepdims=True)
        return gate * cfg.routed_scaling, expert_idx
    logits = jnp.einsum("bsd,de->bse", x, params["w_router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert_idx = jax.lax.top_k(probs, K)  # (B, S, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    return gate, expert_idx


#: tokens per pass of the held-expert share: a pass sorts and gathers
#: tokens x top_k rows, so prefill's 16 x 4096 tokens run in 16 passes
HELD_CHUNK = 4096


def held_experts(params: Params, x: jax.Array, gate: jax.Array,
                 expert_idx: jax.Array, cfg) -> jax.Array:
    """The held experts' share of the routed output, no token dropped:
    ``sum_{k in top-k, held} gate_k E_k(x)`` for every token, where this
    chip holds experts ``cfg.expert_offset ..`` ``+ cfg.experts_held - 1``.
    Each pass sorts its (token, k) pairs by held expert (pairs for experts
    held elsewhere last) and runs the three SwiGLU matmuls as grouped
    matmuls (``jax.lax.ragged_dot``) over the sorted rows."""
    B, S, D = x.shape
    K, Eh = cfg.top_k, cfg.experts_held
    T = B * S
    local = expert_idx.reshape(T, K) - cfg.expert_offset
    group = jnp.where((local >= 0) & (local < Eh), local, Eh)  # Eh: elsewhere
    C = min(T, HELD_CHUNK)
    pad = (-T) % C
    xt = jnp.pad(x.reshape(T, D), ((0, pad), (0, 0)))
    gt = jnp.pad(group, ((0, pad), (0, 0)), constant_values=Eh)
    wt = jnp.pad(gate.reshape(T, K), ((0, pad), (0, 0)))

    def one_pass(args):
        xc, gc, wc = args  # (C, D), (C, K), (C, K)
        flat = gc.reshape(C * K)
        order = jnp.argsort(flat, stable=True)
        rows = xc[order // K]
        sizes = jnp.bincount(flat, length=Eh + 1)[:Eh].astype(jnp.int32)
        h = (jax.nn.silu(jax.lax.ragged_dot(rows, params["w_gate"], sizes))
             * jax.lax.ragged_dot(rows, params["w_up"], sizes))
        y = jax.lax.ragged_dot(h, params["w_down"], sizes)
        y = jnp.where((flat[order] < Eh)[:, None], y, 0)  # rows held elsewhere
        y = jnp.zeros_like(y).at[order].set(y).reshape(C, K, D)
        return jnp.einsum("ckd,ck->cd", y.astype(jnp.float32),
                          jnp.where(gc < Eh, wc, 0.0))

    n = xt.shape[0] // C
    if n == 1:
        out = one_pass((xt, gt, wt))
    else:
        out = jax.lax.map(one_pass, (xt.reshape(n, C, D), gt.reshape(n, C, K),
                                     wt.reshape(n, C, K))).reshape(n * C, D)
    return out[:T].reshape(B, S, D).astype(x.dtype)


def held_counts(expert_idx: jax.Array, cfg) -> jax.Array:
    """``[held_pairs, experts_hit]`` (int32) of one expert layer's call:
    the (token, k) pairs routed to experts this chip holds, and how many
    held experts got at least one."""
    Eh = cfg.experts_held or cfg.n_experts
    local = expert_idx.reshape(-1) - cfg.expert_offset
    held = (local >= 0) & (local < Eh)
    per = jnp.bincount(jnp.where(held, local, Eh), length=Eh + 1)[:Eh]
    return jnp.stack([held.sum(dtype=jnp.int32),
                      (per > 0).sum(dtype=jnp.int32)])


def moe(params: Params, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """Top-k MoE: ``route``, the routed experts' output, plus the shared
    experts (``cfg.shared_ff``).  Returns ``(out, held_counts)``.

    ``cfg.experts_held`` set: the expert-parallel layer — this chip holds
    ``experts_held`` of the ``n_experts`` it routes over and computes
    their share with no token dropped (``held_experts``); what the experts
    held elsewhere add is left out.  Otherwise every expert is computed
    here by sort-based dispatch with PER-SEQUENCE capacity groups.

    Each sequence dispatches its own S·k token-expert pairs (argsort by
    expert, rank-within-expert, first C kept — GShard-style dropping).  The
    group axis rides the batch sharding, so every gather/scatter is a batched
    op local to its data shard (no cross-shard token exchange materializes —
    this was a 100+GiB/device blowup with a single global sort at 1M-token
    prefill).  The (B, E, C, D) buffer shards (data, ep?, -, -); expert d_ff
    shards over "model" in TP mode, the E axis does in EP mode.
    """
    with jax.named_scope("moe_route"):
        gate, expert_idx = route(params, x, cfg)
    with jax.named_scope("moe_experts"):
        if cfg.experts_held:
            out = held_experts(params, x, gate, expert_idx, cfg)
        else:
            out = _capacity_dispatch(params, x, gate, expert_idx, cfg)
    if cfg.shared_ff:
        with jax.named_scope("moe_shared"):
            out = out + mlp({"w_gate": params["shared_gate"],
                             "w_up": params["shared_up"],
                             "w_down": params["shared_down"]}, x, "swiglu")
    out = logical_shard(out, "act_batch", "act_res_seq", "act_embed")
    return out, held_counts(expert_idx, cfg)


def _capacity_dispatch(params: Params, x: jax.Array, gate: jax.Array,
                       expert_idx: jax.Array, cfg) -> jax.Array:
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    C = max(8, int(S * K / E * cfg.capacity_factor))

    pairs_e = expert_idx.reshape(B, S * K)
    order = jnp.argsort(pairs_e, axis=-1, stable=True)  # (B, S*K)
    sorted_e = jnp.take_along_axis(pairs_e, order, axis=-1)
    counts = jax.vmap(lambda p: jnp.bincount(p, length=E))(pairs_e)  # (B, E)
    starts = jnp.cumsum(counts, axis=-1) - counts
    rank = (jnp.arange(S * K, dtype=jnp.int32)[None]
            - jnp.take_along_axis(starts, sorted_e, axis=-1).astype(jnp.int32))
    keep = rank < C
    rank_c = jnp.minimum(rank, C - 1)

    src_token = order // K  # (B, S*K) indices into S
    src_rows = jnp.take_along_axis(
        x, src_token[..., None], axis=1
    ) * keep[..., None].astype(x.dtype)
    # batched 2-D scatter-add: (expert, rank) unique per kept pair per group
    buf = jax.vmap(
        lambda se, rc, rows: jnp.zeros((E, C, D), x.dtype).at[se, rc].add(rows)
    )(sorted_e, rank_c, src_rows)
    buf = logical_shard(buf, "act_batch", "act_experts", None, "act_embed")

    if cfg.act == "swiglu":
        h = jnp.einsum("becd,edf->becf", buf, params["w_gate"])
        u = jnp.einsum("becd,edf->becf", buf, params["w_up"])
        h = logical_shard(h, "act_batch", "act_experts", None, "act_expert_ff")
        u = logical_shard(u, "act_batch", "act_experts", None, "act_expert_ff")
        h = jax.nn.silu(h) * u
    else:
        h = jnp.einsum("becd,edf->becf", buf, params["w_up"])
        h = logical_shard(h, "act_batch", "act_experts", None, "act_expert_ff")
        h = jax.nn.gelu(h)
    eout = jnp.einsum("becf,efd->becd", h, params["w_down"])
    eout = logical_shard(eout, "act_batch", "act_experts", None, "act_embed")

    gathered = jax.vmap(lambda eo, se, rc: eo[se, rc])(eout, sorted_e, rank_c)
    gathered = gathered * keep[..., None].astype(x.dtype)
    w = jnp.take_along_axis(gate.reshape(B, S * K), order, axis=-1)
    out = jax.vmap(
        lambda st, rows: jnp.zeros((S, D), x.dtype).at[st].add(rows)
    )(src_token, gathered * w[..., None].astype(x.dtype))
    return out


def moe_aux_loss(params: Params, x: jax.Array, cfg) -> jax.Array:
    """Load-balancing auxiliary loss (Switch-style f·P)."""
    T = x.shape[0] * x.shape[1]
    logits = jnp.einsum("bsd,de->bse", x, params["w_router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1).reshape(T, cfg.n_experts)
    top1 = jnp.argmax(probs, -1)
    f = jnp.mean(jax.nn.one_hot(top1, cfg.n_experts, dtype=jnp.float32), axis=0)
    p = jnp.mean(probs, axis=0)
    return cfg.n_experts * jnp.sum(f * p)


# ---------------------------------------------------------------------------
# Mamba2 (SSD — state-space duality, chunked)
# ---------------------------------------------------------------------------


def _segsum(x: jax.Array) -> jax.Array:
    """x: (..., T) -> (..., T, T) with out[i,j] = sum_{j<k<=i} x[k], -inf above
    the diagonal."""
    T = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), dtype=bool))
    return jnp.where(mask, out, -jnp.inf)


def ssd_chunked(
    x: jax.Array,  # (B, S, H, P)
    dt: jax.Array,  # (B, S, H) — post-softplus
    A: jax.Array,  # (H,) negative
    Bm: jax.Array,  # (B, S, N)
    Cm: jax.Array,  # (B, S, N)
    chunk: int,
    initial_state: Optional[jax.Array] = None,  # (B, H, P, N)
) -> Tuple[jax.Array, jax.Array]:
    """Mamba-2 SSD forward (chunked scan).  Returns (y, final_state)."""
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    S = x.shape[1]
    nc = S // chunk
    xc = x.reshape(b, nc, chunk, h, p)
    dtc = dt.reshape(b, nc, chunk, h).astype(jnp.float32)
    Bc = Bm.reshape(b, nc, chunk, n)
    Cc = Cm.reshape(b, nc, chunk, n)

    dA = dtc * A.astype(jnp.float32)  # (b,nc,q,h)
    dA_cs = jnp.cumsum(dA, axis=2)

    # 1) intra-chunk (diagonal blocks)
    L = jnp.exp(_segsum(dA.transpose(0, 1, 3, 2)))  # (b,nc,h,q,q)
    scores = jnp.einsum("bcqn,bckn->bcqk", Cc, Bc).astype(jnp.float32)
    M = scores[:, :, None] * L  # (b,nc,h,q,k)
    xdt = (xc.astype(jnp.float32) * dtc[..., None])  # (b,nc,q,h,p)
    y_diag = jnp.einsum("bchqk,bckhp->bcqhp", M, xdt)

    # 2) per-chunk input states
    decay_states = jnp.exp(dA_cs[:, :, -1:, :] - dA_cs)  # (b,nc,q,h)
    states = jnp.einsum("bcqn,bcqh,bcqhp->bchpn", Bc, decay_states * dtc,
                        xc.astype(jnp.float32))

    # 3) inter-chunk recurrence
    chunk_decay = jnp.exp(dA_cs[:, :, -1, :])  # (b,nc,h)
    init = (
        jnp.zeros((b, h, p, n), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )

    def step(carry, inp):
        st, dec = inp  # (b,h,p,n), (b,h)
        new = st + carry * dec[..., None, None]
        return new, carry  # emit state at chunk START

    final, start_states = jax.lax.scan(
        step, init, (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2))
    )
    start_states = start_states.transpose(1, 0, 2, 3, 4)  # (b,nc,h,p,n)

    # 4) inter-chunk output
    state_decay_out = jnp.exp(dA_cs)  # (b,nc,q,h)
    y_off = jnp.einsum(
        "bcqn,bchpn,bcqh->bcqhp", Cc, start_states, state_decay_out
    )
    y = (y_diag + y_off).reshape(b, S, h, p)[:, :s]
    return y.astype(x.dtype), final


def mamba2_block(
    params: Params,
    x: jax.Array,  # (B, S, D)
    cfg,
    *,
    initial_state: Optional[jax.Array] = None,
    initial_conv: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full Mamba2 block (train/prefill). Returns (y, final_state, conv_tail)."""
    B, S, D = x.shape
    d_in, H, P, N = cfg.d_inner, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    conv_ch = d_in + 2 * N

    zxbcdt = jnp.einsum("bsd,de->bse", x, params["w_in"])
    zxbcdt = logical_shard(zxbcdt, "act_batch", "act_seq", "act_feat")
    z, xBC, dt = jnp.split(zxbcdt, [d_in, d_in + conv_ch], axis=-1)

    # causal depthwise conv over xBC
    if initial_conv is None:
        initial_conv = jnp.zeros((B, cfg.d_conv - 1, conv_ch), x.dtype)
    xpad = jnp.concatenate([initial_conv, xBC], axis=1)
    conv_tail = xpad[:, -(cfg.d_conv - 1):, :] if cfg.d_conv > 1 else jnp.zeros(
        (B, 0, conv_ch), x.dtype
    )
    wconv = params["w_conv"]  # (d_conv, conv_ch)
    xconv = sum(
        xpad[:, i : i + S, :] * wconv[i][None, None] for i in range(cfg.d_conv)
    )
    xBC = jax.nn.silu(xconv + params["b_conv"][None, None])

    xs, Bm, Cm = jnp.split(xBC, [d_in, d_in + N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["a_log"].astype(jnp.float32))  # (H,)
    y, final_state = ssd_chunked(
        xs.reshape(B, S, H, P), dt, A, Bm, Cm, cfg.ssm_chunk,
        initial_state=initial_state,
    )
    y = y + xs.reshape(B, S, H, P) * params["d_skip"].astype(x.dtype)[None, None, :, None]
    y = y.reshape(B, S, d_in)
    y = rmsnorm(y * jax.nn.silu(z), params["norm_scale"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, params["w_out"])
    return logical_shard(out, "act_batch", "act_res_seq", "act_embed"), final_state, conv_tail


def mamba2_decode_step(
    params: Params,
    x: jax.Array,  # (B, 1, D)
    cfg,
    *,
    state: jax.Array,  # (B, H, P, N)
    conv_state: jax.Array,  # (B, d_conv-1, conv_ch)
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """O(1) recurrent decode step."""
    B = x.shape[0]
    d_in, H, P, N = cfg.d_inner, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    conv_ch = d_in + 2 * N

    zxbcdt = jnp.einsum("bsd,de->bse", x, params["w_in"])[:, 0]
    z, xBC, dt = jnp.split(zxbcdt, [d_in, d_in + conv_ch], axis=-1)

    xfull = jnp.concatenate([conv_state, xBC[:, None, :]], axis=1)  # (B,d_conv,ch)
    wconv = params["w_conv"]
    xconv = jnp.einsum("bkc,kc->bc", xfull, wconv) + params["b_conv"]
    xBC = jax.nn.silu(xconv)
    new_conv = xfull[:, 1:, :]

    xs, Bm, Cm = jnp.split(xBC, [d_in, d_in + N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (B,H)
    A = -jnp.exp(params["a_log"].astype(jnp.float32))
    dA = jnp.exp(dt * A)  # (B,H)
    xh = xs.reshape(B, H, P).astype(jnp.float32)
    upd = jnp.einsum("bn,bh,bhp->bhpn", Bm.astype(jnp.float32), dt, xh)
    new_state = state * dA[..., None, None] + upd
    y = jnp.einsum("bn,bhpn->bhp", Cm.astype(jnp.float32), new_state)
    y = y + xh * params["d_skip"].astype(jnp.float32)[None, :, None]
    y = y.reshape(B, d_in).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z), params["norm_scale"], cfg.norm_eps)
    out = jnp.einsum("be,ed->bd", y, params["w_out"])[:, None, :]
    return out, new_state, new_conv
