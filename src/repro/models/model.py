"""Model assembly: parameter declaration/init, pattern-scanned stacks, and
the three execution paths (train forward, prefill, decode step).

Layer stacking: the repeating pattern unit (e.g. gemma3's 5×local+1×global,
zamba2's 5×mamba+1×shared_attn) is scanned over ``n_repeats`` with parameters
stacked on a leading "layers" dim — compile time is unit-sized, not
depth-sized.  ``shared_attn`` positions close over ONE unstacked param set
(Zamba2 weight sharing).  Lead layers (``cfg.lead``, DeepSeek-V3's leading
dense layer) run unrolled before the scan, tail layers after it.

A model with "mla_moe" layers also carries ``caches["moe"]``: the last
step's ``[held_pairs, experts_hit]`` summed over its expert layers
(``layers.held_counts``), for the serving engine's counters.

Caches (decode) are PyTrees with leading (n_repeats, ...) dims scanned along
with the params; see ``repro.cache.paged_kv`` for the AWRP bounded pool.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache import paged_kv
from repro.models import layers as L
from repro.sharding.specs import logical_shard

Params = Dict[str, Any]


def pad_vocab(cfg) -> int:
    return ((cfg.vocab + 127) // 128) * 128


# ---------------------------------------------------------------------------
# parameter declarations (single source of truth for init / dry-run / specs)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Decl:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"  # normal | zeros | ones | a_log | dt_bias
    scale: float = 0.02


def _attn_decls(cfg) -> Dict[str, Decl]:
    d, qk, kv = cfg.d_model, cfg.qk_dim, cfg.kv_dim
    out = {
        "wq": Decl((d, qk), ("p_embed", "p_feat")),
        "wk": Decl((d, kv), ("p_embed", "p_feat")),
        "wv": Decl((d, kv), ("p_embed", "p_feat")),
        "wo": Decl((qk, d), ("p_feat", "p_embed")),
        "ln1": Decl((d,), ("p_noshard",), "zeros"),
        "ln2": Decl((d,), ("p_noshard",), "zeros"),
    }
    if cfg.qkv_bias:
        out["bq"] = Decl((qk,), ("p_feat",), "zeros")
        out["bk"] = Decl((kv,), ("p_feat",), "zeros")
        out["bv"] = Decl((kv,), ("p_feat",), "zeros")
    return out


def _mlp_decls(cfg) -> Dict[str, Decl]:
    d, ff = cfg.d_model, cfg.d_ff
    out = {
        "w_up": Decl((d, ff), ("p_embed", "p_feat")),
        "w_down": Decl((ff, d), ("p_feat", "p_embed")),
    }
    if cfg.act == "swiglu":
        out["w_gate"] = Decl((d, ff), ("p_embed", "p_feat"))
    return out


def _moe_decls(cfg) -> Dict[str, Decl]:
    """Router over all ``n_experts``; the held experts' weights (all of
    them unless ``experts_held``); the correction bias and shared experts
    where the config has them."""
    d, e = cfg.d_model, cfg.n_experts
    ff, held = cfg.expert_ff or cfg.d_ff, cfg.experts_held or e
    out = {
        "w_router": Decl((d, e), ("p_embed", "p_noshard")),
        "w_up": Decl((held, d, ff), ("p_experts", "p_embed", "p_expert_ff")),
        "w_down": Decl((held, ff, d), ("p_experts", "p_expert_ff", "p_embed")),
    }
    if cfg.act == "swiglu":
        out["w_gate"] = Decl((held, d, ff), ("p_experts", "p_embed", "p_expert_ff"))
    if cfg.router_scoring == "sigmoid":
        out["router_bias"] = Decl((e,), ("p_noshard",))
    if cfg.shared_ff:
        sf = cfg.shared_ff
        out["shared_gate"] = Decl((d, sf), ("p_embed", "p_feat"))
        out["shared_up"] = Decl((d, sf), ("p_embed", "p_feat"))
        out["shared_down"] = Decl((sf, d), ("p_feat", "p_embed"))
    return out


def _mamba_decls(cfg) -> Dict[str, Decl]:
    d, din, n, h = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    conv_ch = din + 2 * n
    zxbcdt = 2 * din + 2 * n + h
    return {
        "w_in": Decl((d, zxbcdt), ("p_embed", "p_feat")),
        "w_conv": Decl((cfg.d_conv, conv_ch), ("p_noshard", "p_feat")),
        "b_conv": Decl((conv_ch,), ("p_feat",), "zeros"),
        "dt_bias": Decl((h,), ("p_noshard",), "dt_bias"),
        "a_log": Decl((h,), ("p_noshard",), "a_log"),
        "d_skip": Decl((h,), ("p_noshard",), "ones"),
        "norm_scale": Decl((din,), ("p_feat",), "zeros"),
        "w_out": Decl((din, d), ("p_feat", "p_embed")),
        "ln1": Decl((d,), ("p_noshard",), "zeros"),
    }


def _cross_decls(cfg) -> Dict[str, Decl]:
    """whisper decoder: self-attn + cross-attn + mlp (+3 norms)."""
    out = {}
    for pre, decls in (("self_", _attn_decls(cfg)), ("cross_", _attn_decls(cfg))):
        for k, v in decls.items():
            if k.startswith("ln"):
                continue
            out[pre + k] = v
    for k, v in _mlp_decls(cfg).items():
        out[k] = v
    d = cfg.d_model
    out["ln1"] = Decl((d,), ("p_noshard",), "zeros")
    out["ln2"] = Decl((d,), ("p_noshard",), "zeros")
    out["ln3"] = Decl((d,), ("p_noshard",), "zeros")
    return out


def _mla_decls(cfg) -> Dict[str, Decl]:
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq": Decl((d, H * (dn + dr)), ("p_embed", "p_feat")),
        "w_kva": Decl((d, r + dr), ("p_embed", "p_noshard")),
        "kv_norm": Decl((r,), ("p_noshard",), "zeros"),
        "w_kvb": Decl((r, H * (dn + dv)), ("p_noshard", "p_feat")),
        "wo": Decl((H * dv, d), ("p_feat", "p_embed")),
        "ln1": Decl((d,), ("p_noshard",), "zeros"),
        "ln2": Decl((d,), ("p_noshard",), "zeros"),
    }


#: block kinds whose attention is MLA over one cached latent row per token
MLA_KINDS = ("mla", "mla_moe")


def block_decls(cfg, kind: str) -> Dict[str, Decl]:
    if kind in ("attn", "global", "local", "shared_attn"):
        return {**_attn_decls(cfg), **_mlp_decls(cfg)}
    if kind == "moe":
        return {**_attn_decls(cfg), **_moe_decls(cfg)}
    if kind == "mla":
        return {**_mla_decls(cfg), **_mlp_decls(cfg)}
    if kind == "mla_moe":
        return {**_mla_decls(cfg), **_moe_decls(cfg)}
    if kind == "mamba":
        return _mamba_decls(cfg)
    if kind == "enc":
        return {**_attn_decls(cfg), **_mlp_decls(cfg)}
    if kind == "dec":
        return _cross_decls(cfg)
    raise ValueError(kind)


Plan = List[Tuple[str, str]]


def scan_plan(cfg) -> Tuple[Plan, Plan, int, Plan]:
    """Returns (lead, unit, n_repeats, tail) where lead/unit/tail entries
    are (position_name, kind); lead and tail run unrolled around the scan
    of the unit."""
    if cfg.family == "encdec":
        return [], [], 0, []
    lead = [(f"l{i}", k) for i, k in enumerate(cfg.lead)]
    if cfg.pattern is None:
        kind = "moe" if cfg.n_experts else "attn"
        return lead, [("u0", kind)], cfg.n_layers - len(lead), []
    unit = [(f"u{i}", k) for i, k in enumerate(cfg.pattern)]
    tail = [(f"t{i}", k) for i, k in enumerate(cfg.tail)]
    return lead, unit, cfg.n_repeats, tail


def has_expert_counts(cfg) -> bool:
    """Whether the model's steps carry ``caches["moe"]``."""
    return "mla_moe" in cfg.layer_pattern


def param_decls(cfg) -> Dict[str, Any]:
    """Full declaration tree: {name: Decl | {name: Decl}} with stacked
    leading dims for scanned positions."""
    V, d = pad_vocab(cfg), cfg.d_model
    tree: Dict[str, Any] = {
        "embed": Decl((V, d), ("p_vocab", "p_embed"), scale=1.0),
        "final_norm": Decl((d,), ("p_noshard",), "zeros"),
    }
    if not cfg.tie_embeddings:
        tree["unembed"] = Decl((V, d), ("p_vocab", "p_embed"))

    def stack(decls: Dict[str, Decl], n: int) -> Dict[str, Decl]:
        return {
            k: Decl((n,) + v.shape, ("layers",) + v.axes, v.init, v.scale)
            for k, v in decls.items()
        }

    if cfg.family == "encdec":
        tree["enc"] = stack(block_decls(cfg, "enc"), cfg.enc_layers)
        tree["dec"] = stack(block_decls(cfg, "dec"), cfg.dec_layers)
        tree["enc_final_norm"] = Decl((d,), ("p_noshard",), "zeros")
        return tree

    lead, unit, n_rep, tail = scan_plan(cfg)
    for pos, kind in lead:
        tree[pos] = block_decls(cfg, kind)
    shared_done = False
    for pos, kind in unit:
        if kind == "shared_attn":
            if not shared_done:
                tree["shared_attn"] = block_decls(cfg, kind)
                shared_done = True
        else:
            tree[pos] = stack(block_decls(cfg, kind), n_rep)
    for pos, kind in tail:
        tree[pos] = block_decls(cfg, kind)
    return tree


def _materialize(decl: Decl, key: jax.Array, dtype) -> jax.Array:
    if decl.init == "zeros":
        return jnp.zeros(decl.shape, dtype)
    if decl.init == "ones":
        return jnp.ones(decl.shape, dtype)
    if decl.init == "a_log":
        h = decl.shape[-1]
        vals = jnp.log(jnp.linspace(1.0, 16.0, h))
        return jnp.broadcast_to(vals, decl.shape).astype(jnp.float32)
    if decl.init == "dt_bias":
        # inverse softplus of dt in [1e-3, 1e-1]
        u = jax.random.uniform(key, decl.shape, jnp.float32)
        dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return dt + jnp.log(-jnp.expm1(-dt))
    fan_in = decl.shape[-2] if len(decl.shape) >= 2 else decl.shape[-1]
    scale = min(decl.scale, 1.0 / math.sqrt(fan_in))
    return (jax.random.normal(key, decl.shape, jnp.float32) * scale).astype(dtype)


def init_params(cfg, key: jax.Array) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    flat: List[Tuple[Tuple[str, ...], Decl]] = []

    def walk(tree, prefix):
        for k, v in tree.items():
            if isinstance(v, Decl):
                flat.append((prefix + (k,), v))
            else:
                walk(v, prefix + (k,))

    walk(param_decls(cfg), ())
    keys = jax.random.split(key, len(flat))
    out: Params = {}
    for (path, decl), kk in zip(flat, keys):
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        # norm-ish params stay fp32 for stability
        dt = jnp.float32 if decl.init in ("a_log", "dt_bias", "zeros", "ones") and len(decl.shape) <= 2 and decl.shape[-1] <= 16384 and path[-1] in ("ln1", "ln2", "ln3", "final_norm", "enc_final_norm", "norm_scale", "kv_norm", "a_log", "dt_bias", "d_skip") else dtype
        node[path[-1]] = _materialize(decl, kk, dt)
    return out


def abstract_params(cfg) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)

    def to_sds(tree):
        out = {}
        for k, v in tree.items():
            if isinstance(v, Decl):
                dt = jnp.float32 if k in ("ln1", "ln2", "ln3", "final_norm",
                                          "enc_final_norm", "norm_scale",
                                          "kv_norm", "a_log", "dt_bias",
                                          "d_skip") else dtype
                out[k] = jax.ShapeDtypeStruct(v.shape, dt)
            else:
                out[k] = to_sds(v)
        return out

    return to_sds(param_decls(cfg))


def param_logical_axes(cfg) -> Dict[str, Any]:
    def to_axes(tree):
        return {
            k: (v.axes if isinstance(v, Decl) else to_axes(v))
            for k, v in tree.items()
        }

    return to_axes(param_decls(cfg))


# ---------------------------------------------------------------------------
# block application (train / prefill)
# ---------------------------------------------------------------------------


def _apply_block(kind: str, p: Params, x: jax.Array, cfg, positions, collect_cache):
    """One block over a whole sequence: (x, cache or None, expert counts or
    None)."""
    if kind == "mamba":
        h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
        y, state, conv = L.mamba2_block(p, h, cfg)
        cache = {"state": state, "conv": conv} if collect_cache else None
        return x + y, cache, None
    h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
    if kind in MLA_KINDS:
        attn_out, lat = L.mla_attention(p, h, cfg, positions=positions)
        cache = {"c": lat} if collect_cache else None
    else:
        window = cfg.sliding_window if kind == "local" else 0
        attn_out, (k, v) = L.attention(
            p, h, cfg, positions=positions, causal=True, window=window
        )
        cache = {"k": k.reshape(k.shape[0], k.shape[1], -1),
                 "v": v.reshape(v.shape[0], v.shape[1], -1)} if collect_cache else None
    x = x + attn_out
    h2 = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
    counts = None
    if kind == "mla_moe":
        ff, counts = L.moe(p, h2, cfg)
    elif kind == "moe":
        ff, _ = L.moe(p, h2, cfg)
    else:
        ff = L.mlp(p, h2, cfg.act)
    return x + ff, cache, counts


def _add_counts(total, counts):
    if counts is None:
        return total
    return counts if total is None else total + counts


def _stack_scan(params, x, cfg, positions, collect_cache):
    """Run the lead layers, scan the pattern unit over n_repeats, then run
    the tail.  Returns (x, (lead, unit, tail caches), expert counts)."""
    lead, unit, n_rep, tail = scan_plan(cfg)
    stacked = {pos: params[pos] for pos, kind in unit if kind != "shared_attn"}
    shared = params.get("shared_attn")
    counts = None
    lead_caches = {}
    for pos, kind in lead:
        x, cache, c = _apply_block(kind, params[pos], x, cfg, positions,
                                   collect_cache)
        counts = _add_counts(counts, c)
        if collect_cache and cache is not None:
            lead_caches[pos] = cache

    def body(carry, slices):
        h = carry
        caches, unit_counts = {}, None
        for pos, kind in unit:
            p = shared if kind == "shared_attn" else slices[pos]
            h, cache, c = _apply_block(kind, p, h, cfg, positions, collect_cache)
            unit_counts = _add_counts(unit_counts, c)
            if collect_cache and cache is not None:
                caches[pos] = cache
        return h, (caches if collect_cache else None, unit_counts)

    if cfg.remat == "full" and not collect_cache:
        # recompute block interiors in backward: only layer-boundary carries
        # are saved across the depth scan (flash chunks re-checkpoint inside)
        body = jax.checkpoint(body)
    x, (unit_caches, unit_counts) = jax.lax.scan(body, x, stacked, length=n_rep)
    if unit_counts is not None:
        counts = _add_counts(counts, unit_counts.sum(0))
    tail_caches = {}
    for pos, kind in tail:
        x, cache, c = _apply_block(kind, params[pos], x, cfg, positions,
                                   collect_cache)
        counts = _add_counts(counts, c)
        if collect_cache and cache is not None:
            tail_caches[pos] = cache
    return x, (lead_caches, unit_caches, tail_caches), counts


def _encdec_forward(params, cfg, frames, tokens, collect_cache):
    """whisper: frames (B, Se, d) stub embeddings; tokens (B, Sd)."""
    B, Se, _ = frames.shape
    Sd = tokens.shape[1]
    enc_pos = jnp.arange(Se, dtype=jnp.int32)
    dec_pos = jnp.arange(Sd, dtype=jnp.int32)

    h = frames + L.sinusoidal_positions(enc_pos[None], cfg.d_model).astype(frames.dtype)

    def enc_body(carry, p):
        hh = carry
        a = L.rmsnorm(hh, p["ln1"], cfg.norm_eps)
        attn_out, _ = L.attention(p, a, cfg, positions=enc_pos, causal=False,
                                  use_rope=False)
        hh = hh + attn_out
        m = L.rmsnorm(hh, p["ln2"], cfg.norm_eps)
        hh = hh + L.mlp(p, m, cfg.act)
        return hh, None

    if cfg.remat == "full" and not collect_cache:
        enc_body = jax.checkpoint(enc_body)
    h, _ = jax.lax.scan(enc_body, h, params["enc"])
    enc_out = L.rmsnorm(h, params["enc_final_norm"], cfg.norm_eps)

    emb = params["embed"]
    x = jnp.take(emb, tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    x = x + L.sinusoidal_positions(dec_pos[None], cfg.d_model).astype(x.dtype)
    x = logical_shard(x, "act_batch", "act_res_seq", "act_embed")

    def dec_body(carry, p):
        hh = carry
        sp = {k[5:]: v for k, v in p.items() if k.startswith("self_")}
        cp = {k[6:]: v for k, v in p.items() if k.startswith("cross_")}
        a = L.rmsnorm(hh, p["ln1"], cfg.norm_eps)
        self_out, (sk, sv) = L.attention(sp, a, cfg, positions=dec_pos,
                                         causal=True, use_rope=False)
        hh = hh + self_out
        c = L.rmsnorm(hh, p["ln2"], cfg.norm_eps)
        # cross-attention: KV from encoder output
        ek = jnp.einsum("bsd,dh->bsh", enc_out, cp["wk"]).reshape(B, Se, cfg.n_kv_heads, cfg.head_dim)
        ev = jnp.einsum("bsd,dh->bsh", enc_out, cp["wv"]).reshape(B, Se, cfg.n_kv_heads, cfg.head_dim)
        cross_out, _ = L.attention(cp, c, cfg, positions=dec_pos, causal=False,
                                   use_rope=False, kv_override=(ek, ev))
        hh = hh + cross_out
        m = L.rmsnorm(hh, p["ln3"], cfg.norm_eps)
        hh = hh + L.mlp(p, m, cfg.act)
        cache = {
            "k": sk.reshape(B, Sd, -1), "v": sv.reshape(B, Sd, -1),
            "ck": ek.reshape(B, Se, -1), "cv": ev.reshape(B, Se, -1),
        } if collect_cache else None
        return hh, cache

    if cfg.remat == "full" and not collect_cache:
        dec_body = jax.checkpoint(dec_body)
    x, dec_caches = jax.lax.scan(dec_body, x, params["dec"])
    return x, enc_out, dec_caches


def logits_from_hidden(params, cfg, x: jax.Array) -> jax.Array:
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = jnp.einsum("bsd,vd->bsv", x, table).astype(jnp.float32)
    return logical_shard(logits, "act_batch", "act_seq", "act_vocab")


def forward(params: Params, cfg, batch: Dict[str, jax.Array]) -> jax.Array:
    """Training/prefill forward -> logits (B, S, Vpad)."""
    if cfg.family == "encdec":
        x, _, _ = _encdec_forward(params, cfg, batch["frames"], batch["tokens"],
                                  collect_cache=False)
        return logits_from_hidden(params, cfg, x)
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    if cfg.family == "vlm":
        # stub frontend: patch embeddings overwrite the first n_patch positions
        x = jnp.concatenate([batch["patches"].astype(x.dtype),
                             x[:, cfg.n_patch_tokens:]], axis=1)
    x = logical_shard(x, "act_batch", "act_res_seq", "act_embed")
    positions = jnp.arange(S, dtype=jnp.int32)
    x, _, _ = _stack_scan(params, x, cfg, positions, collect_cache=False)
    return logits_from_hidden(params, cfg, x)


def loss_fn(params: Params, cfg, batch: Dict[str, jax.Array]) -> jax.Array:
    logits = forward(params, cfg, batch)
    labels = batch["labels"]
    V = logits.shape[-1]
    # mask vocab padding rows out of the softmax
    vmask = jnp.arange(V) < cfg.vocab
    logits = jnp.where(vmask[None, None], logits, -1e30)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    mask = (labels >= 0).astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# decode path (serving)
# ---------------------------------------------------------------------------


def _decode_cache_decl(cfg, kind: str, batch: int, max_len: int, kv_mode: str,
                       abstract: bool):
    """Cache pytree for one block (no layer-stack dim)."""
    dtype = jnp.dtype(cfg.dtype)
    kvd = cfg.kv_dim
    make = (lambda s, dt: jax.ShapeDtypeStruct(s, dt)) if abstract else (
        lambda s, dt: jnp.zeros(s, dt))
    if kind == "mamba":
        return {
            "state": make((batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                          jnp.float32),
            "conv": make((batch, cfg.d_conv - 1, cfg.d_inner + 2 * cfg.ssm_state),
                         dtype),
        }
    if kind == "local":
        W = cfg.sliding_window
        return {"k": make((batch, W, kvd), dtype), "v": make((batch, W, kvd), dtype)}
    if kind in MLA_KINDS:
        width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        if kv_mode == "paged":
            if cfg.kv_policy in paged_kv.TRUE_ADAPTIVE_KV:
                raise ValueError("MLA's latent pool takes the classic policies "
                                 f"only, not {cfg.kv_policy!r}")
            return paged_kv.latent_pool(batch, cfg.bounded_kv_pages,
                                        cfg.page_size, width, dtype,
                                        abstract=abstract)
        return {"c": make((batch, max_len, width), dtype)}
    # full-attention kinds
    if kv_mode == "paged":
        if cfg.kv_policy in paged_kv.TRUE_ADAPTIVE_KV:
            fn = (paged_kv.abstract_adaptive_pool if abstract
                  else paged_kv.init_adaptive_pool)
            return fn(batch, cfg.bounded_kv_pages, cfg.page_size, kvd, dtype,
                      cfg.kv_policy)
        fn = paged_kv.abstract_pool if abstract else paged_kv.init_pool
        return fn(batch, cfg.bounded_kv_pages, cfg.page_size, kvd, dtype)
    return {"k": make((batch, max_len, kvd), dtype),
            "v": make((batch, max_len, kvd), dtype)}


def decode_caches(cfg, batch: int, max_len: int, *, kv_mode: str = "full",
                  abstract: bool = False):
    """Full decode-cache tree; unit positions carry a leading (n_repeats,)."""
    make_scalar = (lambda: jax.ShapeDtypeStruct((), jnp.int32)) if abstract else (
        lambda: jnp.zeros((), jnp.int32))

    def add_stack(decl, n):
        return jax.tree.map(
            lambda x: (jax.ShapeDtypeStruct((n,) + x.shape, x.dtype)
                       if abstract else jnp.zeros((n,) + x.shape, x.dtype)),
            decl)

    blocks = {}
    if cfg.family == "encdec":
        enc_len = cfg.cross_kv_len
        dec = {
            "k": (batch, max_len, cfg.kv_dim), "v": (batch, max_len, cfg.kv_dim),
            "ck": (batch, enc_len, cfg.kv_dim), "cv": (batch, enc_len, cfg.kv_dim),
        }
        dtype = jnp.dtype(cfg.dtype)
        mk = (lambda s: jax.ShapeDtypeStruct(s, dtype)) if abstract else (
            lambda s: jnp.zeros(s, dtype))
        blocks["dec"] = {k: (jax.ShapeDtypeStruct((cfg.dec_layers,) + s, dtype)
                             if abstract else jnp.zeros((cfg.dec_layers,) + s, dtype))
                         for k, s in dec.items()}
        return {"pos": make_scalar(), "blocks": blocks}

    lead, unit, n_rep, tail = scan_plan(cfg)
    for pos, kind in unit:
        blocks[pos] = add_stack(
            _decode_cache_decl(cfg, kind, batch, max_len, kv_mode, abstract), n_rep)
    for pos, kind in lead + tail:
        blocks[pos] = _decode_cache_decl(cfg, kind, batch, max_len, kv_mode, abstract)
    out = {"pos": make_scalar(), "blocks": blocks}
    if has_expert_counts(cfg):
        out["moe"] = (jax.ShapeDtypeStruct((2,), jnp.int32) if abstract
                      else jnp.zeros((2,), jnp.int32))
    return out


def _decode_block(kind: str, p: Params, x: jax.Array, cfg, cache, pos,
                  win_positions, kv_mode: str, fused: bool = False,
                  mesh=None):
    """One block of one decode step: (x, new cache, expert counts or
    None)."""
    B = x.shape[0]
    if kind == "mamba":
        h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
        y, st, cv = L.mamba2_decode_step(p, h, cfg, state=cache["state"],
                                         conv_state=cache["conv"])
        return x + y, {"state": st, "conv": cv}, None

    h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
    if kind in MLA_KINDS:
        if fused:
            raise ValueError("the fused policy-attention kernel serves K/V "
                             "pools only; MLA's latent pool runs unfused")
        return _decode_mla_block(kind, p, x, h, cfg, cache, pos, kv_mode)
    nk, nv = L.decode_kv_row(p, h, cfg, position=pos)
    if kind == "local":
        k, v = paged_kv.ring_insert(cache["k"], cache["v"], nk, nv, pos)
        kv_pos = jnp.broadcast_to(win_positions[None], (B, win_positions.shape[0]))
        attn_out, _ = L.decode_attend(p, h, cfg, position=pos, k_cache=k,
                                      v_cache=v, kv_positions=kv_pos)
        new_cache = {"k": k, "v": v}
    elif kv_mode == "paged":
        adaptive = cfg.kv_policy in paged_kv.TRUE_ADAPTIVE_KV
        if fused:
            # one Pallas launch: victim selection + KV gather + attention +
            # policy-plane update (kernels/policy_attn.py, DESIGN.md §10);
            # decisions bit-identical to the unfused chain below
            q = L.decode_q(p, h, cfg, position=pos)
            if adaptive:
                core = paged_kv.adaptive_core(cfg.kv_policy, B,
                                              cfg.bounded_kv_pages)
                out, _, new_cache = paged_kv.fused_adaptive_decode_step(
                    cache, q, nk[:, 0], nv[:, 0], pos, cfg.page_size, core,
                    mesh=mesh)
            else:
                out, _, new_cache = paged_kv.fused_decode_step(
                    cache, q, nk[:, 0], nv[:, 0], pos, cfg.page_size,
                    cfg.kv_policy, mesh=mesh)
            attn_out = L.decode_project_out(p, out.astype(x.dtype), cfg)
        else:
            if adaptive:
                core = paged_kv.adaptive_core(cfg.kv_policy, B,
                                              cfg.bounded_kv_pages)
                apool = paged_kv.adaptive_insert_token(
                    cache, nk[:, 0], nv[:, 0], pos, cfg.page_size, core)
                pool = apool.pool
            else:
                pool = paged_kv.insert_token(cache, nk[:, 0], nv[:, 0], pos,
                                             cfg.page_size,
                                             policy=cfg.kv_policy)
            Ppool, page = pool.f.shape[1], cfg.page_size
            kflat = pool.k.reshape(B, Ppool * page, -1)
            vflat = pool.v.reshape(B, Ppool * page, -1)
            kv_pos = paged_kv.kv_positions(pool, pos, page)
            attn_out, mass = L.decode_attend(p, h, cfg, position=pos,
                                             k_cache=kflat, v_cache=vflat,
                                             kv_positions=kv_pos)
            if adaptive:
                new_cache = paged_kv.adaptive_score_update(apool, mass, page,
                                                           core)
            else:
                new_cache = paged_kv.score_update(pool, mass, page)
    else:  # full
        k, v = paged_kv.full_cache_insert(cache["k"], cache["v"], nk, nv, pos)
        T = k.shape[1]
        t = jnp.arange(T, dtype=jnp.int32)
        kv_pos = jnp.broadcast_to(jnp.where(t <= pos, t, -1)[None], (B, T))
        attn_out, _ = L.decode_attend(p, h, cfg, position=pos, k_cache=k,
                                      v_cache=v, kv_positions=kv_pos)
        new_cache = {"k": k, "v": v}
    x = x + attn_out
    h2 = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
    ff = L.moe(p, h2, cfg)[0] if kind == "moe" else L.mlp(p, h2, cfg.act)
    return x + ff, new_cache, None


def _decode_mla_block(kind: str, p: Params, x, h, cfg, cache, pos,
                      kv_mode: str):
    """An MLA block's decode step: the token's latent row into the cache
    (the latent pool, evicting by ``cfg.kv_policy``, or the full cache),
    absorbed attention over the rows, then the MLP or the expert layer."""
    B = x.shape[0]
    row = L.mla_latent(p, h, cfg, jnp.full((B, 1), pos, jnp.int32))[:, 0]
    if kv_mode == "paged":
        page = cfg.page_size
        pool = paged_kv.insert_latent(cache, row, pos, page,
                                      policy=cfg.kv_policy)
        rows = pool.c.reshape(B, -1, pool.c.shape[-1])
        kv_pos = paged_kv.kv_positions(pool, pos, page)
        attn_out, mass = L.mla_decode_attend(p, h, cfg, position=pos,
                                             cache=rows, kv_positions=kv_pos)
        new_cache = paged_kv.score_update(pool, mass, page)
    else:
        c = jax.lax.dynamic_update_slice_in_dim(cache["c"], row[:, None], pos,
                                                axis=1)
        t = jnp.arange(c.shape[1], dtype=jnp.int32)
        kv_pos = jnp.broadcast_to(jnp.where(t <= pos, t, -1)[None], (B, c.shape[1]))
        attn_out, _ = L.mla_decode_attend(p, h, cfg, position=pos, cache=c,
                                          kv_positions=kv_pos)
        new_cache = {"c": c}
    x = x + attn_out
    h2 = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
    if kind == "mla_moe":
        ff, counts = L.moe(p, h2, cfg)
        return x + ff, new_cache, counts
    return x + L.mlp(p, h2, cfg.act), new_cache, None


def _encdec_decode(params, cfg, token, caches):
    pos = caches["pos"]
    B = token.shape[0]
    x = jnp.take(params["embed"], token, axis=0).astype(jnp.dtype(cfg.dtype))
    x = x + L.sinusoidal_positions(
        jnp.full((B, 1), pos, jnp.int32), cfg.d_model).astype(x.dtype)
    dc = caches["blocks"]["dec"]
    Se = dc["ck"].shape[2]
    enc_pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32)[None], (B, Se))

    def body(carry, xs):
        h = carry
        p, c = xs
        sp = {k[5:]: v for k, v in p.items() if k.startswith("self_")}
        cp = {k[6:]: v for k, v in p.items() if k.startswith("cross_")}
        a = L.rmsnorm(h, p["ln1"], cfg.norm_eps)
        nk, nv = L.decode_kv_row(sp, a, cfg, position=pos, use_rope=False)
        k, v = paged_kv.full_cache_insert(c["k"], c["v"], nk, nv, pos)
        T = k.shape[1]
        t = jnp.arange(T, dtype=jnp.int32)
        kv_pos = jnp.broadcast_to(jnp.where(t <= pos, t, -1)[None], (B, T))
        self_out, _ = L.decode_attend(sp, a, cfg, position=pos, k_cache=k,
                                      v_cache=v, kv_positions=kv_pos,
                                      use_rope=False)
        h = h + self_out
        cc = L.rmsnorm(h, p["ln2"], cfg.norm_eps)
        cross_out, _ = L.decode_attend(cp, cc, cfg, position=pos,
                                       k_cache=c["ck"], v_cache=c["cv"],
                                       kv_positions=enc_pos, use_rope=False)
        h = h + cross_out
        m = L.rmsnorm(h, p["ln3"], cfg.norm_eps)
        h = h + L.mlp(p, m, cfg.act)
        return h, {"k": k, "v": v, "ck": c["ck"], "cv": c["cv"]}

    x, new_dec = jax.lax.scan(body, x, (params["dec"], dc))
    logits = logits_from_hidden(params, cfg, x)
    return logits, {"pos": pos + 1, "blocks": {"dec": new_dec}}


def decode_step(params: Params, cfg, token: jax.Array, caches,
                *, kv_mode: str = "full", fused: bool = False, mesh=None):
    """One serving step: token (B, 1) int32 -> (logits (B, 1, Vpad), caches).

    ``fused=True`` routes paged-KV attention blocks through the fused
    policy-attention Pallas kernels (victim selection + gather + score update
    in one launch; interpret-mode fallback on CPU) — decisions bit-identical
    to the unfused path.  ``mesh`` launches the fused kernel shard-locally
    under ``shard_map`` (PR 7 rows-mesh contract)."""
    if cfg.family == "encdec":
        return _encdec_decode(params, cfg, token, caches)
    pos = caches["pos"]
    x = jnp.take(params["embed"], token, axis=0).astype(jnp.dtype(cfg.dtype))
    x = logical_shard(x, "act_batch", "act_res_seq", "act_embed")
    win_positions = (paged_kv.ring_positions(pos, cfg.sliding_window)
                     if cfg.sliding_window else None)

    lead, unit, n_rep, tail = scan_plan(cfg)
    stacked_params = {p: params[p] for p, k in unit if k != "shared_attn"}
    stacked_caches = {p: caches["blocks"][p] for p, k in unit}
    new_blocks, counts = {}, None
    for pname, kind in lead:
        x, new_blocks[pname], c = _decode_block(
            kind, params[pname], x, cfg, caches["blocks"][pname], pos,
            win_positions, kv_mode, fused, mesh)
        counts = _add_counts(counts, c)

    def body(carry, xs):
        h = carry
        pslices, cslices = xs
        new_caches, unit_counts = {}, None
        for pname, kind in unit:
            prm = params["shared_attn"] if kind == "shared_attn" else pslices[pname]
            h, new_caches[pname], c = _decode_block(
                kind, prm, h, cfg, cslices[pname], pos, win_positions,
                kv_mode, fused, mesh)
            unit_counts = _add_counts(unit_counts, c)
        return h, (new_caches, unit_counts)

    x, (new_stacked, unit_counts) = jax.lax.scan(
        body, x, (stacked_params, stacked_caches))
    if unit_counts is not None:
        counts = _add_counts(counts, unit_counts.sum(0))
    new_blocks.update(new_stacked)
    for pname, kind in tail:
        x, new_blocks[pname], c = _decode_block(
            kind, params[pname], x, cfg, caches["blocks"][pname], pos,
            win_positions, kv_mode, fused, mesh)
        counts = _add_counts(counts, c)
    logits = logits_from_hidden(params, cfg, x)
    out = {"pos": pos + 1, "blocks": new_blocks}
    if counts is not None:
        out["moe"] = counts
    return logits, out


# ---------------------------------------------------------------------------
# prefill -> decode cache handoff
# ---------------------------------------------------------------------------


def prefill(params: Params, cfg, batch: Dict[str, jax.Array], max_len: int,
            *, kv_mode: str = "full"):
    """Run the full prompt, return (logits at the last position (B, 1,
    Vpad), decode caches positioned at S) — the head runs on the one
    position decoding continues from.  For kv_mode="paged" the prompt must
    be page-aligned (engine enforces)."""
    if cfg.family == "encdec":
        x, enc_out, dec_caches = _encdec_forward(
            params, cfg, batch["frames"], batch["tokens"], collect_cache=True)
        logits = logits_from_hidden(params, cfg, x[:, -1:])
        B, Sd = batch["tokens"].shape
        pad = max_len - Sd
        new = {
            "k": jnp.pad(dec_caches["k"], ((0, 0), (0, 0), (0, pad), (0, 0))),
            "v": jnp.pad(dec_caches["v"], ((0, 0), (0, 0), (0, pad), (0, 0))),
            "ck": dec_caches["ck"], "cv": dec_caches["cv"],
        }
        return logits, {"pos": jnp.asarray(Sd, jnp.int32), "blocks": {"dec": new}}

    tokens = batch["tokens"]
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    if cfg.family == "vlm":
        x = jnp.concatenate([batch["patches"].astype(x.dtype),
                             x[:, cfg.n_patch_tokens:]], axis=1)
    x = logical_shard(x, "act_batch", "act_res_seq", "act_embed")
    positions = jnp.arange(S, dtype=jnp.int32)
    x, (lead_caches, unit_caches, tail_caches), counts = _stack_scan(
        params, x, cfg, positions, collect_cache=True)
    logits = logits_from_hidden(params, cfg, x[:, -1:])

    lead, unit, n_rep, tail = scan_plan(cfg)
    kinds = dict(lead + unit + tail)

    def convert(pos_name, cache, stacked):
        kind = kinds[pos_name]
        if kind == "mamba":
            return cache  # {"state", "conv"} already decode layout
        seq_ax = 2 if stacked else 1
        if kind in MLA_KINDS:
            if kv_mode == "paged":
                return pool_from_prefill(cfg, {"c": cache["c"]}, S, stacked)
            cfgpad = [(0, 0)] * cache["c"].ndim
            cfgpad[seq_ax] = (0, max_len - S)
            return {"c": jnp.pad(cache["c"], cfgpad)}
        k, v = cache["k"], cache["v"]
        if kind == "local":
            W = cfg.sliding_window
            start = max(S - W, 0)
            ksl = jax.lax.slice_in_dim(k, start, S, axis=seq_ax)
            vsl = jax.lax.slice_in_dim(v, start, S, axis=seq_ax)
            # place rows at their ring slots (contiguous & unique since W rows)
            slots = (jnp.arange(start, S) % W).astype(jnp.int32)
            kr = jnp.zeros(k.shape[:seq_ax] + (W,) + k.shape[seq_ax + 1:], k.dtype)
            vr = jnp.zeros_like(kr)
            if stacked:
                kr, vr = kr.at[:, :, slots].set(ksl), vr.at[:, :, slots].set(vsl)
            else:
                kr, vr = kr.at[:, slots].set(ksl), vr.at[:, slots].set(vsl)
            return {"k": kr, "v": vr}
        if kv_mode == "paged":
            return pool_from_prefill(cfg, {"k": k, "v": v}, S, stacked)
        pad = max_len - S
        cfgpad = [(0, 0)] * k.ndim
        cfgpad[seq_ax] = (0, pad)
        return {"k": jnp.pad(k, cfgpad), "v": jnp.pad(v, cfgpad)}

    blocks = {p: convert(p, c, True) for p, c in unit_caches.items()}
    blocks.update({p: convert(p, c, False)
                   for p, c in {**lead_caches, **tail_caches}.items()})
    out = {"pos": jnp.asarray(S, jnp.int32), "blocks": blocks}
    if counts is not None:
        out["moe"] = counts
    return logits, out


def pool_from_prefill(cfg, planes: Dict[str, jax.Array], S: int,
                      stacked: bool):
    """Seed an AWRP pool from prefill rows, ``planes`` {"k", "v"} (a K/V
    pool) or {"c"} (a latent pool), each (B, S, width): the last `pages`
    page-aligned pages are resident with F=1 and R = page creation order
    (documented seeding — the policy then evolves scores during decode)."""
    page, P = cfg.page_size, cfg.bounded_kv_pages
    n_have = S // page  # prompt must be page-aligned (asserted by engine)
    n_res = min(n_have, P)
    start_tok = (n_have - n_res) * page

    def one(pl):  # {name: (B, S, width)}
        B = next(iter(pl.values())).shape[0]

        def paged(x):
            sl = x[:, start_tok : start_tok + n_res * page].reshape(
                B, n_res, page, x.shape[-1])
            return jnp.zeros((B, P, page, x.shape[-1]), x.dtype).at[:, :n_res].set(sl)

        order = jnp.arange(P, dtype=jnp.int32)
        f = jnp.where(order < n_res, 1, 0).astype(jnp.int32)
        r = jnp.where(order < n_res, order + 1, 0).astype(jnp.int32)
        starts = jnp.where(order < n_res, start_tok + order * page, -1).astype(jnp.int32)
        meta = dict(
            f=jnp.broadcast_to(f, (B, P)),
            r=jnp.broadcast_to(r, (B, P)),
            page_start=jnp.broadcast_to(starts, (B, P)),
            clock=jnp.full((B,), n_res, jnp.int32),
            open_slot=jnp.full((B,), max(n_res - 1, 0), jnp.int32),
        )
        if "c" in pl:
            return paged_kv.LatentPagedPool(c=paged(pl["c"]), **meta)
        pool = paged_kv.PagedPool(k=paged(pl["k"]), v=paged(pl["v"]), **meta)
        if cfg.kv_policy in paged_kv.TRUE_ADAPTIVE_KV:
            return paged_kv.AdaptivePagedPool(
                pool=pool,
                policy=paged_kv.seed_adaptive_state(
                    B, P, start_tok // page, n_res),
            )
        return pool

    return jax.vmap(one)(planes) if stacked else one(planes)
