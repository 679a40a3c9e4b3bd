"""Plain reference of the DeepSeek-V3 block (Moonlight-16B-A3B) on one
chip's share of the experts, served through a bounded paged pool of
latent rows with AWRP eviction, teacher-forced over a prompt and the
tokens a server returned for it.

The model: token embedding; per layer RMSNorm, multi-head latent
attention, a residual, RMSNorm, a SwiGLU (the leading dense layers) or the
expert layer, a residual; a final RMSNorm and an untied head.  A norm's
gain is ``1 + s`` for the stored scale ``s``.

MLA, naive: ``q = x W_q`` is ``[q_nope | q_pe]`` a head; ``[c | k_pe] =
x W_kva``, ``c <- RMSNorm_kv(c)``; ``q_pe`` and ``k_pe`` (one a token,
shared by the heads) rotated at their positions, half-split pairs (the
published adjacent pairs under a fixed permutation of the rope columns);
a head's ``[k_nope | v] = c W_kvb``; scores ``(q_nope.k_nope +
q_pe.k_pe) / sqrt(nope + rope)``; ``out = concat_h softmax(s) v W_o``.

Expert layer: router logits, ``sigma = sigmoid``, the top-k of
``sigma + bias`` (the bias picks, it does not weigh), gates ``sigma_k /
sum_sel sigma * routed_scaling``; every expert held here runs on every
token and counts where the token chose it; experts held elsewhere add
nothing; the shared experts are a SwiGLU on every token.

The pool is ``bench/refs/decoder.py``'s, page for page and eviction for
eviction, over the row this model caches: the latent ``[c | k_pe]``
(``r + rope`` wide), kept as two halves in the two planes of that
reference's loop.  Attention mass is summed over every head.

``precision="float32"`` computes every matmul in float32 at the
``highest`` setting, each layer's bf16 weights cast to float32 inside
that layer's jitted call so that the whole model never is.
``precision="float8"`` is the control: every matmul operand rounded to
float8 e4m3 with a per-tensor scale.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.refs.decoder import Decoder, _fp8


class MlaMoe(Decoder):
    """The reference for one model (``m``: ``bench.moe_sizes.sizes``) and
    one set of weights (the program's parameter tree: the leading dense
    layer ``l0``, the expert layers stacked under ``u0``)."""

    def __init__(self, m: dict, params, precision: str = "float32"):
        width = m["kv_lora_rank"] + m["rope"]
        # decoder.py's loop keeps per-layer K and V planes of
        # (n_kv_heads, head_dim); here they hold the latent row's halves
        self.m = dict(m, n_kv_heads=1, head_dim=width // 2)
        self.q = _fp8 if precision == "float8" else (lambda x: x)
        self.prec = jax.lax.Precision.HIGHEST
        self.params = params
        self.embed = params["embed"]
        self.final_norm = jnp.asarray(params["final_norm"], jnp.float32)
        self.layers = ([(f"l{i}", None) for i in range(m["n_dense"])]
                       + [("u0", i) for i in range(m["n_layers"]
                                                  - m["n_dense"])])
        self._jit_layer = jax.jit(self._layer_fn, static_argnums=(0,))
        self._jit_head = jax.jit(self._head_fn)

    def _layer(self, which, h, pos, K, V, kv_ok):
        name, i = which
        return self._jit_layer(name, self.params[name], i, h, pos, K, V,
                               kv_ok)

    def _head(self, _embed, final_norm, h):
        return self._jit_head(self.params["unembed"], final_norm, h)

    # -- the model ---------------------------------------------------------
    def _swiglu(self, x, g, u, d):
        return self._mm(jax.nn.silu(self._mm(x, g)) * self._mm(x, u), d)

    def _experts(self, lp, x):
        m = self.m
        scores = jax.nn.sigmoid(self._mm(x, lp["w_router"]))
        _, idx = jax.lax.top_k(scores + lp["router_bias"], m["top_k"])
        g = jnp.take_along_axis(scores, idx, axis=-1)
        g = g / g.sum(-1, keepdims=True) * m["routed_scaling"]
        out = self._swiglu(x, lp["shared_gate"], lp["shared_up"],
                           lp["shared_down"])
        for j in range(m["experts_held"]):
            w = jnp.where(idx == m["expert_offset"] + j, g, 0.0).sum(-1)
            out = out + w[..., None] * self._swiglu(
                x, lp["w_gate"][j], lp["w_up"][j], lp["w_down"][j])
        return out

    def _layer_fn(self, name, stacked, i, h, pos, K, V, kv_ok):
        """One layer over queries ``h`` (B, n, d) at positions ``pos``
        (n,), attending to the cached latent rows (the halves ``K``, ``V``
        (B, T, 1, width/2)) after writing the queries' own; ``kv_ok(B, n,
        T)`` says which keys each query sees.  Returns (h, K, V, mass (B,
        n, T))."""
        m = self.m
        lp = stacked if i is None else jax.tree.map(lambda a: a[i], stacked)
        lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
        h = h.astype(jnp.float32)
        B, n, _ = h.shape
        H, r = m["n_heads"], m["kv_lora_rank"]
        dn, dr, dv = m["nope"], m["rope"], m["v"]
        x = self._norm(h, lp["ln1"])
        q = self._mm(x, lp["wq"]).reshape(B, n, H, dn + dr)
        q_pe = self._rope(q[..., dn:], pos)
        ckv = self._mm(x, lp["w_kva"])
        c = self._norm(ckv[..., :r], lp["kv_norm"])
        k_pe = self._rope(ckv[..., None, r:], pos)[:, :, 0]
        row = jnp.concatenate([c, k_pe], -1)[:, :, None]
        half = row.shape[-1] // 2
        K = jax.lax.dynamic_update_slice_in_dim(K, row[..., :half], pos[0], 1)
        V = jax.lax.dynamic_update_slice_in_dim(V, row[..., half:], pos[0], 1)
        rows = jnp.concatenate([K, V], -1)[:, :, 0]  # (B, T, r + rope)
        T = rows.shape[1]
        kv = self._mm(rows[..., :r], lp["w_kvb"]).reshape(B, T, H, dn + dv)
        s = (jnp.einsum("bnhe,bthe->bhnt", self.q(q[..., :dn]),
                        self.q(kv[..., :dn]), precision=self.prec)
             + jnp.einsum("bnhe,bte->bhnt", self.q(q_pe),
                          self.q(rows[..., r:]), precision=self.prec))
        s = s / math.sqrt(dn + dr)
        s = jnp.where(kv_ok[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhnt,bthe->bnhe", self.q(p), self.q(kv[..., dn:]),
                       precision=self.prec).reshape(B, n, H * dv)
        h = h + self._mm(o, lp["wo"])
        x = self._norm(h, lp["ln2"])
        if i is None:  # a leading dense layer
            h = h + self._swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
        else:
            h = h + self._experts(lp, x)
        return h, K, V, p.sum(axis=1)

    def _head_fn(self, unembed, final_norm, h):
        x = self._norm(h.astype(jnp.float32), final_norm)
        return self._mm(x, unembed.astype(jnp.float32).T)[..., : self.m["vocab"]]
