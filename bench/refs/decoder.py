"""Plain reference of a llama-style decoder served through a bounded paged
KV pool with AWRP eviction, teacher-forced over a prompt and the tokens a
server returned for it.

The model (SmolLM2 / llama): token embedding; per layer RMSNorm, grouped-
query attention with rotary embeddings (half-split pairs), a residual,
RMSNorm, a SwiGLU MLP, a residual; a final RMSNorm and the tied embedding
as the output head.  A norm's gain is ``1 + s`` for the stored scale ``s``.

The pool (one per layer and sequence, ``pages`` pages of ``page`` tokens):
the prompt's last ``pages`` pages are resident after prefill, slot i
holding the i-th of them with F = 1, R = i + 1, clock N = resident pages
and the last one open.  Decoding the token at position t: at a page
boundary a new page is allocated in the first free slot, else in the
AWRP victim's (the first slot, other than the open one, of least
W = F / max(N - R, 1) in float32), with F = 1, R = N; attention sees
every resident page's tokens up to t; then every resident page whose
attention mass, summed over its rows and all query heads, is at least
1 / (resident pages) gets F += 1 and R = N + 1, and N += 1.

``precision="float32"`` computes every matmul in float32 at the
``highest`` setting.  ``precision="float8"`` is the control: every matmul
operand rounded to float8 e4m3 with a per-tensor scale.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Q_CHUNK = 512  # prefill queries per attention block


def _fp8(x):
    """Round to float8 e4m3 with a per-tensor scale (448 = e4m3's max)."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


class Decoder:
    """The reference for one model (``m``: the configuration's sizes) and
    one set of weights (the program's parameter tree, any dtype)."""

    def __init__(self, m: dict, params, precision: str = "float32"):
        self.m = m
        self.q = _fp8 if precision == "float8" else (lambda x: x)
        self.prec = jax.lax.Precision.HIGHEST
        f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
        self.embed = f32(params["embed"])
        self.final_norm = f32(params["final_norm"])
        self.layers = [jax.tree.map(lambda x: x[i], params["u0"])
                       for i in range(m["n_layers"])]
        self.layers = [jax.tree.map(f32, lp) for lp in self.layers]
        self._layer = jax.jit(self._layer_fn)
        self._head = jax.jit(self._head_fn)

    # -- the model ---------------------------------------------------------
    def _mm(self, x, w):
        return jnp.matmul(self.q(x), self.q(w), precision=self.prec)

    def _norm(self, x, s):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + self.m["norm_eps"]) * (1.0 + s)

    def _rope(self, x, pos):
        half = x.shape[-1] // 2
        freq = self.m["rope_theta"] ** (
            -jnp.arange(half, dtype=jnp.float32) / half)
        ang = pos[..., None].astype(jnp.float32) * freq
        cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def _layer_fn(self, lp, h, pos, K, V, kv_ok):
        """One layer over queries ``h`` (B, n, d) at positions ``pos``
        (n,), attending to the cached ``K``/``V`` (B, T, KVH, hd) after
        writing the queries' own rows; ``kv_ok(B, n, T)`` says which keys
        each query sees.  Returns (h, K, V, mass (B, n, T))."""
        m = self.m
        B, n, _ = h.shape
        H, KVH, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
        x = self._norm(h, lp["ln1"])
        q = self._rope(self._mm(x, lp["wq"]).reshape(B, n, H, hd), pos)
        k = self._rope(self._mm(x, lp["wk"]).reshape(B, n, KVH, hd), pos)
        v = self._mm(x, lp["wv"]).reshape(B, n, KVH, hd)
        K = jax.lax.dynamic_update_slice_in_dim(K, k, pos[0], axis=1)
        V = jax.lax.dynamic_update_slice_in_dim(V, v, pos[0], axis=1)
        qg = self.q(q).reshape(B, n, KVH, H // KVH, hd)
        s = jnp.einsum("bnkgh,btkh->bkgnt", qg, self.q(K),
                       precision=self.prec) / math.sqrt(hd)
        s = jnp.where(kv_ok[:, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgnt,btkh->bnkgh", self.q(p), self.q(V),
                       precision=self.prec).reshape(B, n, H * hd)
        h = h + self._mm(o, lp["wo"])
        x = self._norm(h, lp["ln2"])
        g = jax.nn.silu(self._mm(x, lp["w_gate"])) * self._mm(x, lp["w_up"])
        h = h + self._mm(g, lp["w_down"])
        return h, K, V, p.sum(axis=(1, 2))

    def _head_fn(self, embed, final_norm, h):
        x = self._norm(h, final_norm)
        return self._mm(x, embed.T)[..., : self.m["vocab"]]

    # -- prompt + served tokens through the bounded pool --------------------
    def logits(self, prompts: np.ndarray, served: np.ndarray,
               pages: int, page: int):
        """Logits (B, G, vocab) of every served position: the prefill's
        last position, then each decode step fed the served token before
        it.  Also returns the pool's eviction count."""
        m = self.m
        B, S = prompts.shape
        G = served.shape[1]
        T = S + G - 1 + page  # room for a whole last chunk
        KVH, hd = m["n_kv_heads"], m["head_dim"]
        nl = m["n_layers"]
        Ks = [jnp.zeros((B, T, KVH, hd), jnp.float32) for _ in range(nl)]
        Vs = [jnp.zeros((B, T, KVH, hd), jnp.float32) for _ in range(nl)]
        out = []

        # prefill, in blocks of queries
        h = self.embed[jnp.asarray(prompts)]
        tpos = jnp.arange(T)
        for li, lp in enumerate(self.layers):
            parts = []
            K, V = Ks[li], Vs[li]
            for a in range(0, S, Q_CHUNK):
                pos = jnp.arange(a, min(a + Q_CHUNK, S))
                ok = jnp.broadcast_to(tpos[None, None] <= pos[None, :, None],
                                      (B, len(pos), T))
                hp, K, V, _ = self._layer(lp, h[:, a:a + Q_CHUNK], pos, K, V,
                                          ok)
                parts.append(hp)
            h = jnp.concatenate(parts, axis=1)
            Ks[li], Vs[li] = K, V
        out.append(np.asarray(self._head(self.embed, self.final_norm,
                                         h[:, -1:])))
        del h

        # the pools, one per layer and sequence
        n_have = S // page
        n_res = min(n_have, pages)
        first = (n_have - n_res) * page
        slot = np.arange(pages)
        f = np.where(slot < n_res, 1, 0)[None, None].repeat(B, 1).repeat(nl, 0)
        r = np.where(slot < n_res, slot + 1, 0)[None, None].repeat(B, 1) \
            .repeat(nl, 0)
        start = np.where(slot < n_res, first + slot * page, -1)[None, None] \
            .repeat(B, 1).repeat(nl, 0)
        clock = np.full((nl, B), n_res)
        open_ = np.full((nl, B), max(n_res - 1, 0))
        evictions = 0

        t = S
        fed = np.asarray(served[:, :-1])  # the tokens decode steps are fed
        while t < S + G - 1:
            n = min(page - t % page, S + G - 1 - t)
            if t % page == 0:  # allocate in every pool
                for li in range(nl):
                    for b in range(B):
                        free = np.flatnonzero(start[li, b] < 0)
                        if free.size:
                            s = int(free[0])
                        else:
                            cand = start[li, b] >= 0
                            cand[open_[li, b]] = False
                            w = (f[li, b].astype(np.float32) / np.maximum(
                                clock[li, b] - r[li, b], 1).astype(np.float32))
                            s = int(np.argmin(np.where(cand, w, np.inf)))
                            evictions += 1
                        f[li, b, s], r[li, b, s] = 1, clock[li, b]
                        start[li, b, s], open_[li, b] = t, s
            pos = jnp.arange(t, t + page)  # a whole chunk; extra rows unused
            toks = np.zeros((B, page), np.int64)
            toks[:, :n] = fed[:, t - S:t - S + n]
            h = self.embed[jnp.asarray(toks)]
            for li, lp in enumerate(self.layers):
                page_of_key = np.arange(T) // page
                resident = np.zeros((B, T), bool)
                for b in range(B):
                    st = start[li, b][start[li, b] >= 0]
                    resident[b] = np.isin(page_of_key * page, st)
                ok = (jnp.asarray(resident)[:, None, :]
                      & (tpos[None, None] <= pos[None, :, None]))
                h, Ks[li], Vs[li], mass = self._layer(lp, h, pos, Ks[li],
                                                      Vs[li], ok)
                mass = np.asarray(mass)[:, :n]  # (B, n, T)
                for b in range(B):
                    res = start[li, b] >= 0
                    idx = np.where(res, start[li, b] // page, 0)
                    by_page = np.add.reduceat(
                        mass[b], np.arange(0, T, page), axis=-1)  # (n, T/page)
                    tau = np.float32(1.0) / np.float32(res.sum())
                    for j in range(n):
                        hit = res & (by_page[j, idx] >= tau)
                        clock[li, b] += 1
                        f[li, b] = np.where(hit, f[li, b] + 1, f[li, b])
                        r[li, b] = np.where(hit, clock[li, b], r[li, b])
            out.append(np.asarray(self._head(self.embed, self.final_norm,
                                             h))[:, :n])
            t += n
        return np.concatenate(out, axis=1), evictions


def widest_gap(ref_logits: np.ndarray, tokens: np.ndarray) -> float:
    """The largest amount by which a token's logit lies below the best
    logit at its position, over every position of ``tokens`` (B, G)."""
    best = ref_logits.max(-1)
    got = np.take_along_axis(ref_logits, tokens[..., None], -1)[..., 0]
    return float((best - got).max())
