"""Bounded-KV serving quality: AWRP vs LRU/FIFO/LFU page eviction vs the
exact full cache.

Protocol: smoke gemma3 (local:global pattern — the arch whose long-context
mode the paper's technique enables), prefill a prompt, decode N steps twice:
once with the full cache (ground truth logits) and once with each bounded
pool; report mean KL(full || bounded) over decode steps and the greedy-token
agreement rate.  Lower KL / higher agreement = the policy kept the pages that
mattered."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import load_smoke_config
from repro.models import model as M

POLICIES = ("awrp", "lru", "fifo", "lfu")


def _kl(p_logits, q_logits, vocab):
    p = jax.nn.log_softmax(p_logits[..., :vocab].astype(jnp.float32))
    q = jax.nn.log_softmax(q_logits[..., :vocab].astype(jnp.float32))
    return float(jnp.sum(jnp.exp(p) * (p - q), axis=-1).mean())


def run(steps: int = 48, pages: int = 4, page_size: int = 8):
    """Serve the same decode under full KV vs each bounded-KV policy and
    report the logits KL vs the full-cache reference."""
    base = load_smoke_config("gemma3_27b")
    base = dataclasses.replace(base, dtype="float32", param_dtype="float32",
                               bounded_kv_pages=pages, page_size=page_size)
    params = M.init_params(base, jax.random.PRNGKey(0))
    B, S = 2, 32  # 4 pages of prompt; pool holds 4 -> evictions during decode
    key = jax.random.PRNGKey(42)
    tokens = jax.random.randint(key, (B, S), 0, base.vocab)

    # ground truth: full cache
    _, caches_full = M.prefill(params, base, {"tokens": tokens},
                               max_len=S + steps + 8, kv_mode="full")
    full_step = jax.jit(lambda t, c: M.decode_step(params, base, t, c,
                                                   kv_mode="full"))
    results = {}
    print(f"== bounded-KV quality (pool={pages}x{page_size} tokens, "
          f"prompt={S}, {steps} decode steps) ==")
    for pol in POLICIES:
        cfg = dataclasses.replace(base, kv_policy=pol)
        _, caches = M.prefill(params, cfg, {"tokens": tokens},
                              max_len=S + steps + 8, kv_mode="paged")
        step = jax.jit(lambda t, c, _cfg=cfg: M.decode_step(params, _cfg, t, c,
                                                            kv_mode="paged"))
        tok_f = tok_b = tokens[:, -1:]
        cf = jax.tree.map(lambda x: x, caches_full)
        kls, agree = [], []
        for _ in range(steps):
            lf, cf = full_step(tok_f, cf)
            lb, caches = step(tok_b, caches)
            kls.append(_kl(lf, lb, cfg.vocab))
            nf = jnp.argmax(lf[:, 0, : cfg.vocab], -1)
            nb = jnp.argmax(lb[:, 0, : cfg.vocab], -1)
            agree.append(float((nf == nb).mean()))
            tok_f, tok_b = nf[:, None].astype(jnp.int32), nf[:, None].astype(jnp.int32)
            # teacher-forced with the full-cache token so KL stays comparable
        results[pol] = (float(np.mean(kls)), float(np.mean(agree)))
        print(f"  {pol:>5}: KL(full||bounded)={results[pol][0]:.4f} "
              f"greedy-agreement={results[pol][1]*100:.1f}%")
    return results


if __name__ == "__main__":
    run()
