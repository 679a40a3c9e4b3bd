"""Paged-KV serving ablation: the paper's policy vs every baseline on
IDENTICAL decode traces — awrp/lru/fifo/lfu exactly, arc/car as the classic
pool's stateless two-segment approximations, and the TRUE adaptive arc/car
(ghost directory + self-tuning p, carried as AdaptiveState planes through
the unified policy core — DESIGN.md §7).

Methodology: a synthetic decode generates an *oracle* attention-mass
distribution over all pages written so far (strong locality on the open
page + a zipf-ish hot page set that shifts phase mid-trace — the regime
where frequency AND recency both matter, AWRP's design point).  Every
policy serves the same stream from the same bounded pool; pages it evicted
can't receive their oracle mass, so the score is the fraction of oracle
attention mass the resident set retains (higher = the policy kept the pages
the model wanted to attend to).  The trace generator never looks at policy
decisions, so the comparison is apples-to-apples by construction.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache import paged_kv

CLASSIC = ("awrp", "lru", "fifo", "lfu", "arc", "car")
ADAPTIVE = tuple(paged_kv.TRUE_ADAPTIVE_KV)  # arc_adaptive, car_adaptive
PAGES, PAGE_SIZE, KVD = 6, 8, 8


def _hot_schedule(n_total: int, seed: int):
    """Per-phase hot page sets, fixed up front (policy-independent)."""
    rng = np.random.RandomState(seed)
    phase_len = max(n_total // 4, 1)
    phases = []
    for ph in range((n_total + phase_len - 1) // phase_len):
        lo = max(ph * phase_len - 8, 0)
        hi = max(ph * phase_len, 1)
        phases.append(rng.randint(lo, hi, size=3))
    return phase_len, phases


def _page_mass(n_have: int, open_page: int, hot: np.ndarray) -> np.ndarray:
    """Oracle attention mass over page ids 0..n_have-1."""
    w = np.full(n_have, 0.05)
    w[open_page] += 3.0  # local attention on the page being written
    if open_page > 0:
        w[open_page - 1] += 1.0
    for i, h in enumerate(hot):
        if h < n_have:
            w[h] += 2.0 / (i + 1)  # zipf-ish weights on the hot set
    return w / w.sum()


def _drive(policy: str, steps: int, seed: int):
    """Serve one decode stream under ``policy``; returns (retained mass
    fraction, us/token)."""
    adaptive = policy in paged_kv.TRUE_ADAPTIVE_KV
    zero = jnp.zeros((1, KVD), jnp.float32)
    if adaptive:
        core = paged_kv.adaptive_core(policy, 1, PAGES)
        state = paged_kv.init_adaptive_pool(
            1, PAGES, PAGE_SIZE, KVD, jnp.float32, policy
        )
        insert = jax.jit(
            lambda st, pos: paged_kv.adaptive_insert_token(
                st, zero, zero, pos, PAGE_SIZE, core
            )
        )
        score = jax.jit(
            lambda st, m: paged_kv.adaptive_score_update(st, m, PAGE_SIZE, core)
        )
        pool_of = lambda st: st.pool  # noqa: E731
    else:
        state = paged_kv.init_pool(1, PAGES, PAGE_SIZE, KVD, jnp.float32)
        insert = jax.jit(
            lambda st, pos: paged_kv.insert_token(
                st, zero, zero, pos, PAGE_SIZE, policy=policy
            )
        )
        score = jax.jit(lambda st, m: paged_kv.score_update(st, m, PAGE_SIZE))
        pool_of = lambda st: st  # noqa: E731

    phase_len, phases = _hot_schedule(steps // PAGE_SIZE + 1, seed)
    retained, t0 = 0.0, time.perf_counter()
    for pos in range(steps):
        state = insert(state, jnp.asarray(pos, jnp.int32))
        pool = pool_of(state)
        open_page = pos // PAGE_SIZE
        n_have = open_page + 1
        w = _page_mass(n_have, open_page, phases[open_page // phase_len])
        ps = np.asarray(pool.page_start)[0]
        pids = ps[ps >= 0] // PAGE_SIZE
        retained += float(w[pids].sum())
        # distribute each resident page's oracle mass over its rows (the
        # model's softmax renormalizes over resident kv), feed the pool
        rows = np.zeros((1, PAGES * PAGE_SIZE), np.float32)
        for slot, start in enumerate(ps):
            if start >= 0:
                pid = start // PAGE_SIZE
                rows[0, slot * PAGE_SIZE : (slot + 1) * PAGE_SIZE] = (
                    w[pid] / PAGE_SIZE
                )
        tot = rows.sum()
        if tot > 0:
            rows /= tot
        state = score(state, jnp.asarray(rows))
    dt = time.perf_counter() - t0
    return retained / steps, dt / steps * 1e6


def run():
    """Ablate paged-KV eviction policies (classic vs true-adaptive) on
    identical decode traces, scoring oracle attention mass retained."""
    steps = 1536
    print("== paged-KV serving ablation: oracle attention mass retained ==")
    print(f"   pool {PAGES} pages x {PAGE_SIZE} tokens, {steps}-step decode, "
          f"hot-set phase changes")
    print(f"{'policy':>14} | retained mass | us/token (host loop + jit step)")
    results = {}
    for policy in CLASSIC + ADAPTIVE:
        kept, us = _drive(policy, steps, seed=17)
        results[policy] = kept
        label = ("true-adaptive" if policy in ADAPTIVE else "classic")
        print(f"{policy:>14} | {100 * kept:12.2f}% | {us:8.1f}  [{label}]")
    assert all(0.0 < v <= 1.0 for v in results.values())
    # every resident-set policy must beat blind FIFO rotation on this
    # locality+hot-set mix for the bench to be meaningfully discriminative
    spread = max(results.values()) - min(results.values())
    print(f"best-to-worst spread: {100 * spread:.2f} points")


if __name__ == "__main__":
    run()
