"""Policy-pluggable victim selection for KV pages (the paper's technique and
its baselines, applied to the serving cache).

``page_victim`` is the single decision point used by the classic paged pool:
AWRP is the paper's eq. (1); LRU/FIFO/LFU are the baselines the paper
compares against, re-expressed on page metadata so the serving ablation
(studies/serve_policy_bench.py) is apples-to-apples.  ``arc`` and ``car``
are stateless two-segment approximations of the adaptive policies on the
same metadata (DESIGN.md §2): pages referenced at most once since insertion
form the T1-analog (evicted first), multiply-referenced pages the T2-analog;
``arc`` orders within a segment by recency, ``car`` by insertion (clock)
order.  The TRUE adaptive ARC/CAR — ghost directory and the self-tuning
``p`` — carry ``AdaptiveState`` planes through the unified policy core
(``repro.core.policy_core``, DESIGN.md §7) and run live in the pool via
``repro.cache.paged_kv``'s adaptive mode as well as in the batched sweep
engine.

The victim *reductions* live in the policy core: every branch is a chain of
vectorizable min-reductions (``policy_core.first_min``) — no ``argmin``,
which XLA CPU lowers to a ~30x slower scalar reduce (decision-identical to
the argmin formulation; parity-tested in tests/test_paged_pool.py).  The
AWRP branch is a core-level dispatch (``policy_core.awrp_victim_rows``):
pass ``use_kernel=True`` to route through the fused Pallas kernel on TPU;
the inline jnp path used inside the GSPMD-partitioned decode step is
decision-identical (property-tested).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.policy_core import INT_MAX, awrp_victim_rows, first_min

__all__ = ["PAGE_POLICIES", "first_min", "page_victim"]

PAGE_POLICIES = ("awrp", "lru", "fifo", "lfu", "arc", "car")


def _masked_tiebreak(primary: jax.Array, secondary: jax.Array) -> jax.Array:
    """First index minimizing (primary, secondary) lexicographically."""
    m = jnp.min(primary, axis=-1, keepdims=True)
    return first_min(jnp.where(primary == m, secondary, INT_MAX))


def page_victim(
    policy: str,
    f: jax.Array,  # (B, P) int32 frequency
    r: jax.Array,  # (B, P) int32 last-reference clock
    page_start: jax.Array,  # (B, P) int32 token start, -1 free
    clock: jax.Array,  # (B,) int32
    pinned: jax.Array,  # (B, P) bool
    *,
    use_kernel: bool = False,
) -> jax.Array:
    """Advisory next-victim page for each row of the paged-KV pool state
    (the pool's eviction rule; pure, jit-safe)."""
    valid = (page_start >= 0) & ~pinned
    if policy == "awrp":
        return awrp_victim_rows(f, r, clock, valid, use_kernel=use_kernel)
    if policy == "lru":
        return first_min(jnp.where(valid, r, INT_MAX))
    if policy == "fifo":
        return first_min(jnp.where(valid, page_start, INT_MAX))
    if policy == "lfu":
        return _masked_tiebreak(jnp.where(valid, f, INT_MAX), r)
    if policy == "arc":
        # T1-analog (f <= 1, seen once) evicts before T2-analog; LRU within
        cold = jnp.where(valid, (f > 1).astype(jnp.int32), INT_MAX)
        return _masked_tiebreak(cold, r)
    if policy == "car":
        # same segmentation, clock-hand (insertion) order within a segment
        cold = jnp.where(valid, (f > 1).astype(jnp.int32), INT_MAX)
        return _masked_tiebreak(cold, page_start)
    raise ValueError(f"unknown page policy {policy!r}; have {PAGE_POLICIES}")
