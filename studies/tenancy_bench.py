"""Multi-tenant tenancy ablation: shared cache vs quota rows vs AWRP-ranked
rebalancing on the IDENTICAL interleaved multi-tenant trace
(``traces.trace_multi_tenant`` — the same workload the sweep engine and the
property suite replay).

Three mounts of the same total lane budget:

* **shared** — one policy instance of ``sum(quotas)`` lanes serves every
  tenant's stream mixed together: the pre-tenancy serving shape, where a
  thrash-heavy tenant pollutes everyone's residency;
* **quota rows** — ``TenantCacheManager``: one core row per tenant, quotas
  as per-row capacities (masked dead lanes), per-row accounting from the
  core itself.  Isolation by construction;
* **rebalanced** — quota rows plus the AWRP tenant ranking: every chunk the
  most-pressured tenant takes one lane from the coldest (eq. (1) at tenant
  altitude, DESIGN.md §8).

Score is per-tenant *retained mass*: the fraction of the tenant's accesses
its resident set served (hit ratio), reported per tenant and
traffic-weighted.  The trace generator never sees policy decisions, so the
three mounts are apples-to-apples by construction.
"""

from __future__ import annotations

import time

import numpy as np

from repro.core.traces import trace_multi_tenant
from repro.serve.tenancy import TenantCacheManager

TENANTS = ("hot", "mid", "scan")
#: the hot tenant drives half the traffic; the no-locality tenant is cold
MIX = (0.5, 0.3, 0.2)
ALPHAS = (1.2, 0.8, 0.0)


def _trace(n: int, seed: int = 0):
    return trace_multi_tenant(
        n, n_tenants=3, working_set=120, alphas=ALPHAS, mix=MIX,
        phase_at=0.5, seed=seed)


def _per_tenant_hits(tenant_rows, hits, n_tenants=3):
    out = []
    for t in range(n_tenants):
        sel = tenant_rows == t
        out.append((int(hits[sel].sum()), int(sel.sum())))
    return out


def _shared(policy, quotas, tenant_rows, keys):
    """One shared cache of the total lane budget; per-tenant attribution of
    the mixed stream's hit bits."""
    mgr = TenantCacheManager({"all": sum(quotas)}, policy)
    hits = mgr.access_stream(np.zeros_like(tenant_rows), keys)
    return _per_tenant_hits(tenant_rows, hits)


def _quota_rows(policy, quotas, tenant_rows, keys):
    mgr = TenantCacheManager(dict(zip(TENANTS, quotas)), policy)
    t0 = time.perf_counter()
    hits = mgr.access_stream(tenant_rows, keys)
    dt = time.perf_counter() - t0
    return _per_tenant_hits(tenant_rows, hits), dt, mgr


def _rebalanced(policy, quotas, tenant_rows, keys, chunks=8):
    """Quota rows + the AWRP tenant ranking, one lane move per chunk: the
    HIGHEST-ranked tenant under eviction pressure takes a lane, the
    lowest-ranked donates (``rebalance`` picks the donor).  Ranking by
    eq. (1) — not by raw pressure — matters: the no-locality tenant has the
    highest pressure (it thrashes at any quota) but the lowest weight, so
    it donates instead of being rewarded for thrashing."""
    mgr = TenantCacheManager(dict(zip(TENANTS, quotas)), policy)
    hits = np.zeros(len(keys), dtype=bool)
    bounds = np.linspace(0, len(keys), chunks + 1, dtype=int)
    moves = 0
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        hits[lo:hi] = mgr.access_stream(tenant_rows[lo:hi], keys[lo:hi])
        ranked = mgr.rank_tenants()  # coldest first
        for cand in reversed(ranked):  # hottest first
            if cand != ranked[0] and mgr.pressure(cand) > 0.05:
                moved, _ = mgr.rebalance(cand, 1)
                moves += moved
                break
    return _per_tenant_hits(tenant_rows, hits), mgr.quotas, moves


def run():
    """Ablate shared cache vs quota rows vs AWRP-ranked rebalancing on
    the identical interleaved multi-tenant trace."""
    n = 6_000
    policy = "awrp"
    quotas = (16, 16, 16)
    tenant_rows, keys = _trace(n)
    keys = keys % (2**31 - 1)

    shared = _shared(policy, quotas, tenant_rows, keys)
    rows, dt, mgr = _quota_rows(policy, quotas, tenant_rows, keys)
    rebal, final_quotas, moves = _rebalanced(policy, quotas, tenant_rows, keys)

    def ratios(stats):
        return [h / max(a, 1) for h, a in stats]

    def weighted(stats):
        h = sum(x for x, _ in stats)
        a = sum(x for _, x in stats)
        return h / max(a, 1)

    print(f"== tenancy ablation ({policy}, {n} accesses, quotas {quotas}, "
          f"mix {MIX}, alphas {ALPHAS}) ==")
    print(f"{'mount':>12} | " + " | ".join(f"{t:>6}" for t in TENANTS)
          + " | weighted")
    for name, stats in (("shared", shared), ("quota_rows", rows),
                        ("rebalanced", rebal)):
        r = ratios(stats)
        print(f"{name:>12} | " + " | ".join(f"{x:6.3f}" for x in r)
              + f" | {weighted(stats):8.3f}")
    us = 1e6 * dt / n
    print(f"quota-row device replay: {us:.2f} us/access "
          f"(one jitted masked-row scan)")
    print(f"rebalancer: {moves} lane moves, final quotas {final_quotas}")
    tel = mgr.telemetry()
    print("per-tenant manager telemetry (quota rows): "
          + ", ".join(f"{t}: hr={tel[t]['hit_ratio']:.3f} "
                      f"ev={tel[t]['evictions']} p={tel[t]['pressure']:.2f}"
                      for t in TENANTS))


if __name__ == "__main__":
    run()
