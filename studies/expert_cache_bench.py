"""MoE expert-cache bench: miss rate == host->HBM transfer volume under each
policy, on router traces from the two assigned MoE archs' configurations;
plus the batched device runtime path (one (n_layers,)-row policy-core step
per router batch, DESIGN.md §7) vs the per-layer host dict-oracle loop."""

from __future__ import annotations

import time

import numpy as np

from repro.cache.expert_cache import ExpertCacheRuntime, simulate_router_trace

CASES = [
    # (name, experts, cache_capacity, expert MB, zipf a, phases)
    ("grok1_8e_cache6", 8, 6, 805, 1.2, 1),
    ("phi35_16e_cache8", 16, 8, 105, 1.3, 2),
    ("fine_grained_64e_cache16", 64, 16, 25, 1.1, 3),
]


def _trace(E, alpha, phases, n=20_000, seed=0):
    rng = np.random.RandomState(seed)
    per = n // phases
    parts = []
    for ph in range(phases):
        t = rng.zipf(alpha, size=per) % E
        parts.append((t + ph * max(E // 4, 1)) % E)  # hot set drifts per phase
    return np.concatenate(parts)


def run():
    """Replay phase-drifting Zipf expert-routing traces through each cache
    policy and report hit ratio plus host-to-device GB moved."""
    print("== expert cache (policy -> hit ratio | GB transferred) ==")
    pols = ["awrp", "lru", "fifo", "lfu", "car", "arc"]
    for name, E, cap, mb, alpha, phases in CASES:
        tr = _trace(E, alpha, phases)
        res = simulate_router_trace(pols, tr, cap, expert_bytes=mb << 20)
        row = " | ".join(
            f"{p}:{100*res[p]['hit_ratio']:.1f}%/"
            f"{res[p]['transfer_bytes']/2**30:.0f}GB" for p in pols)
        print(f"  {name:>24}: {row}")

    # runtime paths: per-layer host oracles vs the batched device core
    # (identical accounting — parity-tested; here we time the two paths)
    n_layers, cap, k, steps = 16, 8, 2, 400
    rng = np.random.RandomState(1)
    route = rng.zipf(1.3, size=(steps, n_layers, k)) % 16
    rows = {}
    for device in (False, True):
        rt = ExpertCacheRuntime(n_layers, cap, policy="awrp", device=device)
        # untimed warmup step (same on both paths, so accounting stays
        # comparable): excludes the device path's one-off jit compile —
        # the step function's cache lives on the runtime instance
        rt.route_step(route[0])
        t0 = time.perf_counter()
        for s in range(1, steps):
            rt.route_step(route[s])
        dt = (time.perf_counter() - t0) / (steps - 1) * 1e6
        rows[device] = (dt, rt.hit_ratio)
    assert rows[False][1] == rows[True][1], "device path accounting diverged"
    print(f"  runtime route_step ({n_layers} layers x top-{k}): "
          f"host {rows[False][0]:.0f}us | device {rows[True][0]:.0f}us "
          f"per step (identical hit ratio {100*rows[False][1]:.1f}%)")


if __name__ == "__main__":
    run()
