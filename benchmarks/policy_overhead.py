"""Policy overhead: µs/access host-side (the paper's 'low overhead' claim —
AWRP's lazy weights vs WRP's eager recompute), device throughput of the
vectorized policies (lax.scan over a trace), and the batched sweep engine's
whole-grid speedup over the host loop (the Table-1 acceptance number)."""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.core import make_policy
from repro.core.jax_policies import (
    DEVICE_POLICIES,
    simulate_trace,
    simulate_trace_batched,
)
from repro.core.traces import trace_zipf

TRACE = trace_zipf(20_000, 2_000, 0.9, seed=5)
CAP = 512
SWEEP_CAPS = [30, 60, 90, 120, 150, 180, 210, 240]  # the Table-1 frame sizes


def host_us_per_access(policy: str, trace, cap) -> float:
    """Microseconds per access of the host oracle for ``policy`` at
    capacity ``cap`` over ``trace`` (one timed pass)."""
    p = make_policy(policy, cap)
    if hasattr(p, "prepare"):
        p.prepare(trace)
    t0 = time.perf_counter()
    for b in trace:
        p.access(int(b))
    return (time.perf_counter() - t0) / len(trace) * 1e6


def device_us_per_access(policy: str, trace, cap) -> float:
    """Microseconds per access of the jitted device scan for ``policy``
    (compile excluded; mean of 3 warm passes)."""
    tr = jnp.asarray(trace)
    h = simulate_trace(tr, cap, policy=policy)
    h.block_until_ready()  # compile
    t0 = time.perf_counter()
    for _ in range(3):
        simulate_trace(tr, cap, policy=policy).block_until_ready()
    return (time.perf_counter() - t0) / 3 / len(trace) * 1e6


def batched_sweep_speedup(out_lines=None, n_accesses: int = 100_000,
                          sweep_json=None):
    """The COMPLETE six-policy Table-1 grid (awrp/lru/fifo/lfu + the
    array-encoded arc/car x all frame sizes) as ONE jitted program vs the
    host oracle loop, plus a kernel-routed run — the Pallas
    awrp_select_rows path the sweep exercises on TPU.  ``sweep_json``
    additionally writes the grid throughput + speedup record
    (BENCH_sweep.json, a CI artifact tracking the perf trajectory
    PR-over-PR)."""
    tr = trace_zipf(n_accesses, 2_000, 0.9, seed=5)
    grid = len(DEVICE_POLICIES) * len(SWEEP_CAPS)

    def timed(**kw):
        h = simulate_trace_batched(tr, DEVICE_POLICIES, SWEEP_CAPS, **kw)
        h.block_until_ready()  # compile
        t0 = time.perf_counter()
        h = simulate_trace_batched(tr, DEVICE_POLICIES, SWEEP_CAPS, **kw)
        h.block_until_ready()
        return time.perf_counter() - t0, np.asarray(h[0].sum(-1))

    dev_s, counts = timed()
    ker_s, ker_counts = timed(use_kernel=True)

    t0 = time.perf_counter()
    host_counts = np.zeros((len(DEVICE_POLICIES), len(SWEEP_CAPS)), dtype=np.int64)
    for pi, pol in enumerate(DEVICE_POLICIES):
        for ci, cap in enumerate(SWEEP_CAPS):
            p = make_policy(pol, cap)
            for b in tr:
                p.access(int(b))
            host_counts[pi, ci] = p.hits
    host_s = time.perf_counter() - t0

    parity = (counts == host_counts).all() and (ker_counts == host_counts).all()
    print(f"== batched sweep engine: {grid}-config six-policy Table-1 grid, "
          f"{n_accesses} accesses ==")
    print(f"host oracle loop : {host_s:8.3f}s")
    print(f"one-jit grid     : {dev_s:8.3f}s  ({host_s / dev_s:5.1f}x)")
    print(f"  + Pallas kernel: {ker_s:8.3f}s  ({host_s / ker_s:5.1f}x, "
          f"interpret mode off-TPU)")
    print(f"hit counts vs host oracles: {'bit-identical' if parity else 'MISMATCH'}")
    if not parity:
        raise AssertionError("batched sweep diverged from host oracles")
    if out_lines is not None:
        out_lines.append(
            f"batched_sweep_grid,{1e6 * dev_s / n_accesses:.2f},"
            f"{host_s / dev_s:.1f}x_vs_host")
        out_lines.append(
            f"batched_sweep_grid_kernel,{1e6 * ker_s / n_accesses:.2f},"
            f"{host_s / ker_s:.1f}x_vs_host")
    if sweep_json is not None:
        import json

        record = {
            "n_accesses": n_accesses,
            "grid_configs": grid,
            "policies": list(DEVICE_POLICIES),
            "capacities": list(SWEEP_CAPS),
            "host_loop_s": round(host_s, 4),
            "device_grid_s": round(dev_s, 4),
            "device_grid_kernel_s": round(ker_s, 4),
            "grid_accesses_per_s": round(n_accesses / dev_s, 1),
            "speedup_vs_host": round(host_s / dev_s, 2),
            "speedup_vs_host_kernel": round(host_s / ker_s, 2),
            "parity_with_host_oracles": bool(parity),
        }
        with open(sweep_json, "w") as fh:
            json.dump(record, fh, indent=2)
            fh.write("\n")
        print(f"(sweep record written to {sweep_json})")


def run(out_lines=None, smoke: bool = False, sweep_json=None):
    """Per-policy host vs device overhead table (the paper §3 claim) plus
    the batched sweep-engine throughput/speedup record — written as the
    base ``sweep_json`` record other sections merge into.  ``smoke``
    shrinks the trace; CSV rows appended to ``out_lines``."""
    trace = TRACE[:5_000] if smoke else TRACE
    print("== policy overhead ==")
    print(f"{'policy':>8} | host us/access | device us/access (lax.scan)")
    for pol in ("awrp", "wrp", "lru", "fifo", "lfu", "arc", "car", "2q"):
        host = host_us_per_access(pol, trace, CAP)
        dev = (device_us_per_access(pol, trace, CAP)
               if pol in DEVICE_POLICIES else float("nan"))
        print(f"{pol:>8} | {host:14.2f} | {dev:14.2f}")
        if out_lines is not None:
            out_lines.append(f"policy_host_{pol},{host:.2f},us_per_access")
            if pol in DEVICE_POLICIES:
                out_lines.append(f"policy_device_{pol},{dev:.2f},us_per_access")
    # the paper's overhead claim: AWRP (lazy) cheaper than WRP (eager)
    a = host_us_per_access("awrp", trace, CAP)
    w = host_us_per_access("wrp", trace, CAP)
    print(f"AWRP lazy-weight speedup over WRP: {w / a:.2f}x")
    if out_lines is not None:
        out_lines.append(f"awrp_vs_wrp_speedup,{a:.2f},{w / a:.2f}x")
    batched_sweep_speedup(out_lines, n_accesses=10_000 if smoke else 100_000,
                          sweep_json=sweep_json)


if __name__ == "__main__":
    run()
