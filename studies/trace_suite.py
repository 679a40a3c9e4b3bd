"""Multi-trace policy evaluation (the honest generalization check behind the
single calibrated trace): real-program traces + locality models, AWRP vs
every implemented policy.  ``sweep()`` runs the device-capable policies
(lru/fifo/lfu/awrp plus the array-encoded arc/car) through the batched
engine per trace; 2q/opt stay on the host oracle path."""

from __future__ import annotations

import numpy as np

from repro.core import sweep
from repro.core.traces import (
    trace_hashjoin,
    trace_markov,
    trace_matmul,
    trace_mergesort,
    trace_scan_mix,
    trace_zipf,
)

POLICIES = ["lru", "fifo", "lfu", "car", "arc", "2q", "awrp", "opt"]


def suite():
    """The named generalization traces (matmul/mergesort/hashjoin/zipf/
    markov/scan-mix) the suite sweeps, freshly generated."""
    return {
        "matmul_tiled": trace_matmul(n=12, tile=4),
        "matmul_flat": trace_matmul(n=16),
        "mergesort": trace_mergesort(n=256),
        "hashjoin": trace_hashjoin(),
        "zipf_a0.8": trace_zipf(4000, 600, 0.8, 0),
        "zipf_a1.1": trace_zipf(4000, 461, 1.1, 1),
        "markov_ws": trace_markov(4000),
        "scan_mix": trace_scan_mix(4000),
    }


def run():
    """Sweep every policy over the generalization trace suite at 4 cache
    sizes and print mean hit ratios."""
    print("== trace suite: mean hit ratio over 4 cache sizes (10/25/50/75% of "
          "working set) ==")
    header = f"{'trace':>14} | " + " | ".join(f"{p:>6}" for p in POLICIES)
    print(header)
    print("-" * len(header))
    agg = {p: [] for p in POLICIES}
    for name, tr in suite().items():
        u = len(np.unique(tr))
        caps = sorted({max(4, int(u * f)) for f in (0.1, 0.25, 0.5, 0.75)})
        res = sweep(POLICIES, tr, caps)
        means = {p: float(np.mean(list(res[p].values()))) for p in POLICIES}
        for p in POLICIES:
            agg[p].append(means[p])
        print(f"{name:>14} | " + " | ".join(f"{100*means[p]:6.2f}" for p in POLICIES))
    print(f"{'MEAN':>14} | " + " | ".join(
        f"{100*np.mean(agg[p]):6.2f}" for p in POLICIES))
    return agg


if __name__ == "__main__":
    run()
