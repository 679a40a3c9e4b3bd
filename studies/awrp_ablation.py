"""Beyond-paper ablation: generalized weight W = F^alpha / (N-R)^beta.

The paper's §5 ("if additional parameters and factors ... be taken into
account, then AWRP can be suitably used ...") invites exactly this: alpha
re-weights frequency, beta re-weights recency-age; (1,1) is eq. (1).  Grid
over the trace suite; report mean hit ratio and the best setting per trace."""

from __future__ import annotations

import numpy as np

from repro.core.simulator import simulate
from studies.trace_suite import suite

GRID = [(1.0, 1.0), (0.5, 1.0), (2.0, 1.0), (1.0, 0.5), (1.0, 2.0),
        (0.5, 2.0), (2.0, 0.5)]


def run():
    """Sweep AWRP's (alpha, beta) weighting grid over the trace suite and
    print mean hit %% per configuration — the paper-§5 sensitivity
    direction."""
    print("== AWRP(alpha, beta) ablation: mean hit % over 4 cache sizes ==")
    header = f"{'trace':>14} | " + " | ".join(f"a{a:g}/b{b:g}" for a, b in GRID)
    print(header)
    print("-" * len(header))
    means = {g: [] for g in GRID}
    for name, tr in suite().items():
        u = len(np.unique(tr))
        caps = sorted({max(4, int(u * f)) for f in (0.1, 0.25, 0.5, 0.75)})
        row = []
        for a, b in GRID:
            hr = float(np.mean([
                simulate("awrp", tr, c, alpha=a, beta=b).hit_ratio
                for c in caps
            ]))
            means[(a, b)].append(hr)
            row.append(hr)
        best = GRID[int(np.argmax(row))]
        print(f"{name:>14} | " + " | ".join(f"{100*v:6.2f}" for v in row)
              + f"   best=a{best[0]:g}/b{best[1]:g}")
    print(f"{'MEAN':>14} | " + " | ".join(
        f"{100*np.mean(means[g]):6.2f}" for g in GRID))
    overall = max(GRID, key=lambda g: np.mean(means[g]))
    base = 100 * np.mean(means[(1.0, 1.0)])
    best_v = 100 * np.mean(means[overall])
    print(f"paper eq.(1) mean: {base:.2f}%  |  best "
          f"(a={overall[0]:g}, b={overall[1]:g}): {best_v:.2f}% "
          f"({best_v - base:+.2f}pp)")


if __name__ == "__main__":
    run()
