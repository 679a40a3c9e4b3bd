"""Table 1 reproduction: hit ratio of LRU / FIFO / CAR / AWRP over the
paper's frame sizes, on the calibrated stand-in trace (+ the paper's own
digits for side-by-side comparison).

Every row — including the adaptive CAR, array-encoded per DESIGN.md §2 —
runs through the batched device engine as one jitted program for the whole
policy x frame-size grid, bit-identical to the host oracles."""

from __future__ import annotations

import numpy as np

from repro.core import hit_ratio_table, sweep
from repro.core.traces import paper_trace

# Table 1 of the paper (percent hit ratio)
PAPER_TABLE1 = {
    "lru": {30: 41.6, 60: 48.6, 90: 54.5, 120: 60.81, 150: 65.21, 180: 72.3, 210: 72.7},
    "fifo": {30: 40.93, 60: 49.26, 90: 57.48, 120: 62.14, 150: 66.3, 180: 72.84, 210: 74.03},
    "car": {30: 40.24, 60: 49.65, 90: 59.27, 120: 66.2, 150: 70.96, 180: 75.22, 210: 75.42},
    "awrp": {30: 41.92, 60: 54.41, 90: 64.02, 120: 69.27, 150: 71.65, 180: 74.53, 210: 75.42},
}

CAPS = [30, 60, 90, 120, 150, 180, 210, 240]  # paper text says 8 sizes


def run(out_lines=None):
    """Reproduce the paper's Table 1 hit-ratio grid on the calibrated
    stand-in trace and check AWRP's gain ordering (CSV rows appended to
    ``out_lines``)."""
    tr = paper_trace()
    res = sweep(["lru", "fifo", "car", "awrp"], tr, CAPS)
    print("== Table 1 reproduction (stand-in trace; paper digits in brackets) ==")
    print(hit_ratio_table(res, CAPS))
    gains = {}
    for other in ("lru", "fifo", "car"):
        ours = np.mean([res["awrp"][c] - res[other][c] for c in CAPS]) * 100
        caps7 = [c for c in CAPS if c in PAPER_TABLE1["awrp"]]
        paper = np.mean([PAPER_TABLE1["awrp"][c] - PAPER_TABLE1[other][c]
                         for c in caps7])
        gains[other] = (ours, paper)
        print(f"AWRP mean gain vs {other.upper():4s}: ours {ours:+.2f}pp | "
              f"paper {paper:+.2f}pp")
    if out_lines is not None:
        for other, (ours, paper) in gains.items():
            out_lines.append(
                f"table1_gain_vs_{other},0,{ours:+.3f}pp(paper {paper:+.2f}pp)")
        for c in CAPS:
            out_lines.append(
                f"table1_awrp_hit_cap{c},0,{100*res['awrp'][c]:.2f}%")
    return res


if __name__ == "__main__":
    run()
