"""Mesh-sharded sweep engine: bit-identity gate + scaling record.

Runs the six-policy capacity sweep (``simulate_trace_batched``) three ways
on the SAME trace — unsharded, on a 2-device rows mesh, and on the full
host-device mesh — and

* **hard-gates bit-identity**: the sharded grids must equal the unsharded
  grid exactly (every hit bit, every config).  A mismatch raises, which
  fails the section and the CI bench job — sharding is only allowed to
  change WHERE rows compute, never WHAT they decide (DESIGN.md §4);
* records measured grid throughput and speedup-vs-unsharded for each mesh
  into the ``sharded_sweep`` key of the BENCH_sweep.json artifact,
  alongside ``os.cpu_count()`` and the device count, so the numbers are
  interpretable: XLA host devices TIME-SLICE the available cores, so
  speedup tracks physical parallelism — on a 1-core container the meshes
  measure near (or below) 1x, and the >=Nx scaling materializes only with
  >=N physical cores (e.g. the CI matrix's multi-core runners or a real
  TPU/GPU mesh).  The parity gate is meaningful at ANY core count.

Requires multiple XLA host devices: run through ``benchmarks/run.py
--devices 8`` (which sets ``--xla_force_host_platform_device_count``
before jax loads) or set XLA_FLAGS yourself.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.core import sharding
from repro.core.jax_policies import DEVICE_POLICIES, simulate_trace_batched
from repro.core.traces import trace_zipf

#: Table-1 frame sizes — the same grid policy_overhead sweeps
SWEEP_CAPS = [30, 60, 90, 120, 150, 180, 210, 240]


def _timed_grid(tr, mesh):
    """(seconds, hits ndarray) for one warm sweep of the full grid."""
    h = simulate_trace_batched(tr, DEVICE_POLICIES, SWEEP_CAPS, mesh=mesh)
    h.block_until_ready()  # compile outside the timed region
    t0 = time.perf_counter()
    h = simulate_trace_batched(tr, DEVICE_POLICIES, SWEEP_CAPS, mesh=mesh)
    h.block_until_ready()
    return time.perf_counter() - t0, np.asarray(h)


def run(out_lines=None, smoke: bool = False, sweep_json=None):
    """Benchmark section entrypoint (see ``benchmarks/run.py``).

    Appends CSV rows to ``out_lines``, shrinks the trace under ``smoke``,
    and merges the ``sharded_sweep`` record into ``sweep_json`` when set.
    Raises AssertionError if any sharded grid deviates from the unsharded
    one — the bit-identity gate is the point of the section."""
    n_dev = sharding.device_count()
    if n_dev < 2:
        print("== sharded sweep: SKIPPED (1 XLA device; rerun via "
              "`benchmarks/run.py --devices 8`) ==")
        return

    n_accesses = 20_000 if smoke else 100_000
    tr = trace_zipf(n_accesses, 2_000, 0.9, seed=5)
    grid = len(DEVICE_POLICIES) * len(SWEEP_CAPS)

    base_s, base_hits = _timed_grid(tr, mesh=None)
    meshes = sorted({2, n_dev})
    results = {}
    for n in meshes:
        mesh_s, mesh_hits = _timed_grid(tr, sharding.rows_mesh(n))
        identical = bool((mesh_hits == base_hits).all())
        assert identical, (
            f"sharded sweep on {n} devices diverged from the unsharded "
            f"grid — sharding must be decision-invariant")
        results[n] = (mesh_s, identical)

    thr = grid * n_accesses / base_s
    print(f"== sharded sweep ({grid} configs x {n_accesses} accesses, "
          f"{n_dev} XLA host devices, {os.cpu_count()} cpu cores) ==")
    print(f"{'mesh':>10} | grid s | configs*acc/s | speedup | bit-identical")
    print(f"{'unsharded':>10} | {base_s:6.2f} | {thr:13.3g} | {1.0:7.2f} | "
          f"{'--':>13}")
    for n, (s, ident) in results.items():
        print(f"{f'mesh({n})':>10} | {s:6.2f} | "
              f"{grid * n_accesses / s:13.3g} | {base_s / s:7.2f} | "
              f"{str(ident):>13}")
    print("(XLA host devices time-slice the physical cores: speedup tracks "
          "core count, parity holds regardless)")

    if out_lines is not None:
        out_lines.append(
            f"sharded_sweep_unsharded,{1e6 * base_s / n_accesses:.2f},"
            f"{thr:.0f}_cfg_acc_per_s")
        for n, (s, _) in results.items():
            out_lines.append(
                f"sharded_sweep_mesh{n},{1e6 * s / n_accesses:.2f},"
                f"{base_s / s:.2f}x_vs_unsharded")

    if sweep_json is not None:
        record = {
            "n_accesses": n_accesses,
            "grid_configs": grid,
            "policies": list(DEVICE_POLICIES),
            "capacities": list(SWEEP_CAPS),
            "devices": n_dev,
            "cpu_count": os.cpu_count(),
            "unsharded_s": round(base_s, 4),
            "bit_identical": True,
            "meshes": {
                str(n): {
                    "grid_s": round(s, 4),
                    "speedup_vs_unsharded": round(base_s / s, 3),
                    "throughput_cfg_acc_per_s": round(
                        grid * n_accesses / s, 1),
                }
                for n, (s, _) in results.items()
            },
        }
        base = {}
        if os.path.exists(sweep_json):
            with open(sweep_json) as fh:
                base = json.load(fh)
        base["sharded_sweep"] = record
        with open(sweep_json, "w") as fh:
            json.dump(base, fh, indent=2)
            fh.write("\n")
        print(f"(sharded_sweep record merged into {sweep_json})")


if __name__ == "__main__":
    run()
