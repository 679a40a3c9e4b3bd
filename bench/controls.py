"""Read the numbers a cell's check compares, for the program and for its
control, on many seeds, one process a seed: the readings the check's
limits are set from.

    python3 bench/controls.py --workload serve.longctx --seeds 11 12 13
    python3 bench/controls.py --workload sweep.table1 --seeds 11 12 13

Per seed it makes the cell's inputs, runs one call (a sweep call, or one
serving batch) through the cell's own timed path, and prints one JSON
line with the check's numbers and the control's reading on the same
sample (the driver's ``control``):

* sweep: ``control_rows``, the check's rows on which the reference
  computed in bfloat16 differs from the float32 one;
* serve: ``control_gap``, at each served position the gap under the
  float32 reference of the token the float8 reference puts first.

Given several seeds it runs itself once for each and stays off JAX,
whose chip the child needs: a dropped ``ServeEngine`` is never freed,
so a dozen engines in one process would not fit the chip.  A measuring
machine is needed, as for ``bench/run.py``; ``--rehearse`` runs the
cell's tiny sizes on the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if len(args.seeds) > 1:
        for seed in args.seeds:
            cmd = [sys.executable, __file__, "--workload", args.workload,
                   "--seeds", str(seed)] + ["--rehearse"] * args.rehearse
            if subprocess.run(cmd).returncode:
                return 1
        return 0
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from bench import harness, traffic
    from repro.launch.compile_cache import enable_compile_cache

    cell, entry = harness.find_cell(harness.load_benchmark(), args.workload)
    cfg = harness.load_config(entry, rehearsal=args.rehearse)
    mix = traffic.load_mix(cell["traffic"], rehearsal=args.rehearse)
    harness.device_info(cell["chips"], args.rehearse)
    enable_compile_cache()
    seed, = args.seeds
    run = harness.driver(cfg).Run(cfg, mix, seed, args.rehearse)
    run.setup(warm=False)
    run.window(0.0, 1)
    run.release()
    out = {name: value for name, value, _ in run.check()}
    out.update(run.control())
    print(json.dumps({"workload": args.workload, "seed": seed, **out}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
