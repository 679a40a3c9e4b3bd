"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload sweep.table1 --seed 7 --seconds 30 --trace 0
    python bench/run.py --workload serve.longctx --seed 7 --seconds 30 --trace 1
    python bench/run.py --workload sweep.table1 --seed 7 --rehearse

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  A measuring run
needs a TPU whose kind is in ``bench/peaks.PEAKS`` and exits non-zero
without one.  ``--rehearse`` runs the cell at its tiny sizes on the CPU
and prints its checks, never a metric.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; prints checks, no metric")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from bench import harness

    return harness.main(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
