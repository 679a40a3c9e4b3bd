"""Benchmark entrypoint: one function per paper table / framework artifact.
Prints a ``name,us_per_call,derived`` CSV summary at the end (and writes it
to ``--csv PATH`` for CI artifact upload).  Exits non-zero when any section
fails, so CI bench jobs gate regressions instead of always passing.

Usage::

    PYTHONPATH=src python benchmarks/run.py                # full pass
    PYTHONPATH=src python benchmarks/run.py --smoke        # reduced CI pass
    PYTHONPATH=src python benchmarks/run.py --sections table1,policy_overhead
"""

from __future__ import annotations

import os
import sys

# make `python benchmarks/run.py` work from any cwd (script-mode sys.path
# holds benchmarks/, not the repo root that anchors the benchmarks package)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.xla_env import set_host_device_count  # noqa: E402

import argparse
import inspect
import traceback

#: sections cheap enough for the CI bench-smoke job (the rest stress model /
#: serving layers and take minutes even at reduced sizes).  policy_overhead
#: precedes tenancy and sharded_sweep: all three contribute to the
#: --sweep-json artifact and the later two merge into the record
#: policy_overhead writes.
SMOKE_SECTIONS = ("table1", "trace_suite", "policy_overhead", "tenancy",
                  "sharded_sweep", "serve_loop", "obs_overhead",
                  "kernel_bench", "policy_attn")


def main(argv=None) -> None:
    """Parse args, run the selected benchmark sections, emit the CSV
    summary, and exit non-zero if any section failed.  ``--devices`` is
    applied via ``set_host_device_count`` BEFORE any benchmark module (and
    therefore jax) is imported — that is why the section modules are
    imported inside this function."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes + cheap section subset (CI gate)")
    ap.add_argument("--csv", metavar="PATH", default=None,
                    help="also write the CSV summary to PATH")
    ap.add_argument("--sections", default=None,
                    help="comma-separated section keys to run (default: all, "
                    "or SMOKE_SECTIONS with --smoke)")
    ap.add_argument("--sweep-json", metavar="PATH", default=None,
                    help="write the batched-sweep grid throughput + "
                    "speedup-vs-host record (BENCH_sweep.json) to PATH — "
                    "uploaded as a CI artifact to track the perf trajectory "
                    "PR-over-PR")
    ap.add_argument("--devices", type=int, metavar="N", default=None,
                    help="expose N XLA host devices before jax loads "
                    "(sharded_sweep needs >=2; host devices time-slice the "
                    "physical cores)")
    args = ap.parse_args(argv)
    if args.devices is not None:
        set_host_device_count(args.devices)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    out_lines = []
    sections = []

    def section(name, fn):
        print(f"\n{'='*72}\n{name}\n{'='*72}")
        try:
            kw = {}
            params = inspect.signature(fn).parameters
            if "smoke" in params:
                kw["smoke"] = args.smoke
            if "sweep_json" in params:
                kw["sweep_json"] = args.sweep_json
            fn(out_lines, **kw)
            sections.append((name, "ok"))
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            sections.append((name, f"FAIL: {e}"))

    from benchmarks import (
        awrp_ablation,
        expert_cache_bench,
        grad_compress_bench,
        kernel_bench,
        obs_bench,
        policy_attn_bench,
        policy_overhead,
        roofline_report,
        serve_loop_bench,
        serve_policy_bench,
        serve_quality_bench,
        sharded_sweep,
        table1,
        tenancy_bench,
        trace_suite,
    )

    registry = {
        "table1": ("Table 1 reproduction (paper §4.2)", table1.run),
        "trace_suite": ("Trace suite (generalization)", trace_suite.run),
        "awrp_ablation": (
            "AWRP(alpha,beta) ablation (beyond paper, its §5 direction)",
            awrp_ablation.run),
        "policy_overhead": (
            "Policy overhead + batched sweep engine (paper §3 overhead claim)",
            policy_overhead.run),
        "kernel_bench": ("Kernel bench", kernel_bench.run),
        "policy_attn": (
            "Fused policy-attention kernels (bit-identity + dispatch gate, "
            "DESIGN.md §10)",
            policy_attn_bench.run),
        "serve_policy": (
            "Paged-KV policy ablation (classic vs true-adaptive, "
            "identical decode traces)",
            serve_policy_bench.run),
        "serve_quality": (
            "Bounded-KV serving quality (AWRP vs baselines)",
            serve_quality_bench.run),
        "tenancy": (
            "Multi-tenant tenancy (shared vs quota rows vs rebalancing)",
            tenancy_bench.run),
        "sharded_sweep": (
            "Mesh-sharded sweep (bit-identity gate + scaling, DESIGN.md §4)",
            sharded_sweep.run),
        "serve_loop": (
            "Fully-jitted serve loop vs host-orchestrated (DESIGN.md §9)",
            serve_loop_bench.run),
        "obs_overhead": (
            "Observability overhead gate + exporter artifacts "
            "(DESIGN.md §11)",
            obs_bench.run),
        "expert_cache": ("Expert cache (MoE serving)", expert_cache_bench.run),
        "grad_compress": ("Gradient compression", grad_compress_bench.run),
        "roofline": ("Roofline report (from dry-run artifacts)",
                     roofline_report.run),
    }

    if args.sections:
        keys = [k.strip() for k in args.sections.split(",") if k.strip()]
        unknown = [k for k in keys if k not in registry]
        if unknown:
            ap.error(f"unknown sections {unknown}; have {sorted(registry)}")
    elif args.smoke:
        keys = list(SMOKE_SECTIONS)
    else:
        keys = list(registry)

    for key in keys:
        section(*registry[key])

    print(f"\n{'='*72}\nCSV summary (name,us_per_call,derived)\n{'='*72}")
    for line in out_lines:
        print(line)
    if args.csv:
        with open(args.csv, "w") as fh:
            fh.write("name,us_per_call,derived\n")
            fh.write("\n".join(out_lines) + "\n")
        print(f"(written to {args.csv})")
    print()
    for name, status in sections:
        print(f"[{status}] {name}")
    if any(s != "ok" for _, s in sections):
        sys.exit(1)


if __name__ == "__main__":
    main()
