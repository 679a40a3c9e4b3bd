"""Serving driver: batched requests through the AWRP-managed engine.

The published configuration of ``--arch`` is served at full width with
random weights; ``--smoke`` swaps in the arch's reduced
``SMOKE_CONFIG`` (CPU-sized):

  PYTHONPATH=src python -m repro.launch.serve --arch smollm_360m \
      --requests 8 --new-tokens 32 --kv-mode paged --kv-policy awrp --fused

Multi-tenant serving (DESIGN.md §8) — one policy-core row per tenant,
per-tenant quotas/telemetry and pressure-driven admission:

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3_27b --smoke \
      --requests 8 --tenants "alice=4,bob=2" --repeat-prompts

Performance observability (DESIGN.md §12) — live Prometheus endpoint,
periodic JSONL snapshots, and jax.profiler trace capture:

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3_27b --smoke \
      --requests 8 --metrics-port 0 --metrics-out /tmp/serve_metrics \
      --snapshot-every 2 --profile-dir /tmp/serve_prof --profile-phases
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs.base import ARCH_IDS, load_config, load_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serve.engine import Request, ServeEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_360m", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="serve the arch's reduced SMOKE_CONFIG instead of "
                    "the published CONFIG")
    ap.add_argument("--fused", action="store_true",
                    help="paged KV only: decode through the fused "
                    "policy-attention Pallas kernel (DESIGN.md §10)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--kv-mode", default="full", choices=("full", "paged"))
    ap.add_argument("--kv-policy", default="awrp",
                    choices=("awrp", "lru", "fifo", "lfu",
                             "arc_adaptive", "car_adaptive"))
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--repeat-prompts", action="store_true",
                    help="send duplicate prompts to exercise the prefix cache")
    ap.add_argument("--tenants", default=None, metavar="NAME=QUOTA,...",
                    help="multi-tenant mode: per-tenant prompt-cache quotas "
                    "(one policy-core row each); requests round-robin the "
                    "tenants and telemetry reports per-tenant hit ratios "
                    "and pressure")
    ap.add_argument("--auto-rebalance", action="store_true",
                    help="move quota lanes to pressured tenants from the "
                    "coldest (AWRP tenant ranking)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="export the final telemetry snapshot: writes "
                    "PATH.prom (Prometheus text exposition) and appends one "
                    "JSON line to PATH.jsonl (obs.export)")
    ap.add_argument("--decision-trace", type=int, default=0, metavar="N",
                    help="multi-tenant only: record the last N policy "
                    "decisions in the on-device trace ring and report "
                    "OPT-regret gauges in the final snapshot")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve live telemetry over HTTP from a background "
                    "thread while generating: /metrics (Prometheus text), "
                    "/metrics.json, /healthz (obs.server; 0 = ephemeral "
                    "port, printed at startup)")
    ap.add_argument("--snapshot-every", type=float, default=0.0,
                    metavar="SECONDS",
                    help="with --metrics-out: append a JSONL telemetry "
                    "snapshot every SECONDS from a background thread while "
                    "generating (plus the final snapshot)")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture annotated jax.profiler device traces "
                    "under DIR (one capture per --profile-every requests; "
                    "open with TensorBoard's profile plugin)")
    ap.add_argument("--profile-every", type=int, default=16, metavar="N",
                    help="requests between jax.profiler captures "
                    "(with --profile-dir)")
    ap.add_argument("--profile-phases", action="store_true",
                    help="sync-disciplined phase timers: each span blocks "
                    "on its own outputs so span/* isolates per-phase "
                    "device time (obs.spans sync discipline)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    tenants = None
    if args.tenants:
        tenants = {}
        for part in args.tenants.split(","):
            name, _, quota = part.partition("=")
            tenants[name.strip()] = int(quota)

    cfg = (load_smoke_config if args.smoke else load_config)(args.arch)
    cfg = dataclasses.replace(cfg, kv_policy=args.kv_policy)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    if args.decision_trace and tenants is None:
        ap.error("--decision-trace needs --tenants")
    if args.snapshot_every and not args.metrics_out:
        ap.error("--snapshot-every needs --metrics-out")
    engine = ServeEngine(cfg, params, max_len=args.max_len,
                         kv_mode=args.kv_mode, tenants=tenants,
                         auto_rebalance=args.auto_rebalance,
                         fused=args.fused,
                         decision_trace=args.decision_trace,
                         profile_dir=args.profile_dir,
                         profile_every=args.profile_every,
                         profile_phases=args.profile_phases)

    # live export (obs.server): both run on daemon threads and read the
    # registry through the same one-pull snapshot protocol telemetry() uses
    server = logger = None
    if args.metrics_port is not None:
        from repro.obs.server import MetricsServer

        server = MetricsServer(engine.telemetry,
                               port=args.metrics_port).start()
        print(f"metrics: serving http://127.0.0.1:{server.port}/metrics")
    if args.snapshot_every:
        from repro.obs.server import SnapshotLogger

        logger = SnapshotLogger(
            engine.telemetry, args.metrics_out + ".jsonl",
            interval_s=args.snapshot_every,
            extra={"arch": cfg.name, "kv_mode": args.kv_mode},
        ).start()

    rng = np.random.RandomState(0)
    names = list(tenants) if tenants else ["default"]
    reqs = []
    for i in range(args.requests):
        if args.repeat_prompts and i >= 2 * len(names):
            # repeat an earlier prompt of the SAME tenant (prefix reuse)
            prompt = reqs[i - 2 * len(names)].prompt[:]
        else:
            prompt = rng.randint(1, cfg.vocab, size=args.prompt_len).tolist()
        reqs.append(Request(i, prompt, max_new_tokens=args.new_tokens,
                            tenant_id=names[i % len(names)]))

    t0 = time.time()
    if tenants is None:
        results = engine.generate(reqs)
    else:
        # per-request submission: the prefix path and admission controller
        # act request-by-request, as a serving frontend would drive them
        results = {}
        for r in reqs:
            results.update(engine.generate([r]))
    dt = time.time() - t0
    total_tokens = sum(len(r.tokens) for r in results.values())
    print(f"arch={cfg.name} kv_mode={args.kv_mode} policy={args.kv_policy} "
          f"fused={args.fused}")
    print(f"{len(reqs)} requests, {total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens/dt:.1f} tok/s host-side)")
    if args.decision_trace:
        regret = engine.opt_regret()  # also sets the registry gauges
        agg = regret["aggregate"]
        print(f"opt regret ({agg['accesses']} traced accesses): "
              f"observed={agg['observed']:.2f} opt={agg['opt']:.2f} "
              f"regret={agg['regret']:.2f}")
    tel = engine.telemetry()  # ONE flat snapshot, one device pull
    traced = " ".join(
        f"{k.split('/')[1]}={tel[k]}" for k in sorted(tel)
        if k.startswith("compile/") and k.endswith("/count") and tel[k]
    )
    print(f"compile traces: {traced}")
    if tenants is None:
        print(f"prefix cache: hits={tel['prefix/hits']} "
              f"misses={tel['prefix/misses']} "
              f"(ratio {tel['prefix/hit_ratio']:.2f})")
    else:
        for name in names:
            print(f"tenant {name}: quota={tel[f'tenant/{name}/quota']} "
                  f"hit_ratio={tel[f'tenant/{name}/hit_ratio']:.2f} "
                  f"evictions={tel[f'tenant/{name}/evictions']} "
                  f"pressure={tel[f'tenant/{name}/pressure']:.2f}")
        print(f"admission: shed={tel['serve/shed']} "
              f"deferred={tel['serve/deferred']} "
              f"rebalances={tel['serve/rebalances']}")
    if args.metrics_out:
        from repro.obs.export import append_jsonl, prometheus_text

        with open(args.metrics_out + ".prom", "w") as fh:
            fh.write(prometheus_text(tel))
        if logger is not None:
            logger.stop()  # appends the final JSONL snapshot itself
        else:
            append_jsonl(args.metrics_out + ".jsonl", tel,
                         extra={"arch": cfg.name, "kv_mode": args.kv_mode})
        print(f"metrics: wrote {args.metrics_out}.prom, appended "
              f"{args.metrics_out}.jsonl")
    if server is not None:
        server.stop()
    for rid in sorted(results)[:4]:
        r = results[rid]
        print(f"  req {rid}: cached={r.prefill_cached} status={r.status} "
              f"tokens={r.tokens[:8]}...")


if __name__ == "__main__":
    main()
