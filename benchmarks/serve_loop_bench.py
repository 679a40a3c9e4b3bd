"""Fully-jitted serve loop vs host-orchestrated loop (DESIGN.md §9).

Two measurements on the SAME multi-tenant request stream (derived from
``traces.trace_multi_tenant`` — the tenancy bench workload):

* **requests/sec** — ``ServeEngine.generate`` end to end, ``jit_loop=True``
  (one donated-buffer scan program per bucket, device batch admission)
  against ``jit_loop=False`` (one jitted decode step per token, host
  admission).  Both engines see identical requests; a warmup pass compiles
  every bucket shape first, so the timed pass is the steady-state serving
  regime.
* **per-decision admission overhead** — ``AdmissionController.decide`` in
  a host loop (with decay-on-shed) vs ONE jitted ``decide_batch`` scan
  over the same decision stream, microseconds per decision.  This is the
  "policy overhead ≈ 0" number: the device path amortizes one dispatch
  over the whole batch while staying bit-identical to the host loop.

Lands the ``serve_loop`` section in the ``--sweep-json`` perf artifact.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time

import jax
import numpy as np

from repro.configs.base import load_smoke_config
from repro.core.traces import trace_multi_tenant
from repro.models import model as M
from repro.serve.engine import Request, ServeEngine
from repro.serve.tenancy import (
    SHED,
    AdmissionController,
    TenantCacheManager,
)

TENANTS = ("hot", "mid", "scan")
QUOTAS = (4, 3, 2)
MIX = (0.5, 0.3, 0.2)
ALPHAS = (1.2, 0.8, 0.0)


def _requests(n: int, cfg, new_tokens: int):
    """Request stream from the tenancy bench trace: the trace's tenant row
    picks the tenant AND the prompt length bucket (page multiples 1..3), the
    trace key seeds the prompt tokens — repeated keys repeat prompts."""
    tenant_rows, keys = trace_multi_tenant(
        n, n_tenants=3, working_set=24, alphas=ALPHAS, mix=MIX,
        phase_at=0.5, seed=0)
    page = cfg.page_size
    reqs = []
    for i, (t, k) in enumerate(zip(tenant_rows.tolist(), keys.tolist())):
        rng = np.random.RandomState(k % (2**31 - 1))
        plen = page * (t + 1)
        prompt = rng.randint(1, cfg.vocab, size=plen).tolist()
        reqs.append(Request(i, prompt, max_new_tokens=new_tokens,
                            temperature=0.0, tenant_id=TENANTS[t]))
    return reqs


def _engine(cfg, params, jit_loop: bool) -> ServeEngine:
    return ServeEngine(cfg, params, max_len=128, kv_mode="full",
                       tenants=dict(zip(TENANTS, QUOTAS)),
                       admission=AdmissionController(),
                       jit_loop=jit_loop, seed=0)


def _timed_pass(engine: ServeEngine, reqs) -> float:
    """One warmup ``generate`` (compiles every bucket shape), one timed."""
    engine.generate([dataclasses.replace(r) for r in reqs])
    t0 = time.perf_counter()
    engine.generate([dataclasses.replace(r) for r in reqs])
    return time.perf_counter() - t0


def _admission_streams(n_decisions: int):
    """A manager whose rows sit in distinct pressure bands (accept / defer
    / shed) plus a round-robin decision stream over them, so the timed
    loops exercise every branch including decay-on-shed."""
    mgr = TenantCacheManager(dict(zip(TENANTS, QUOTAS)), "lru",
                             pressure_alpha=0.5)
    for i in range(12):
        mgr.access("hot", i)  # quota 4, 12 distinct keys: sustained misses
    for i in range(12):
        mgr.access("mid", i % 4)  # mostly hits: low pressure
    for i in range(12):
        mgr.access("scan", i)  # thrash
    stream = [TENANTS[i % 3] for i in range(n_decisions)]
    return mgr, stream


def _host_decisions(adm, mgr, stream):
    out = []
    for t in stream:
        d = adm.decide(mgr, t)
        if d == SHED:
            mgr.decay_pressure(t)
        out.append(d)
    return out


def run(out_lines=None, smoke: bool = False, sweep_json=None):
    """Time the fully-jitted decode loop against the host-orchestrated
    baseline (same request stream) plus batched vs per-request admission;
    merges the ``serve_loop`` record into ``sweep_json``.  ``smoke``
    shrinks the stream; CSV rows appended to ``out_lines``."""
    n_reqs = 9 if smoke else 24
    new_tokens = 8 if smoke else 16
    n_decisions = 240 if smoke else 1200

    cfg = load_smoke_config("gemma3_27b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    reqs = _requests(n_reqs, cfg, new_tokens)

    dt_host = _timed_pass(_engine(cfg, params, jit_loop=False), reqs)
    dt_jit = _timed_pass(_engine(cfg, params, jit_loop=True), reqs)
    rps_host, rps_jit = n_reqs / dt_host, n_reqs / dt_jit

    # per-decision admission overhead, identical decision streams
    adm = AdmissionController(defer_at=0.4, shed_at=0.8, warmup=4)
    mgr_h, stream = _admission_streams(n_decisions)
    t0 = time.perf_counter()
    host_dec = _host_decisions(adm, mgr_h, stream)
    us_host = 1e6 * (time.perf_counter() - t0) / n_decisions
    mgr_d, _ = _admission_streams(n_decisions)
    adm.decide_batch(mgr_d, stream)  # compile outside the timed region
    mgr_d, _ = _admission_streams(n_decisions)  # fresh state for the timed run
    t0 = time.perf_counter()
    dev_dec = adm.decide_batch(mgr_d, stream)
    us_dev = 1e6 * (time.perf_counter() - t0) / n_decisions
    if dev_dec != host_dec:  # the property test pins this; fail loudly here
        raise AssertionError("device admission diverged from host loop")

    print(f"== serve loop ({n_reqs} requests x {new_tokens} new tokens, "
          f"tenants {dict(zip(TENANTS, QUOTAS))}) ==")
    print(f"host-orchestrated loop: {rps_host:6.2f} req/s ({dt_host:.2f}s)")
    print(f"fully-jitted loop:      {rps_jit:6.2f} req/s ({dt_jit:.2f}s)  "
          f"[{rps_jit / rps_host:.2f}x]")
    print(f"admission ({n_decisions} decisions, bit-identical): "
          f"host {us_host:.2f} us/decision, "
          f"device batch {us_dev:.2f} us/decision "
          f"[{us_host / max(us_dev, 1e-9):.1f}x]")

    if out_lines is not None:
        out_lines.append(
            f"serve_loop_jit,{1e6 / rps_jit:.0f},{rps_jit:.2f}_req_per_s")
        out_lines.append(
            f"serve_loop_host,{1e6 / rps_host:.0f},{rps_host:.2f}_req_per_s")
        out_lines.append(
            f"admission_device,{us_dev:.2f},{us_host:.2f}_us_host")
    if sweep_json is not None:
        record = {
            "n_requests": n_reqs,
            "new_tokens": new_tokens,
            "requests_per_sec": {"jit_loop": round(rps_jit, 2),
                                 "host_loop": round(rps_host, 2)},
            "speedup_jit_vs_host": round(rps_jit / rps_host, 3),
            "admission_us_per_decision": {"host": round(us_host, 2),
                                          "device_batch": round(us_dev, 2)},
            "admission_bit_identical": True,
        }
        base = {}
        if os.path.exists(sweep_json):
            with open(sweep_json) as fh:
                base = json.load(fh)
        base["serve_loop"] = record
        with open(sweep_json, "w") as fh:
            json.dump(base, fh, indent=2)
            fh.write("\n")
        print(f"(serve_loop record merged into {sweep_json})")


if __name__ == "__main__":
    run()
