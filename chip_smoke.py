"""Chip smoke test: drive the sweep engine and paged-KV serving once on a TPU
through their normal entry points, check what comes out, and print one JSON
line.

    python chip_smoke.py              # one chip: the sweep and serve phases
    python chip_smoke.py --chips 4    # the rows-mesh sharded sweep, 4 chips

Phases (one chip):

* sweep: ``simulate_trace_batched`` over the six device policies x the
  Table-1 capacities, on ``paper_trace()`` and on a grid of 64 seeded
  100 000-access traces (zipf, scan-polluted, Markov working sets).  Every
  hit of the paper trace, and of one grid trace, must equal the host
  oracles' bit for bit, and the compiled scan must hold the Pallas victim
  kernel (``tpu_custom_call``).
* serve: ``ServeEngine`` on the published SmolLM-360M config with random
  bf16 weights, a bounded paged-KV pool of 64 pages x 64 tokens, and 8
  requests of 4096 prompt + 256 new tokens, so the pool is full when decode
  starts and every page boundary evicts.  ``awrp`` and ``arc_adaptive``,
  each unfused and through the fused policy-attention kernel; every
  request must return 256 tokens, every page boundary must evict, and the
  fused decode loop must hold the kernel.  Fused and unfused engines are
  compared token by token for information (their attention arithmetic
  differs); the policy is held to bitwise equality by a teacher-forced
  check: 256 steps from the prefilled pool through the fused kernel and
  through the unfused chain fed the kernel's page mass.

``--chips 4`` runs the sharded sweep on a 4-device rows mesh and compares
it bit for bit with the same sweep on one device; it runs nothing else.

Weights and traces come from ``--seed``.  The process exits non-zero, and
prints no result line, when JAX finds no TPU or any check fails.  Compile
and run seconds, accesses/s and tokens/s are printed for information; they
are single runs, not measurements.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

POLICIES = ("awrp", "lru", "fifo", "lfu", "arc", "car")
CAPS = (30, 60, 90, 120, 150, 180, 210, 240)  # the paper's Table-1 frames
GRID_TRACES, GRID_ACCESSES = 64, 100_000
SERVE_POLICIES = ("awrp", "arc_adaptive")
REQUESTS, PROMPT, NEW_TOKENS, POOL_PAGES = 8, 4096, 256, 64
MASS_TOL = 2.0**-12  # kernel page mass vs the float32 reference, relative


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    """Fail the run (non-zero exit, no result line) unless ``ok``."""
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")
    log(f"  ok: {what}")


def host_hits(policy: str, trace: np.ndarray, cap: int) -> np.ndarray:
    """Per-access hits of the host oracle (``repro.core.policies``)."""
    from repro.core.policies import make_policy

    pol = make_policy(policy, cap)
    return np.fromiter((pol.access(b) for b in trace.tolist()), bool,
                       len(trace))


def grid_traces(seed: int, n: int, length: int) -> np.ndarray:
    """``n`` seeded traces cycling through the zipf, scan-mix and Markov
    generators of ``repro.core.traces``."""
    from repro.core.traces import trace_markov, trace_scan_mix, trace_zipf

    gens = (trace_zipf, trace_scan_mix, trace_markov)
    return np.stack([gens[i % 3](length, seed=seed * 1000 + i)
                     for i in range(n)])


def sweep_vs_oracle(hits: np.ndarray, trace: np.ndarray, tag: str) -> None:
    """``hits`` (P, C, T) from the device engine against the host oracles,
    access by access."""
    for pi, p in enumerate(POLICIES):
        for ci, c in enumerate(CAPS):
            ref = host_hits(p, trace, c)
            if not np.array_equal(hits[pi, ci], ref):
                bad = int(np.argmax(hits[pi, ci] != ref))
                check(False, f"{tag}: {p}@{c} differs from the host oracle "
                      f"at access {bad}")
    check(True, f"{tag}: {len(POLICIES)}x{len(CAPS)} configs x "
          f"{trace.size} accesses equal the host oracles bit for bit")


def sweep_phase(seed: int, n_traces: int, length: int,
                kernel: bool) -> None:
    from repro.core.jax_policies import (
        _simulate_batched_impl,
        simulate_trace_batched,
    )
    from repro.core.traces import paper_trace

    scan = _simulate_batched_impl.sentinel
    log("[sweep] paper_trace")
    tr = paper_trace(seed=seed)
    hits = np.asarray(simulate_trace_batched(tr, POLICIES, CAPS))[0]
    log(f"  compile_s={scan.last_trace_s:.3f}")
    sweep_vs_oracle(hits, tr, "paper_trace")
    if kernel:
        check("tpu_custom_call" in scan.compiled_text(),
              "compiled sweep scan holds the Pallas awrp_select_rows kernel")

    log(f"[sweep] grid: {n_traces} traces x {length} accesses x "
        f"{len(POLICIES)} policies x {len(CAPS)} capacities")
    t0 = time.perf_counter()
    traces = grid_traces(seed, n_traces, length)
    log(f"  traces made in {time.perf_counter() - t0:.3f}s")
    t0 = time.perf_counter()
    hits = simulate_trace_batched(traces, POLICIES, CAPS)
    counts = np.asarray(hits.sum(-1))
    first = time.perf_counter() - t0
    log(f"  first call {first:.3f}s (compile_s={scan.last_trace_s:.3f})")
    t0 = time.perf_counter()
    again = simulate_trace_batched(traces, POLICIES, CAPS)
    counts_again = np.asarray(again.sum(-1))
    run = time.perf_counter() - t0
    check(np.array_equal(counts, counts_again), "a second run repeats the "
          "first hit counts")
    log(f"  second call {run:.3f}s: "
        f"{traces.size * len(POLICIES) * len(CAPS) / run:.0f} accesses/s "
        f"(one unrepeated run; information only)")
    k = 1  # a scan-polluted trace
    sweep_vs_oracle(np.asarray(hits[k]), traces[k], f"grid trace {k}")
    ratios = counts.sum(axis=(0, 2)) / (traces.size * len(CAPS))
    log("  mean hit ratio: " + " ".join(
        f"{p}={r:.4f}" for p, r in zip(POLICIES, ratios)))


def serve_run(cfg, params, prompts: np.ndarray, new_tokens: int,
              fused: bool, kernel: bool) -> dict:
    """One engine, one batch of requests, twice (the second run times the
    compiled programs).  Returns tokens and KV telemetry of the first."""
    from repro.serve.engine import Request, ServeEngine

    B, S = prompts.shape
    eng = ServeEngine(cfg, params, max_len=S + new_tokens, kv_mode="paged",
                      fused=fused)

    def requests():
        return [Request(i, prompts[i].tolist(), max_new_tokens=new_tokens)
                for i in range(B)]

    t0 = time.perf_counter()
    res = eng.generate(requests())
    first = time.perf_counter() - t0
    tel = eng.telemetry()
    tokens = [res[i].tokens for i in range(B)]
    check(all(len(t) == new_tokens for t in tokens),
          f"all {B} requests returned {new_tokens} tokens")
    log(f"  first generate {first:.3f}s (compile_s: prefill="
        f"{eng._prefill.sentinel.last_trace_s:.3f} decode_loop="
        f"{eng._loop_sentinel.last_trace_s:.3f})")
    t0 = time.perf_counter()
    res2 = eng.generate(requests())
    run = time.perf_counter() - t0
    log(f"  second generate {run:.3f}s: {B * new_tokens / run:.1f} tok/s "
        f"(prefill included; one unrepeated run; information only)")
    check(all(res2[i].tokens == tokens[i] for i in range(B)),
          "a second generate repeats the tokens")
    if fused and kernel:
        check("tpu_custom_call" in eng._loop_sentinel.compiled_text(),
              "compiled decode loop holds the fused policy-attention kernel")
    out = {"tokens": np.asarray(tokens), "hits": int(tel["kv/pool/hits"]),
           "evictions": int(tel["kv/pool/evictions"])}
    log(f"  kv/pool/hits={out['hits']} kv/pool/evictions="
        f"{out['evictions']}")
    return out


def policy_step_check(cfg, params, prompts: np.ndarray, steps: int,
                      seed: int) -> None:
    """Teacher-forced check of the fused kernel at the serve shapes.

    From the first layer's prefilled pool, ``steps`` decode steps run
    through the fused kernel and through the unfused policy chain
    (``insert_token`` + ``score_update``, or their adaptive pair) fed the
    kernel's own page mass, with the same random queries and K/V rows.
    Every pool plane — K/V, F/R/page starts/clock/open slot and the adaptive
    directory — must stay bitwise equal, and the kernel's attention must
    match the float32 reference (``kernels.ref``) to bf16 rounding."""
    import jax
    import jax.numpy as jnp

    from repro.cache import paged_kv
    from repro.kernels.ref import ref_paged_attention
    from repro.models import model as M

    B, S = prompts.shape
    page, P = cfg.page_size, cfg.bounded_kv_pages
    KVH, hd = cfg.n_kv_heads, cfg.head_dim
    G = cfg.n_heads // KVH
    _, caches = jax.jit(lambda p, t: M.prefill(
        p, cfg, {"tokens": t}, max_len=S + steps, kv_mode="paged"))(
        params, jnp.asarray(prompts, jnp.int32))
    pool0 = jax.tree.map(lambda x: x[0],
                         next(iter(caches["blocks"].values())))
    adaptive = cfg.kv_policy in paged_kv.TRUE_ADAPTIVE_KV
    core = paged_kv.adaptive_core(cfg.kv_policy, B, P) if adaptive else None
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    dt = jnp.dtype(cfg.dtype)
    xs = (jax.random.normal(kq, (steps, B, KVH, G, hd)).astype(dt),
          jax.random.normal(kk, (steps, B, KVH * hd)).astype(dt),
          jax.random.normal(kv, (steps, B, KVH * hd)).astype(dt),
          S + jnp.arange(steps, dtype=jnp.int32))

    def step(carry, x):
        fused, unfused = carry
        q, nk, nv, pos = x
        if adaptive:
            out, mass, fused = paged_kv.fused_adaptive_decode_step(
                fused, q, nk, nv, pos, page, core)
            ins = paged_kv.adaptive_insert_token(unfused, nk, nv, pos, page,
                                                 core)
            pool = ins.pool
        else:
            out, mass, fused = paged_kv.fused_decode_step(
                fused, q, nk, nv, pos, page, cfg.kv_policy)
            ins = pool = paged_kv.insert_token(unfused, nk, nv, pos, page,
                                               policy=cfg.kv_policy)
        # the kernel's page mass as per-row mass: page sums stay exact
        rows = jnp.zeros((B, P, page), jnp.float32).at[:, :, 0].set(mass)
        rows = rows.reshape(B, P * page)
        unfused = (paged_kv.adaptive_score_update(ins, rows, page, core)
                   if adaptive else paged_kv.score_update(ins, rows, page))
        same = jnp.all(jnp.stack([
            jnp.array_equal(a, b) for a, b in
            zip(jax.tree.leaves(fused), jax.tree.leaves(unfused))]))
        ref_out, ref_mass = ref_paged_attention(
            q, pool.k.reshape(B, P, page, KVH, hd),
            pool.v.reshape(B, P, page, KVH, hd), pool.page_start,
            jnp.full((B,), pos))
        out_err = jnp.max(jnp.abs(out.astype(jnp.float32)
                                  - ref_out.astype(jnp.float32)))
        out_scale = jnp.max(jnp.abs(ref_out.astype(jnp.float32)))
        # page mass relative to itself, floored at the reference threshold
        # tau = 1/resident it is compared with
        resident = (pool.page_start >= 0).sum(-1, keepdims=True)
        scale = jnp.maximum(ref_mass, 1.0 / jnp.maximum(resident, 1))
        mass_rel = jnp.max(jnp.abs(mass - ref_mass) / scale)
        return (fused, unfused), (same, out_err / out_scale, mass_rel,
                                  ref_mass, scale)

    run = jax.jit(lambda c, x: jax.lax.scan(step, c, x))
    _, ys = run((pool0, pool0), xs)
    same, out_rel, mass_rel, ref_mass, scale = map(np.asarray, ys)
    # the control the mass limit must refuse: the reference mass rounded to
    # bf16, on the host (under jit XLA may drop a float32 -> bf16 -> float32
    # round trip as excess precision)
    bf16_mass = ref_mass.astype(jnp.bfloat16).astype(np.float32)
    bf16_rel = np.abs(bf16_mass - ref_mass) / scale
    boundaries = sum(1 for pos in range(S, S + steps) if pos % page == 0)
    log(f"  teacher-forced: max attention error {out_rel.max():.3e} of its "
        f"scale, max page-mass error {mass_rel.max():.3e} of the mass "
        f"(bf16-rounded reference mass: {bf16_rel.max():.3e})")
    check(bool(same.all()), f"{cfg.kv_policy}: {steps} teacher-forced steps "
          f"({boundaries} evicting page boundaries): fused kernel and unfused "
          f"chain keep every pool plane bitwise equal")
    # the output: one bf16 rounding (8 significant bits)
    check(out_rel.max() <= 2.0**-7, f"{cfg.kv_policy}: kernel attention "
          f"matches the float32 reference to bf16 rounding")
    # the mass: float32 softmax sums over 4096 rows stay far inside 2**-12,
    # while one bf16 rounding (up to 2**-9 of the value) does not
    check(mass_rel.max() <= MASS_TOL < bf16_rel.max(),
          f"{cfg.kv_policy}: kernel page mass within {MASS_TOL:.3e} of the "
          f"float32 reference, which a bf16 mass exceeds")


def serve_phase(cfg, seed: int, n_requests: int, prompt: int,
                new_tokens: int, pages: int, kernel: bool) -> None:
    import jax

    from repro.models import model as M

    page = cfg.page_size
    check(prompt == pages * page, f"a {prompt}-token prompt fills the "
          f"{pages}-page pool")
    log(f"[serve] {cfg.name}: {n_requests} requests x {prompt} prompt + "
        f"{new_tokens} new tokens, pool {pages} pages x {page}")
    t0 = time.perf_counter()
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    log(f"  params: {sum(x.size for x in jax.tree.leaves(params))} "
        f"({time.perf_counter() - t0:.3f}s)")
    prompts = np.random.RandomState(seed).randint(
        1, cfg.vocab, size=(n_requests, prompt))
    # allocations during decode (the first token comes from prefill), one
    # eviction each per layer and sequence: the pool starts full
    boundaries = sum(1 for pos in range(prompt, prompt + new_tokens - 1)
                     if pos % page == 0)
    want_evictions = boundaries * cfg.n_layers * n_requests
    for policy in SERVE_POLICIES:
        pcfg = dataclasses.replace(cfg, kv_policy=policy,
                                   bounded_kv_pages=pages)
        runs = {}
        for fused in (False, True):
            log(f"[serve] kv_policy={policy} fused={fused}")
            runs[fused] = serve_run(pcfg, params, prompts, new_tokens, fused,
                                    kernel)
            check(runs[fused]["evictions"] == want_evictions,
                  f"{want_evictions} evictions: every page boundary evicts")
        # the engines' attention arithmetic differs (the unfused path rounds
        # scores and probabilities to bf16, the kernel keeps float32 and
        # accumulates page by page), so greedy tokens part at the first
        # near-tie and the pools then see different inputs: reported here,
        # while the policy itself is held to bitwise equality below
        u, f = runs[False], runs[True]
        same = u["tokens"] == f["tokens"]
        diverge = [int(np.argmin(row)) for row in same if not row.all()]
        log(f"  fused vs unfused engines: {int(same.all(axis=1).sum())}/"
            f"{n_requests} requests with equal tokens, {int(same.sum())}/"
            f"{same.size} tokens equal, first differing token per diverged "
            f"request {diverge}; kv/pool/hits {u['hits']} vs {f['hits']}")
        log(f"[serve] kv_policy={policy}: teacher-forced kernel check")
        policy_step_check(pcfg, params, prompts, new_tokens, seed)


def sharded_phase(seed: int, n_devices: int, n_traces: int, length: int,
                  kernel: bool) -> None:
    import jax

    from repro.core import sharding
    from repro.core.jax_policies import (
        _simulate_batched_impl,
        simulate_trace_batched,
    )

    check(len(jax.devices()) >= n_devices, f"{n_devices} devices present")
    mesh = sharding.rows_mesh(n_devices)
    scan = _simulate_batched_impl.sentinel
    log(f"[sharded] rows mesh over {n_devices} devices: {n_traces} traces x "
        f"{length} accesses x {len(POLICIES)} policies x {len(CAPS)} "
        f"capacities")
    traces = grid_traces(seed, n_traces, length)
    t0 = time.perf_counter()
    one = np.asarray(simulate_trace_batched(traces, POLICIES, CAPS))
    log(f"  one device: {time.perf_counter() - t0:.3f}s "
        f"(compile_s={scan.last_trace_s:.3f})")
    t0 = time.perf_counter()
    hits = simulate_trace_batched(traces, POLICIES, CAPS, mesh=mesh)
    hits.block_until_ready()
    log(f"  mesh: {time.perf_counter() - t0:.3f}s "
        f"(compile_s={scan.last_trace_s:.3f})")
    check(np.array_equal(np.asarray(hits), one),
          "sharded sweep equals the one-device sweep bit for bit")
    shards = {s.device for s in hits.addressable_shards}
    check(len(shards) == n_devices, f"hits held on all {n_devices} devices")
    # the rows landed on the devices: the partitioned scan carries each
    # state group's per-device share of rows, never the whole group
    text = scan.compiled_text()
    W = max(CAPS) + (-max(CAPS)) % 128 if kernel else max(CAPS)
    for group, rows, lanes in (("flat", n_traces * 4 * len(CAPS), W),
                               ("arc", n_traces * len(CAPS), 2 * max(CAPS))):
        per = sharding.pad_rows_to(rows, n_devices) // n_devices
        check(f"s32[{per},{lanes}]" in text
              and f"s32[{rows},{lanes}]" not in text,
              f"{group} state: {per} of {rows} rows per device")
    if kernel:
        check("tpu_custom_call" in text,
              "compiled sharded scan holds the Pallas kernel")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the rows-mesh sharded sweep on four "
                    "chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{dev.platform!r}")
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.configs.smollm_360m import CONFIG
    from repro.launch.compile_cache import enable_compile_cache

    log(f"device: {dev.device_kind} x {len(jax.devices())}; jax "
        f"{jax.__version__}; compile cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(args.seed, 4, GRID_TRACES, GRID_ACCESSES, kernel=True)
    else:
        sweep_phase(args.seed, GRID_TRACES, GRID_ACCESSES, kernel=True)
        serve_phase(CONFIG, args.seed, REQUESTS, PROMPT, NEW_TOKENS,
                    POOL_PAGES, kernel=True)
    log(f"total {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
