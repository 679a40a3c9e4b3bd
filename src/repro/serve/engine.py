"""Batched serving engine: prefill -> decode with AWRP-managed caches.

Production shape (scaled down to run on this CPU container with smoke
configs; the same jitted functions are what the dry-run lowers for the
256/512-chip meshes):

  * length-bucketed batching: requests with equal (page-aligned) prompt
    lengths are batched together — the jitted prefill/decode have one scalar
    position per batch (documented simplification vs fully ragged batching);
  * prompt cache: exact-match prefix reuse through ``cache.PrefixCache``
    (AWRP eviction) — a hit skips prefill entirely;
  * bounded-KV mode: ``kv_mode="paged"`` serves long contexts in a fixed
    page pool with the paper's eviction rule (``cfg.kv_policy`` — including
    the true-adaptive ``arc_adaptive``/``car_adaptive`` pool mode);
  * multi-tenant mode: ``tenants={name: quota}`` mounts the prompt cache as
    one policy-core row per tenant (``serve.tenancy``, DESIGN.md §8) with
    per-tenant accounting, an eviction-pressure admission controller
    (accept / defer / shed) and optional AWRP-ranked quota rebalancing;
  * ghost-hit feed: in the true-adaptive paged mode the engine persists
    each tenant's final pool policy state and, on a prefix-cache miss that
    re-prefills previously evicted page positions, replays those page ids
    through it (``paged_kv.reseed_from_ghosts``) — the cross-request
    re-references that actually move ARC/CAR's ``p`` (DESIGN.md §8);
  * per-policy telemetry from one code path: every cache the engine holds
    is built through the unified policy factory and reports a uniform
    ``telemetry()`` dict under a namespaced key (``prefix/...``,
    ``kv/...``, ``expert/...``) — see ``ServeEngine.telemetry``;
  * fully-jitted decode loop: by default the whole decode loop (decode
    step + sampling + PRNG chain) is ONE jitted program per ``steps``
    bucket (temperature is traced) with the KV caches and PRNG key donated in
    (``donate_argnums`` — XLA reuses the buffers in place), and
    multi-tenant admission runs as one jitted batch scan on the device
    pressure plane; ``jit_loop=False`` restores the host-orchestrated
    per-step loop (the measured baseline) — DESIGN.md §9.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import sharding
from repro.cache import paged_kv
from repro.cache.paged_kv import AdaptivePagedPool
from repro.cache.prefix_cache import PrefixCache
from repro.models import layers as L
from repro.models import model as M
from repro.obs import profiling
from repro.obs.metrics import Derived, Registry, loop_planes, loop_update, safe_ratio
from repro.obs.profiling import Sentinel, TraceCapture
from repro.obs.spans import SpanSet
from repro.serve.sampling import sample, sample_traced
from repro.serve.tenancy import (
    DEFER,
    SHED,
    AdmissionController,
    TenantPrefixCache,
)


@dataclasses.dataclass
class Request:
    """One generation request: ``prompt`` token ids (page-aligned by the
    engine), a per-request decode budget and sampling temperature, and the
    ``tenant_id`` admission/quota accounting charges it to (ignored by
    single-tenant engines)."""

    rid: int
    prompt: List[int]
    max_new_tokens: int = 16
    temperature: float = 0.0
    tenant_id: str = "default"


@dataclasses.dataclass
class Result:
    """Outcome of one request.  ``status`` is the admission trajectory:
    ``"ok"`` ran in the first pass, ``"deferred"`` was pushed behind the
    unpressured work but completed (tokens and telemetry identical to an
    ``"ok"`` run of the same stream), ``"shed"`` was refused — no tokens,
    and NO cache or tenancy state was touched on its behalf."""

    rid: int
    tokens: List[int]
    prefill_cached: bool
    latency_s: float
    status: str = "ok"  # "ok" | "deferred" | "shed"


def _is_apool(x) -> bool:
    return isinstance(x, AdaptivePagedPool)


def _kv_step(old_caches, new_caches) -> jax.Array:
    """One decode step's row of counts: paged-KV ``[hits, evictions]``
    (int32, bounded by layers x sequences x pages), then, for a model with
    expert layers, the step's ``[held_pairs, experts_hit]``
    (``caches["moe"]``); the engine sums the steps on the host."""
    row = jnp.stack(paged_kv.step_counts(old_caches, new_caches))
    if "moe" in new_caches:
        row = jnp.concatenate([row, new_caches["moe"]])
    return row


class ServeEngine:
    """Continuous-batching serving engine over AWRP-managed caches.

    Two decode-loop modes (DESIGN.md §9):

    * ``jit_loop=True`` (default) — ONE jitted program per ``steps``
      bucket (temperature traced) runs the whole decode loop on device (``lax.scan`` of
      decode+sample), with the KV caches and the PRNG key DONATED into it
      (``jax.jit(..., donate_argnums=...)``): XLA reuses the cache buffers
      in place, and host code only marshals inputs/outputs.  Admission for
      multi-tenant engines runs as one jitted batch scan
      (``AdmissionController.decide_batch``) on the device pressure plane.
    * ``jit_loop=False`` — the host-orchestrated per-step loop (one jitted
      decode step per token, sampling and admission on host).  Kept as the
      measured baseline for ``benchmarks/serve_loop_bench.py``; token
      streams across the two modes agree in sampling LOGIC but are not
      asserted bit-identical (scan-compiled vs per-call numerics).

    State mutated per ``generate`` call: ``self.key`` (PRNG chain),
    ``self.stats``, the prefix/tenant caches, and (true-adaptive paged
    mode) the per-tenant KV ghost sessions.  Donation means a stored
    prefix payload is never aliased with loop buffers — payloads are
    snapshotted on insert and on hit (see ``_run_bucket``)."""

    def __init__(self, cfg, params, *, max_len: int = 512,
                 kv_mode: str = "full", prefix_cache_entries: int = 8,
                 prefix_policy: str = "awrp", expert_cache=None, seed: int = 0,
                 tenants: Optional[Dict[str, int]] = None,
                 admission: Optional[AdmissionController] = None,
                 auto_rebalance: bool = False, jit_loop: bool = True,
                 mesh=None, fused: bool = False, metrics: bool = True,
                 decision_trace: int = 0, profile_dir: Optional[str] = None,
                 profile_every: int = 16, profile_phases: bool = False):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.kv_mode = kv_mode
        #: route paged-KV decode blocks through the fused policy-attention
        #: Pallas kernels (kernels/policy_attn.py) — victim selection, KV
        #: gather and the score update in one launch, decisions bit-identical
        #: to the unfused path; interpret-mode fallback on CPU
        self.fused = bool(fused)
        self.tenants = dict(tenants) if tenants else None
        self.auto_rebalance = bool(auto_rebalance)
        #: optional core.sharding rows mesh: KV caches (and the tenant rows)
        #: are placed across it by their batch axis, and the donated decode
        #: loop then keeps the buffers device-resident under that placement
        #: for its whole scan (donation reuses the sharded buffers in place)
        self.mesh = mesh
        if decision_trace and self.tenants is None:
            raise ValueError(
                "decision_trace records the tenancy core's per-access "
                "events; construct the engine with tenants={...}"
            )
        if self.tenants is None:
            # prefix_policy may be a name or a prebuilt policy instance —
            # both resolve through the unified factory inside PrefixCache
            self.prefix_cache = PrefixCache(prefix_cache_entries, prefix_policy)
            self.tenant_cache = None
            self.admission = None
        else:
            self.prefix_cache = None
            self.tenant_cache = TenantPrefixCache(
                self.tenants, prefix_policy, mesh=mesh,
                ring_capacity=int(decision_trace),
            )
            self.admission = admission or AdmissionController()
        #: optional ExpertCacheRuntime the model's MoE router reports into
        self.expert_cache = expert_cache
        self.key = jax.random.PRNGKey(seed)
        self.jit_loop = bool(jit_loop)
        # compile/retrace sentinels (obs.profiling, DESIGN.md §12) around
        # every jitted entry point the engine builds: trace counts, cache
        # sizes, trace wall time and jaxpr eqn audits surface under
        # compile/<fn>/... in telemetry()
        self._prefill = profiling.instrument(
            "prefill",
            lambda p, b: M.prefill(p, cfg, b, max_len=max_len, kv_mode=kv_mode)
        )
        self._decode = profiling.instrument(
            "decode_step",
            lambda p, t, c: M.decode_step(p, cfg, t, c, kv_mode=kv_mode,
                                          fused=self.fused, mesh=mesh)
        )
        #: jitted whole-decode-loop programs, one per steps bucket
        #: (temperature is a traced operand — no retrace per temperature);
        #: ONE shared sentinel across the buckets, so compile/decode_loop/
        #: count is the engine-wide loop trace total and cache_size the
        #: total compiled-bucket count
        self._loops: Dict[int, object] = {}
        self._loop_sentinel = Sentinel("decode_loop", scopes=L.SERVE_SCOPES)
        self.stats = {"prefills": 0, "decode_steps": 0, "tokens": 0,
                      "shed": 0, "deferred": 0, "kv_ghost_hits": 0,
                      "rebalances": 0}
        #: ghost-hit feed: per-tenant persisted pool policy states (one list
        #: entry per AdaptivePagedPool node of the cache tree, in traversal
        #: order) + per-tenant ghost-hit counters
        self._kv_sessions: Dict[str, list] = {}
        self._kv_ghost_hits: Dict[str, int] = {}
        # -- observability layer (DESIGN.md §11) ----------------------------
        #: loop-metric planes carried through the jitted decode loop (or
        #: folded per step by the host loop — same jitted update, so the
        #: planes are bit-identical across the modes); None with metrics off
        self.metrics = bool(metrics)
        self._planes = loop_planes() if self.metrics else None
        self._fold = jax.jit(functools.partial(loop_update, vocab=cfg.vocab))
        #: paged-KV page hits (paper reference rule) and evictions, summed
        #: over layers, sequences and decode steps: the decode loops emit
        #: per-step counts and the host adds them here as Python ints, so
        #: the totals never wrap however long the engine serves
        self._kv_counts = {"hits": 0, "evictions": 0}
        #: expert-layer work of models that have it (``caches["moe"]``):
        #: token-expert pairs routed to the experts held here and held
        #: experts with at least one pair, summed over layers and calls —
        #: prefill from its caches, decode from the loops' per-step rows
        self._moe_counts = (
            {ph: {"held_pairs": 0, "experts_hit": 0}
             for ph in ("prefill", "decode")}
            if M.has_expert_counts(cfg) else None)
        self._kv_step = jax.jit(_kv_step)
        #: host timing spans around the serving sections (prefill / decode /
        #: rebalance / trace_drain) — mounted on the registry like the
        #: caches.  ``profile_phases=True`` turns on the sync discipline:
        #: each phase blocks on its own outputs at close so the timing
        #: isolates that phase's device time (obs.spans module docstring)
        self.spans = SpanSet(sync=bool(profile_phases))
        #: opt-in jax.profiler capture: one annotated device trace per
        #: ``profile_every`` requests under ``profile_dir`` (DESIGN.md §12)
        self._capture = (
            TraceCapture(profile_dir, profile_every)
            if profile_dir else None
        )
        #: the unified metrics registry: every telemetry surface the engine
        #: holds mounts a provider; ``telemetry()`` is ONE flat snapshot
        #: with a single batched device pull (zero per-step syncs)
        self.registry = Registry()
        self._mount_providers()

    # -- internals ----------------------------------------------------------
    def _align(self, prompt: List[int]) -> List[int]:
        """Page-align by left-trimming (bounded-KV mode needs page-aligned
        prefill; full mode aligns too for bucket reuse)."""
        page = self.cfg.page_size
        n = max((len(prompt) // page) * page, page)
        if len(prompt) < page:
            prompt = [0] * (page - len(prompt)) + prompt  # left-pad
        return prompt[-n:]

    def _batch_prefill(self, prompts: List[List[int]]):
        tokens = jnp.asarray(np.stack(prompts), jnp.int32)
        batch = {"tokens": tokens}
        if self.cfg.family == "vlm":
            B = tokens.shape[0]
            batch["patches"] = jnp.zeros(
                (B, self.cfg.n_patch_tokens, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype))
        if self.cfg.family == "encdec":
            B, S = tokens.shape
            batch["frames"] = jnp.zeros(
                (B, S // self.cfg.enc_seq_divisor, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype))
        with self.spans.span("prefill") as sp:
            logits, caches = self._prefill(self.params, batch)
            sp.ready(logits)  # sync mode: time prefill's own device work
        self.stats["prefills"] += 1
        return logits, caches

    # -- the jitted decode loop (DESIGN.md §9) ------------------------------
    def _get_loop(self, steps: int):
        """The jitted decode-loop program for this ``steps`` bucket: greedy
        first token from the prefill logits, then ``steps - 1`` scanned
        decode+sample iterations.  ``caches`` and ``key`` are DONATED — the
        caller must treat the passed-in values as consumed and use only the
        returned ones (stored prefix payloads are snapshotted around this,
        see ``_run_bucket``).  ``temperature`` is a TRACED loop operand
        (``sample_traced``), so only ``steps`` buckets compile — previously
        every (steps, temperature) pair retraced the whole loop."""
        k = int(steps)
        loop = self._loops.get(k)
        if loop is None:
            loop = self._build_loop(k)
            self._loops[k] = loop
        return loop

    def _build_loop(self, steps: int):
        cfg, kv_mode = self.cfg, self.kv_mode
        fused, mesh = self.fused, self.mesh

        if self.metrics:
            # metrics variant: the loop planes are one more donated carry —
            # folded after every sampling event (first greedy token
            # included) by the same jitted `loop_update` the host loop
            # applies per step, so the planes are bit-identical across the
            # loop modes (integer adds / scatter-adds only).  The paged-KV
            # counts leave the scan per step (``_kv_step``)
            def loop(params, logits, caches, key, temperature, planes):
                toks = sample(logits[:, -1:], key, temperature=0.0,
                              vocab=cfg.vocab)
                planes = loop_update(planes, toks, vocab=cfg.vocab)

                def body(carry, _):
                    t, c, k, pl = carry
                    k, sub = jax.random.split(k)
                    lg, c2 = M.decode_step(params, cfg, t, c, kv_mode=kv_mode,
                                           fused=fused, mesh=mesh)
                    t = sample_traced(lg, sub, temperature, vocab=cfg.vocab)
                    pl = loop_update(pl, t, vocab=cfg.vocab)
                    return (t, c2, k, pl), (t, _kv_step(c, c2))

                (_, caches, key, planes), (ys, kv) = jax.lax.scan(
                    body, (toks, caches, key, planes), None, length=steps - 1
                )
                gen = jnp.concatenate(
                    [toks, jnp.moveaxis(ys[..., 0], 0, 1)], axis=1
                )
                return gen, caches, key, planes, kv

            return self._loop_sentinel.wrap(loop, donate_argnums=(2, 3, 5))

        def loop(params, logits, caches, key, temperature):
            toks = sample(logits[:, -1:], key, temperature=0.0,
                          vocab=cfg.vocab)

            def body(carry, _):
                t, c, k = carry
                k, sub = jax.random.split(k)
                lg, c = M.decode_step(params, cfg, t, c, kv_mode=kv_mode,
                                      fused=fused, mesh=mesh)
                t = sample_traced(lg, sub, temperature, vocab=cfg.vocab)
                return (t, c, k), t

            (_, caches, key), ys = jax.lax.scan(
                body, (toks, caches, key), None, length=steps - 1
            )
            # ys: (steps-1, B, 1) -> (B, steps-1); prepend the first token
            gen = jnp.concatenate([toks, jnp.moveaxis(ys[..., 0], 0, 1)],
                                  axis=1)
            return gen, caches, key

        return self._loop_sentinel.wrap(loop, donate_argnums=(2, 3))

    # -- ghost-hit feed (true-adaptive paged KV, DESIGN.md §8) --------------
    @property
    def _ghost_feed_on(self) -> bool:
        return (self.kv_mode == "paged"
                and self.cfg.kv_policy in paged_kv.TRUE_ADAPTIVE_KV)

    def _kv_reseed(self, caches, tenant: str, plen: int):
        """On a re-prefill, replay the prefilled page ids through the
        tenant's persisted pool policy state: previously evicted positions
        ghost-hit and adapt ``p``; the rebuilt state seeds the new pool."""
        prev = self._kv_sessions.get(tenant)
        if prev is None:
            return caches
        page, P = self.cfg.page_size, self.cfg.bounded_kv_pages
        n_have = plen // page
        n_res = min(n_have, P)
        it = iter(prev)

        def reseed(x):
            if not _is_apool(x):
                return x
            state, gh = paged_kv.reseed_from_ghosts(
                next(it), self.cfg.kv_policy, P, n_have, n_res)
            n = int(np.asarray(gh).sum())
            self.stats["kv_ghost_hits"] += n
            self._kv_ghost_hits[tenant] = self._kv_ghost_hits.get(tenant, 0) + n
            return AdaptivePagedPool(pool=x.pool, policy=state)

        return jax.tree.map(reseed, caches, is_leaf=_is_apool)

    def _kv_persist(self, caches, tenant: str) -> None:
        """Persist the request's final pool policy states (ghost lists, p)
        so the tenant's next re-prefill can replay into them."""
        states = []
        jax.tree.map(
            lambda x: states.append(x.policy) if _is_apool(x) else None,
            caches, is_leaf=_is_apool)
        if states:
            self._kv_sessions[tenant] = states

    # -- observability mounts (DESIGN.md §11) -------------------------------
    def _mount_providers(self) -> None:
        """Mount every telemetry surface the engine holds onto the unified
        registry.  Providers read ``self`` dynamically (an expert cache
        attached after construction appears at the next snapshot) and
        return device arrays UN-pulled — the snapshot's single batched
        ``device_get`` is the only sync."""
        self.registry.mount("serve", self._serve_provider)
        if self.tenants is None:
            self.registry.mount(
                "prefix", lambda: self.prefix_cache.telemetry()
            )
        else:
            self.registry.mount("tenant", self._tenant_provider)
        self.registry.mount("kv", self._kv_provider)
        self.registry.mount("moe", lambda: (
            self._moe_counts if self.metrics and self._moe_counts else {}))
        self.registry.mount(
            "expert",
            lambda: (
                self.expert_cache.telemetry()
                if self.expert_cache is not None
                else {}
            ),
        )
        self.registry.mount("span", self.spans.metrics)
        # process-global compile/retrace sentinels (every engine mounts the
        # same aggregation — one series per entry-point name)
        self.registry.mount("compile", profiling.compile_metrics)
        if self._capture is not None:
            self.registry.mount("profiler", self._capture.metrics)

    def _serve_provider(self) -> dict:
        out: dict = dict(self.stats)
        if self._planes is not None:
            out["loop"] = dict(self._planes)
        return out

    def _tenant_provider(self) -> dict:
        mgr = self.tenant_cache.manager
        rows = mgr.row_metrics()  # (rows,) device arrays — not pulled here
        ratio = Derived(lambda g: safe_ratio(g["hits"], g["accesses"]))
        out = {}
        for t in mgr.tenants:
            r = mgr.row(t)
            out[t] = {
                "policy": mgr.policy_name,
                "quota": mgr.quotas[t],
                "entries": len(self.tenant_cache.stores[t]),
                "occupancy": rows["occupancy"][r],
                "hits": rows["hits"][r],
                "misses": rows["misses"][r],
                "evictions": rows["evictions"][r],
                "accesses": rows["accesses"][r],
                "pressure": rows["pressure"][r],
                "hit_ratio": ratio,
            }
        return out

    def _add_kv_counts(self, per_step) -> None:
        """Fold per-step rows (``_kv_step``: ``[hits, evictions]``, then
        the expert counts where the model has them) into the engine's
        Python-int totals (one pull, after the tokens' own)."""
        a = np.asarray(per_step, np.int64)
        tot = a.reshape(-1, a.shape[-1]).sum(0)
        self._kv_counts["hits"] += int(tot[0])
        self._kv_counts["evictions"] += int(tot[1])
        if self._moe_counts is not None:
            self._add_moe_counts("decode", tot[2:])

    def _add_moe_counts(self, phase: str, counts) -> None:
        held_pairs, experts_hit = np.asarray(counts, np.int64)
        self._moe_counts[phase]["held_pairs"] += int(held_pairs)
        self._moe_counts[phase]["experts_hit"] += int(experts_hit)

    def _kv_provider(self) -> dict:
        if self.kv_mode != "paged":
            return {}
        out: dict = {"pool": {"policy": self.cfg.kv_policy,
                              "pages": self.cfg.bounded_kv_pages}}
        if self.metrics:
            out["pool"].update(self._kv_counts)
        for t, states in self._kv_sessions.items():
            tel = [paged_kv.pool_telemetry(s) for s in states]
            out[t] = {
                "policy": self.cfg.kv_policy,
                "ghost_hits": self._kv_ghost_hits.get(t, 0),
                "p_mean": jnp.mean(jnp.stack([x["p_mean"] for x in tel])),
                "p_max": jnp.max(jnp.stack([x["p_max"] for x in tel])),
            }
        return out

    # -- public -------------------------------------------------------------
    def telemetry(self) -> Dict[str, object]:
        """ONE flat namespaced snapshot of every metrics surface the engine
        serves from (``Registry.snapshot`` — DESIGN.md §11): engine counters
        and decode-loop planes under ``serve/...``, the prompt cache under
        ``prefix/...`` (single-tenant) or ``tenant/<name>/...``, the paged
        KV pool and ghost-hit feed under ``kv/...``, the MoE expert cache
        under ``expert/...``, host timing spans under ``span/...``, and any
        OPT-regret gauges (``opt_regret()``).  Every per-tenant hit ratio is
        the exact float64 division of the pulled int counters; the whole
        snapshot costs exactly one batched ``jax.device_get``."""
        return self.registry.snapshot()

    def drain_decision_trace(self) -> np.ndarray:
        """Pull the decision-trace ring (``decision_trace=N`` engines) to
        host as a structured record array — chronological per-access and
        per-admission policy events (``obs.decision_trace``)."""
        if self.tenants is None:
            raise ValueError("decision tracing needs a multi-tenant engine")
        with self.spans.span("trace_drain"):
            return self.tenant_cache.manager.drain_trace()

    def opt_regret(self) -> Dict[str, dict]:
        """OPT-regret telemetry: drain the decision trace, replay each
        tenant's recorded key stream through the offline Belady oracle at
        that tenant's quota, and publish ``opt − observed`` hit-ratio regret
        as sticky registry gauges (``tenant/<t>/opt_regret`` plus the
        access-weighted ``policy/<name>/opt_regret``).  Returns the detailed
        per-tenant numbers (``obs.opt_oracle.regret_from_records``)."""
        from repro.obs.opt_oracle import regret_from_records

        records = self.drain_decision_trace()
        mgr = self.tenant_cache.manager
        caps = {mgr.row(t): mgr.quotas[t] for t in mgr.tenants}
        per_row, aggregate = regret_from_records(records, caps)
        out = {}
        for t in mgr.tenants:
            info = per_row[mgr.row(t)]
            self.registry.set_gauge(f"tenant/{t}/opt_regret", info["regret"])
            out[t] = info
        self.registry.set_gauge(
            f"policy/{mgr.policy_name}/opt_regret", aggregate["regret"]
        )
        out["aggregate"] = aggregate
        return out

    def _admit(self, requests: List[Request]) -> List[str]:
        """Admission decisions for ``requests`` in order, with the
        decay-on-shed probation credit applied.  ``jit_loop`` engines run
        one jitted device scan (``decide_batch`` — decides AND decays);
        host engines run the per-request host loop.  Both paths are
        bit-identical on identical streams (the parity property test)."""
        mgr = self.tenant_cache.manager
        if self.jit_loop:
            return self.admission.decide_batch(
                mgr, [r.tenant_id for r in requests])
        decisions = []
        for r in requests:
            d = self.admission.decide(mgr, r.tenant_id)
            if d == SHED:
                # refused work is probation time: decay the EWMA so a
                # shed tenant can re-enter once its burst has passed
                mgr.decay_pressure(r.tenant_id)
            decisions.append(d)
        return decisions

    def generate(self, requests: List[Request]) -> Dict[int, Result]:
        """Length-bucketed batched generation.  Multi-tenant engines run an
        admission pass first: shed requests return immediately with
        ``status="shed"`` and leave every cache and tenancy counter
        untouched; deferred requests run after the unpressured work (shed
        only if their tenant is still at shed pressure by then, otherwise
        completed with ``status="deferred"`` and the exact telemetry an
        accepted run would have produced).  Mutates engine state (PRNG
        chain, stats, caches) — see the class docstring.  With
        ``profile_dir`` set, one batch per ``profile_every`` requests runs
        inside an annotated ``jax.profiler`` capture."""
        if self._capture is None:
            return self._generate(requests)
        with self._capture.maybe(len(requests)):
            return self._generate(requests)

    def _generate(self, requests: List[Request]) -> Dict[int, Result]:
        out: Dict[int, Result] = {}
        for r in requests:
            r.prompt = self._align(r.prompt)

        if self.tenants is None:
            phases = [list(requests)]
        else:
            accepted, deferred = [], []
            for r, decision in zip(requests, self._admit(requests)):
                if decision == SHED:
                    self.stats["shed"] += 1
                    out[r.rid] = Result(rid=r.rid, tokens=[],
                                        prefill_cached=False, latency_s=0.0,
                                        status="shed")
                elif decision == DEFER:
                    self.stats["deferred"] += 1
                    deferred.append(r)
                else:
                    accepted.append(r)
            phases = [accepted, deferred]

        for phase_i, phase in enumerate(phases):
            if phase_i == 1 and phase:
                # deferred retry: shed only if still critical
                kept = []
                for r, decision in zip(phase, self._admit(phase)):
                    if decision == SHED:
                        self.stats["shed"] += 1
                        out[r.rid] = Result(rid=r.rid, tokens=[],
                                            prefill_cached=False,
                                            latency_s=0.0, status="shed")
                    else:
                        kept.append(r)
                phase = kept
            buckets: Dict[int, List[Request]] = {}
            for r in phase:
                buckets.setdefault(len(r.prompt), []).append(r)
            for plen, reqs in sorted(buckets.items()):
                res = self._run_bucket(plen, reqs)
                if phase_i == 1:
                    # deferred-then-completed: same run, same counters —
                    # only the status records the admission trajectory
                    for v in res.values():
                        v.status = "deferred"
                out.update(res)
        return out

    def _maybe_rebalance(self, tenant: str) -> None:
        """AWRP-ranked quota rebalancing: when a tenant's pressure crosses
        the defer threshold, move one quota lane to it from the
        lowest-ranked (coldest) tenant — the paper's eviction rule applied
        to tenants instead of lines."""
        if not (self.auto_rebalance and self.tenants is not None):
            return
        mgr = self.tenant_cache.manager
        if mgr.is_adaptive:
            return  # adaptive quotas are fixed (tenancy module docstring)
        if mgr.pressure(tenant) < self.admission.defer_at:
            return
        coldest = mgr.rank_tenants()[0]
        if coldest == tenant:
            return
        with self.spans.span("rebalance"):
            moved, _ = self.tenant_cache.rebalance(tenant, 1)
        self.stats["rebalances"] += moved

    def _lookup_prefix(self, req: Request):
        if self.tenants is None:
            return self.prefix_cache.lookup(req.prompt)
        return self.tenant_cache.lookup(req.tenant_id, req.prompt)

    def _insert_prefix(self, req: Request, payload) -> None:
        if self.tenants is None:
            self.prefix_cache.insert(req.prompt, payload)
        else:
            self.tenant_cache.insert(req.tenant_id, req.prompt, payload)
            self._maybe_rebalance(req.tenant_id)

    @staticmethod
    def _snapshot(caches):
        """Deep copy of a cache pytree.  Donation makes this load-bearing:
        a stored prefix payload aliased with loop buffers would be
        invalidated the first time the loop consumed it, so payloads are
        snapshotted both on insert (the live caches continue into the
        donated loop) and on hit (an entry can be hit again)."""
        return jax.tree.map(jnp.array, caches)

    def _shard_caches(self, caches, batch: int):
        """Place every cache leaf's batch axis across ``self.mesh``.

        Unit-position leaves are stacked with a leading ``(n_repeats,)``
        dim, so the batch axis is detected per leaf (axis 0 elsewhere,
        axis 1 there); scalars such as ``pos`` and any leaf without a
        batch-sized axis are left replicated.  No-op without a mesh or
        when ``batch`` does not divide the device count (NamedSharding
        placement requires even division — see ``core.sharding``)."""
        if self.mesh is None or batch % self.mesh.devices.size:
            return caches
        mesh = self.mesh

        def place(x):
            if not hasattr(x, "ndim") or x.ndim == 0:
                return x
            if x.shape[0] == batch:
                spec = PartitionSpec(
                    sharding.ROWS_AXIS, *([None] * (x.ndim - 1)))
            elif x.ndim >= 2 and x.shape[1] == batch:
                spec = PartitionSpec(
                    None, sharding.ROWS_AXIS, *([None] * (x.ndim - 2)))
            else:
                return x
            return jax.device_put(x, NamedSharding(mesh, spec))

        return jax.tree.map(place, caches)

    def _run_bucket(self, plen: int, reqs: List[Request]) -> Dict[int, Result]:
        t0 = time.time()
        prompts = [r.prompt for r in reqs]
        max_new = max(r.max_new_tokens for r in reqs)
        single = len(reqs) == 1

        cached = None
        if single:
            cached = self._lookup_prefix(reqs[0])
        if cached is not None:
            logits, caches = cached
            if self.jit_loop:
                caches = self._snapshot(caches)  # loop will consume them
            was_cached = True
        else:
            logits, caches = self._batch_prefill(prompts)
            was_cached = False
            if single:
                if self._ghost_feed_on:
                    # prefix miss -> this prefill re-references page
                    # positions the tenant's previous pool may have evicted
                    caches = self._kv_reseed(caches, reqs[0].tenant_id, plen)
                payload = (
                    (logits, self._snapshot(caches)) if self.jit_loop
                    else (logits, caches)
                )
                self._insert_prefix(reqs[0], payload)

        prefill_counts = None
        if self._moe_counts is not None:
            # the loop carries the step's counts; the prefill's leave here
            # (a prefix hit did no prefill work)
            prefill_counts = None if was_cached else caches["moe"]
            caches = {**caches, "moe": jnp.zeros_like(caches["moe"])}
        caches = self._shard_caches(caches, len(reqs))
        if self.jit_loop:
            loop = self._get_loop(max_new)
            # the span contains the host pull serving itself performs
            # (np.asarray of the tokens) — async dispatch means a span
            # around the bare call would time only enqueue; sync mode
            # additionally blocks on the caches (obs.spans docstring)
            with self.spans.span("decode") as sp:
                if self.metrics:
                    gen_dev, caches, self.key, self._planes, kv = loop(
                        self.params, logits, caches, self.key,
                        jnp.float32(reqs[0].temperature), self._planes)
                else:
                    gen_dev, caches, self.key = loop(
                        self.params, logits, caches, self.key,
                        jnp.float32(reqs[0].temperature))
                sp.ready(caches)
                gen = np.asarray(gen_dev)
            if self.metrics:
                self._add_kv_counts(kv)
            self.stats["decode_steps"] += max_new - 1
        else:
            with self.spans.span("decode"):
                toks = sample(logits[:, -1:], self.key, temperature=0.0,
                              vocab=self.cfg.vocab)
                if self.metrics:
                    self._planes = self._fold(self._planes, toks)
                generated, kv = [toks], []
                for step in range(max_new - 1):
                    self.key, sub = jax.random.split(self.key)
                    prev = caches
                    logits, caches = self._decode(self.params, toks, caches)
                    toks = sample(logits, sub,
                                  temperature=reqs[0].temperature,
                                  vocab=self.cfg.vocab)
                    if self.metrics:
                        self._planes = self._fold(self._planes, toks)
                        kv.append(self._kv_step(prev, caches))
                    generated.append(toks)
                    self.stats["decode_steps"] += 1
                gen = np.concatenate(
                    [np.asarray(t) for t in generated], axis=1)
            if self.metrics and kv:
                self._add_kv_counts(jnp.stack(kv))
        if self.metrics and prefill_counts is not None:
            self._add_moe_counts("prefill", prefill_counts)
        if single and self._ghost_feed_on:
            self._kv_persist(caches, reqs[0].tenant_id)
        dt = time.time() - t0
        self.stats["tokens"] += gen.size
        return {
            r.rid: Result(
                rid=r.rid,
                tokens=gen[i, : r.max_new_tokens].tolist(),
                prefill_cached=was_cached,
                latency_s=dt,
            )
            for i, r in enumerate(reqs)
        }
