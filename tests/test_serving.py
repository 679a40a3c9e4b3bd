"""Serving engine + prefix cache + expert cache behaviour tests."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.cache.expert_cache import ExpertCacheRuntime, simulate_router_trace
from repro.cache.prefix_cache import PrefixCache
from repro.configs.base import load_smoke_config
from repro.models import model as M
from repro.serve.engine import Request, ServeEngine


@pytest.fixture(scope="module")
def engine():
    cfg = load_smoke_config("gemma3_27b")
    cfg = dataclasses.replace(cfg, dtype="float32", param_dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return ServeEngine(cfg, params, max_len=96)


def test_generate_deterministic_greedy(engine):
    prompt = list(range(1, 17))
    r1 = engine.generate([Request(0, list(prompt), max_new_tokens=6)])
    r2 = engine.generate([Request(1, list(prompt), max_new_tokens=6)])
    assert r1[0].tokens == r2[1].tokens
    assert len(r1[0].tokens) == 6
    assert all(0 <= t < engine.cfg.vocab for t in r1[0].tokens)


def test_decode_loop_temperature_does_not_retrace(engine):
    """Regression (ROADMAP "cross-batch persistent decode"): temperature is
    a TRACED loop operand, so requests at new temperatures reuse the
    compiled program — only ``steps`` buckets compile.  Counted via the
    jitted loop's compilation-cache size."""
    prompt = list(range(1, 17))
    n_loops_before = len(engine._loops)
    for i, temp in enumerate((0.0, 0.7, 1.3)):
        engine.generate([Request(100 + i, list(prompt), max_new_tokens=5,
                                 temperature=temp)])
    assert len(engine._loops) == n_loops_before + 1  # ONE steps=5 bucket
    loop = engine._loops[5]
    assert loop._cache_size() == 1  # ONE compilation across 3 temperatures
    # and temperature zero through the traced operand stays greedy-identical
    greedy = engine.generate(
        [Request(200, list(prompt), max_new_tokens=5, temperature=0.0)])
    again = engine.generate(
        [Request(201, list(prompt), max_new_tokens=5, temperature=0.0)])
    assert greedy[200].tokens == again[201].tokens


def test_prefix_cache_hit_skips_prefill(engine):
    prompt = list(range(30, 46))
    before = engine.stats["prefills"]
    engine.generate([Request(10, list(prompt), max_new_tokens=4)])
    mid = engine.stats["prefills"]
    out = engine.generate([Request(11, list(prompt), max_new_tokens=4)])
    after = engine.stats["prefills"]
    assert mid == before + 1
    assert after == mid  # second call: prompt-cache hit, no prefill
    assert out[11].prefill_cached


def test_batched_bucket_matches_single(engine):
    """Two same-length requests batched == each run alone (greedy)."""
    p1, p2 = list(range(5, 21)), list(range(40, 56))
    solo1 = engine.generate([Request(20, list(p1), max_new_tokens=5)])[20].tokens
    solo2 = engine.generate([Request(21, list(p2), max_new_tokens=5)])[21].tokens
    both = engine.generate([
        Request(22, list(p1), max_new_tokens=5),
        Request(23, list(p2), max_new_tokens=5),
    ])
    assert both[22].tokens == solo1
    assert both[23].tokens == solo2


def test_bounded_kv_engine_runs_past_pool_capacity():
    cfg = load_smoke_config("gemma3_27b")
    cfg = dataclasses.replace(cfg, dtype="float32", param_dtype="float32",
                              bounded_kv_pages=3, page_size=8)
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    eng = ServeEngine(cfg, params, max_len=128, kv_mode="paged")
    out = eng.generate([Request(0, list(range(1, 17)), max_new_tokens=40)])
    assert len(out[0].tokens) == 40  # decoded far past 3*8=24 resident tokens


def test_prefix_cache_awrp_eviction_bounded():
    pc = PrefixCache(capacity=2, policy="awrp")
    pc.insert([1, 2], "a")
    pc.insert([3, 4], "b")
    assert pc.lookup([1, 2]) == "a"  # F(a) grows
    pc.insert([5, 6], "c")  # evicts argmin W — the cold "b"
    assert len(pc.store) <= 2
    assert pc.lookup([1, 2]) == "a"
    assert pc.lookup([3, 4]) is None


def test_expert_cache_awrp_beats_fifo_on_skewed_router():
    rng = np.random.RandomState(0)
    # zipf-hot experts with phase change halfway (64 experts, cache 16)
    t1 = rng.zipf(1.5, size=4000) % 64
    t2 = (rng.zipf(1.5, size=4000) % 64 + 17) % 64
    trace = np.concatenate([t1, t2])
    res = simulate_router_trace(["awrp", "fifo", "lru"], trace, capacity=16,
                                expert_bytes=100 << 20)
    assert res["awrp"]["hit_ratio"] >= res["fifo"]["hit_ratio"]
    assert res["awrp"]["transfer_bytes"] <= res["fifo"]["transfer_bytes"]


def test_expert_cache_runtime_counts():
    rt = ExpertCacheRuntime(n_layers=2, capacity=2, policy="awrp")
    rt.route(0, [1, 2])
    rt.route(0, [1, 2])
    rt.route(1, [3, 3])
    assert rt.accesses == 6
    assert rt.transfers == 3  # 1,2 cold + 3 cold (second 3 hits)
    assert 0 < rt.hit_ratio < 1


def test_expert_cache_route_miss_accounting():
    """route()'s return value, .transfers and .accesses stay mutually
    consistent under hits, misses and evictions — per layer."""
    rt = ExpertCacheRuntime(n_layers=2, capacity=2, policy="lru")
    assert rt.route(0, [1, 2]) == 2  # both cold
    assert rt.route(0, [1, 2]) == 0  # both resident
    assert rt.route(0, [3]) == 1  # evicts LRU expert 1
    assert rt.route(0, [1]) == 1  # 1 was evicted: miss again
    assert rt.route(1, [1]) == 1  # layers are independent instances
    assert rt.route(0, []) == 0  # empty router step: no accounting drift
    assert rt.accesses == 7
    assert rt.transfers == 5
    assert rt.hit_ratio == 2 / 7
    t = rt.telemetry()
    assert t["policy"] == "lru" and t["backend"] == "host"
    assert t["transfers"] == 5 and t["accesses"] == 7


@pytest.mark.parametrize("policy", ["awrp", "lfu", "arc", "car"])
def test_expert_cache_device_path_matches_host(policy):
    """The batched (n_layers,)-row device path — unified policy core —
    reproduces the host dict-oracle accounting exactly, including true
    arc/car, via both per-layer route() and batched route_step()."""
    rng = np.random.RandomState(4)
    host = ExpertCacheRuntime(n_layers=3, capacity=4, policy=policy)
    dev = ExpertCacheRuntime(n_layers=3, capacity=4, policy=policy, device=True)
    # interleave per-layer routes and full-step batched routes
    for step in range(15):
        if step % 3 == 2:
            idx = rng.randint(0, 10, size=(3, 2))
            m_h, m_d = host.route_step(idx), dev.route_step(idx)
        else:
            layer = int(rng.randint(0, 3))
            experts = rng.randint(0, 10, size=2).tolist()
            m_h = host.route(layer, experts)
            m_d = dev.route(layer, experts)
        assert m_h == m_d, f"step {step}: host {m_h} != device {m_d}"
    assert host.accesses == dev.accesses
    assert host.transfers == dev.transfers
    assert host.hit_ratio == dev.hit_ratio
    assert dev.telemetry()["backend"] == "device"


def test_expert_cache_route_step_shape_validation():
    rt = ExpertCacheRuntime(n_layers=2, capacity=2, policy="awrp", device=True)
    with pytest.raises(ValueError, match="n_layers"):
        rt.route_step(np.zeros((3, 2), np.int32))


def test_expert_cache_rejects_shared_instance_across_layers():
    """A prebuilt policy instance can only back a single layer — sharing one
    residency set across layers would corrupt miss accounting."""
    from repro.core.policies import LRU

    with pytest.raises(ValueError, match="shared across layers"):
        ExpertCacheRuntime(n_layers=2, capacity=2, policy=LRU(2))
    rt = ExpertCacheRuntime(n_layers=1, capacity=2, policy=LRU(2))
    assert rt.route(0, [1]) == 1  # instance accepted for the single layer
    assert rt.telemetry()["policy"] == "lru"


# ---------------------------------------------------------------------------
# prefix cache: store / policy residency coherence
# ---------------------------------------------------------------------------


def _assert_coherent(pc):
    from repro.cache.prefix_cache import prompt_key  # noqa: F401

    assert set(pc.store) == pc.policy.resident_set(), (
        f"store {sorted(pc.store)} != policy {sorted(pc.policy.resident_set())}"
    )


@pytest.mark.parametrize("policy", ["awrp", "lru", "fifo", "lfu", "arc", "car"])
def test_prefix_cache_store_policy_coherence(policy):
    """The store and the policy's resident set never diverge — across
    misses, hits, evictions, re-inserts of resident keys, and lookups of
    long-evicted keys — for every policy the factory can build."""
    rng = np.random.RandomState(9)
    pc = PrefixCache(capacity=3, policy=policy)
    prompts = [[i, i + 1] for i in range(8)]
    for step in range(120):
        p = prompts[int(rng.randint(len(prompts)))]
        if rng.rand() < 0.5:
            got = pc.lookup(p)
            if got is not None:
                assert got == tuple(p)
        else:
            pc.insert(p, tuple(p))  # re-insert path when already resident
        _assert_coherent(pc)
        assert len(pc.store) <= 3
    t = pc.telemetry()
    assert t["policy"] == policy
    assert t["entries"] == len(pc.store)
    assert 0.0 <= t["hit_ratio"] <= 1.0


def test_prefix_cache_reinsert_updates_value_without_eviction():
    pc = PrefixCache(capacity=2, policy="awrp")
    pc.insert([1, 2], "a")
    pc.insert([3, 4], "b")
    before = set(pc.store)
    pc.insert([1, 2], "a2")  # re-insert: value swap, no eviction
    assert set(pc.store) == before
    assert pc.lookup([1, 2]) == "a2"
    _assert_coherent(pc)


def test_prefix_cache_accepts_prebuilt_policy_instance():
    from repro.core.policies import LRU

    pc = PrefixCache(capacity=2, policy=LRU(2))
    pc.insert([1], "x")
    assert pc.telemetry()["policy"] == "lru"
    _assert_coherent(pc)


# ---------------------------------------------------------------------------
# engine telemetry + true-adaptive bounded KV
# ---------------------------------------------------------------------------


def test_engine_telemetry_one_code_path(engine):
    """Telemetry keys are namespaced by cache layer (``prefix/...``,
    ``kv/...``, ``expert/...``) so two caches running the same policy never
    collide in the merged dict."""
    engine.generate([Request(50, list(range(2, 18)), max_new_tokens=2)])
    t = engine.telemetry()
    assert t["prefix/policy"] == "awrp"
    assert {"prefix/hits", "prefix/misses", "prefix/hit_ratio"} <= set(t)
    assert t["serve/prefills"] >= 1
    assert not any(k.startswith("expert/") for k in t)  # none attached
    rt = ExpertCacheRuntime(n_layers=1, capacity=2, policy="awrp")
    engine.expert_cache = rt
    rt.route(0, [5])
    t = engine.telemetry()
    # same policy name in two layers -> two distinct namespaced keys
    assert t["expert/policy"] == t["prefix/policy"] == "awrp"
    assert t["expert/transfers"] == 1


@pytest.mark.parametrize("kv_policy", ["arc_adaptive", "car_adaptive"])
def test_bounded_kv_true_adaptive_engine_runs_past_pool_capacity(kv_policy):
    """End-to-end: the decode scan carries AdaptiveState planes through the
    model cache tree and keeps decoding far past the resident pool."""
    cfg = load_smoke_config("gemma3_27b")
    cfg = dataclasses.replace(cfg, dtype="float32", param_dtype="float32",
                              bounded_kv_pages=3, page_size=8,
                              kv_policy=kv_policy)
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    eng = ServeEngine(cfg, params, max_len=128, kv_mode="paged")
    out = eng.generate([Request(0, list(range(1, 17)), max_new_tokens=40)])
    assert len(out[0].tokens) == 40  # decoded far past 3*8=24 resident tokens
    assert eng.telemetry()["kv/pool/policy"] == kv_policy


@pytest.mark.parametrize("jit_loop", [True, False])
def test_kv_pool_counters_do_not_wrap_past_int32(jit_loop):
    """``kv/pool/hits`` and ``kv/pool/evictions`` keep counting past 2**31
    (a long-lived engine): each generate's per-step counts are added on the
    host, so a total near the int32 limit grows by exactly what a fresh
    engine counts for the same requests."""
    cfg = load_smoke_config("gemma3_27b")
    cfg = dataclasses.replace(cfg, dtype="float32", param_dtype="float32",
                              bounded_kv_pages=3, page_size=8)
    params = M.init_params(cfg, jax.random.PRNGKey(1))

    def counts(start):
        eng = ServeEngine(cfg, params, max_len=128, kv_mode="paged",
                          jit_loop=jit_loop)
        eng._kv_counts.update(hits=start, evictions=start)
        eng.generate([Request(0, list(range(1, 17)), max_new_tokens=30)])
        tel = eng.telemetry()
        return tel["kv/pool/hits"], tel["kv/pool/evictions"]

    hits, evictions = counts(0)
    assert hits > 0 and evictions > 0
    start = 2**31 - 1
    assert counts(start) == (start + hits, start + evictions)


# ---------------------------------------------------------------------------
# multi-tenant serving (serve.tenancy, DESIGN.md §8)
# ---------------------------------------------------------------------------


def _tenant_requests(n_good=6, n_hog=6):
    """A loop-heavy tenant (repeats two prompts — should hit) interleaved
    with a hog tenant (all-distinct prompts at quota 1 — pure thrash)."""
    good_prompts = [list(range(1, 17)), list(range(30, 46))]
    reqs = []
    rid = 0
    for i in range(max(n_good, n_hog)):
        if i < n_good:
            reqs.append(Request(rid, list(good_prompts[i % 2]),
                                max_new_tokens=2, tenant_id="good"))
            rid += 1
        if i < n_hog:
            reqs.append(Request(rid, [100 + 16 * i + j for j in range(16)],
                                max_new_tokens=2, tenant_id="hog"))
            rid += 1
    return reqs


def test_two_tenant_hit_ratios_match_host_oracles(engine):
    """Acceptance (a): per-tenant hit ratios from ``ServeEngine.telemetry``
    reproduce host oracles run on the demuxed per-tenant prompt streams —
    the manager's row accounting is the oracle accounting."""
    from repro.core.policies import make_policy
    from repro.serve.tenancy import _prompt_key

    quotas = {"good": 3, "hog": 1}
    eng = ServeEngine(engine.cfg, engine.params, max_len=96, tenants=quotas)
    reqs = _tenant_requests()
    for r in reqs:  # one request per generate(): the prefix path engages
        out = eng.generate([Request(r.rid, list(r.prompt),
                                    max_new_tokens=r.max_new_tokens,
                                    tenant_id=r.tenant_id)])
        assert out[r.rid].status == "ok"
    oracles = {t: make_policy("awrp", q) for t, q in quotas.items()}
    expect = {t: [0, 0] for t in quotas}  # hits, accesses
    for r in reqs:
        hit = oracles[r.tenant_id].access(_prompt_key(eng._align(r.prompt)))
        expect[r.tenant_id][0] += int(hit)
        expect[r.tenant_id][1] += 1
    t = eng.telemetry()
    for tenant in quotas:
        assert t[f"tenant/{tenant}/accesses"] == expect[tenant][1]
        assert t[f"tenant/{tenant}/hits"] == expect[tenant][0], (tenant, t)
        assert t[f"tenant/{tenant}/hit_ratio"] == (
            expect[tenant][0] / expect[tenant][1])
    # the hog thrashes (quota 1, distinct prompts): pressure near 1
    assert t["tenant/hog/pressure"] > 0.3
    assert t["tenant/good/pressure"] < t["tenant/hog/pressure"]


def test_admission_sheds_hog_without_perturbing_other_tenant(engine):
    """Acceptance (b): under quota pressure the admission controller sheds
    the pressured tenant; the other tenant's hit ratio is EXACTLY what it
    would be alone (quota rows are independent policy instances — not just
    'within noise')."""
    from repro.serve.tenancy import AdmissionController

    quotas = {"good": 3, "hog": 1}
    # thresholds sized to the EWMA ramp (alpha 0.1): the hog's all-miss
    # stream crosses 0.45 within ~7 evicting accesses
    adm = AdmissionController(defer_at=0.3, shed_at=0.45, warmup=3)
    eng = ServeEngine(engine.cfg, engine.params, max_len=96, tenants=quotas,
                      admission=adm)
    solo = ServeEngine(engine.cfg, engine.params, max_len=96,
                       tenants={"good": 3})
    statuses = {}
    for r in _tenant_requests(n_good=5, n_hog=8):
        out = eng.generate([Request(r.rid, list(r.prompt),
                                    max_new_tokens=2,
                                    tenant_id=r.tenant_id)])
        statuses.setdefault(r.tenant_id, []).append(out[r.rid].status)
        if r.tenant_id == "good":
            solo.generate([Request(r.rid, list(r.prompt), max_new_tokens=2,
                                   tenant_id="good")])
    assert "shed" in statuses["hog"]  # pressure crossed shed_at
    assert all(s == "ok" for s in statuses["good"])
    both = eng.telemetry()
    alone = solo.telemetry()
    assert both["tenant/good/hits"] == alone["tenant/good/hits"]
    assert both["tenant/good/hit_ratio"] == alone["tenant/good/hit_ratio"]
    assert eng.stats["shed"] >= 1


def test_shed_request_mutates_no_pool_or_tenancy_state(engine):
    """Regression (ISSUE 6): a shed request must leave EVERY cache and
    tenancy structure untouched — core state, hit/miss/eviction counters,
    payload stores, prefill count, KV sessions.  The only permitted change
    is the shed tenant's pressure decay (probation credit)."""
    import jax as _jax

    from repro.serve.tenancy import AdmissionController

    adm = AdmissionController(defer_at=0.1, shed_at=0.2, warmup=1)
    eng = ServeEngine(engine.cfg, engine.params, max_len=96,
                      tenants={"hog": 1, "calm": 2}, admission=adm)
    # drive the hog into shed territory: distinct prompts at quota 1
    for i in range(6):
        eng.generate([Request(i, [200 + 16 * i + j for j in range(16)],
                              max_new_tokens=2, tenant_id="hog")])
    mgr = eng.tenant_cache.manager
    assert adm.decide(mgr, "hog") == "shed"

    state_before = _jax.tree.map(np.asarray, mgr.state)
    ctr_before = _jax.tree.map(np.asarray, mgr.counters)
    stores_before = {t: dict(s) for t, s in eng.tenant_cache.stores.items()}
    prefills_before = eng.stats["prefills"]
    sessions_before = dict(eng._kv_sessions)
    p_before = float(mgr.pressure("hog"))

    out = eng.generate([Request(99, list(range(1, 17)), max_new_tokens=4,
                                tenant_id="hog")])
    assert out[99].status == "shed" and out[99].tokens == []

    state_after = _jax.tree.map(np.asarray, mgr.state)
    for b, a in zip(_jax.tree.leaves(state_before),
                    _jax.tree.leaves(state_after)):
        assert np.array_equal(b, a)
    for name in ("hits", "misses", "evictions"):
        assert np.array_equal(getattr(ctr_before, name),
                              getattr(_jax.tree.map(np.asarray,
                                                    mgr.counters), name))
    assert {t: dict(s) for t, s in eng.tenant_cache.stores.items()} \
        == stores_before
    assert eng.stats["prefills"] == prefills_before
    assert eng._kv_sessions == sessions_before
    # pressure: exactly one probation decay, nothing else
    assert float(mgr.pressure("hog")) == np.float32(p_before) * np.float32(
        1.0 - mgr.pressure_alpha)
    assert float(mgr.pressure("calm")) == 0.0


def test_deferred_then_completed_matches_unpressured_telemetry(engine):
    """Bugfix (ISSUE 6): a deferred-then-completed request reports
    ``status="deferred"`` but is otherwise indistinguishable from an
    accepted run — same tokens, same prefix-cache counters, same engine
    stats (minus the deferral count itself)."""
    from repro.serve.tenancy import AdmissionController

    # defer_at=0, huge shed_at, warmup=0: every request defers, none shed
    adm = AdmissionController(defer_at=0.0, shed_at=100.0, warmup=0)
    deferred_eng = ServeEngine(engine.cfg, engine.params, max_len=96,
                               tenants={"t": 3}, admission=adm)
    plain_eng = ServeEngine(engine.cfg, engine.params, max_len=96,
                            tenants={"t": 3})
    prompts = [list(range(1, 17)), list(range(30, 46)), list(range(1, 17))]
    for i, p in enumerate(prompts):
        d = deferred_eng.generate([Request(i, list(p), max_new_tokens=4,
                                           tenant_id="t")])
        o = plain_eng.generate([Request(i, list(p), max_new_tokens=4,
                                        tenant_id="t")])
        assert d[i].status == "deferred" and o[i].status == "ok"
        assert d[i].tokens == o[i].tokens
        assert d[i].prefill_cached == o[i].prefill_cached
    td = deferred_eng.telemetry()
    tp = plain_eng.telemetry()
    keys = {k for k in td if k.startswith("tenant/t/")}
    assert keys == {k for k in tp if k.startswith("tenant/t/")}
    # counters identical: hits/misses/evictions/pressure/occupancy/...
    assert {k: td[k] for k in keys} == {k: tp[k] for k in keys}
    sd, sp = dict(deferred_eng.stats), dict(plain_eng.stats)
    assert sd.pop("deferred") == len(prompts) and sp.pop("deferred") == 0
    assert sd == sp


def test_jit_loop_matches_host_loop_greedy(engine):
    """The donated-buffer scan loop and the host per-step loop agree on
    greedy decode (argmax is stable across the two compilation contexts),
    and the jit loop counts the same decode steps."""
    jit_eng = ServeEngine(engine.cfg, engine.params, max_len=96,
                          jit_loop=True)
    host_eng = ServeEngine(engine.cfg, engine.params, max_len=96,
                           jit_loop=False)
    prompt = list(range(7, 23))
    rj = jit_eng.generate([Request(0, list(prompt), max_new_tokens=6)])
    rh = host_eng.generate([Request(0, list(prompt), max_new_tokens=6)])
    assert rj[0].tokens == rh[0].tokens
    assert len(rj[0].tokens) == 6
    assert jit_eng.stats["decode_steps"] == host_eng.stats["decode_steps"]


def test_jit_loop_prefix_payload_survives_donation(engine):
    """Donation regression: stored prefix payloads must be snapshots —
    aliasing them with the loop's donated buffers would invalidate the
    entry on first reuse (jax deletes donated arrays).  Three hits on the
    same entry prove the payload outlives repeated donated loops."""
    eng = ServeEngine(engine.cfg, engine.params, max_len=96, jit_loop=True)
    prompt = list(range(60, 76))
    first = eng.generate([Request(0, list(prompt), max_new_tokens=4)])
    outs = [eng.generate([Request(i, list(prompt), max_new_tokens=4)])
            for i in (1, 2, 3)]
    assert not first[0].prefill_cached
    for i, out in enumerate(outs, start=1):
        assert out[i].prefill_cached  # every reuse hit the stored payload
        assert out[i].tokens == first[0].tokens
    assert eng.stats["prefills"] == 1


def test_ghost_hit_feed_adapts_p_under_prefix_reuse():
    """Acceptance (c): in the true-adaptive paged mode, prefix-reuse traffic
    (re-prefills of page positions the tenant's previous pool evicted)
    replays ghost hits and demonstrably moves ``p`` — which pure decode
    provably cannot (tests/test_adaptive_kv.py pins that side)."""
    cfg = load_smoke_config("gemma3_27b")
    cfg = dataclasses.replace(cfg, dtype="float32", param_dtype="float32",
                              bounded_kv_pages=2, page_size=8,
                              kv_policy="arc_adaptive")
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    eng = ServeEngine(cfg, params, max_len=256, kv_mode="paged",
                      tenants={"a": 3})
    rng = np.random.RandomState(0)
    p_max = []
    for i in range(3):
        prompt = rng.randint(1, cfg.vocab, size=16).tolist()
        out = eng.generate([Request(i, prompt, max_new_tokens=32,
                                    tenant_id="a")])
        assert len(out[i].tokens) == 32
        states = eng._kv_sessions["a"]
        p_max.append(max(float(np.asarray(s.p).max()) for s in states))
    t = eng.telemetry()
    assert t["kv/a/ghost_hits"] > 0  # the feed fired
    assert eng.stats["kv_ghost_hits"] == t["kv/a/ghost_hits"]
    assert max(p_max) > 0.0  # p moved (provably static in pure decode)


def test_serve_cli_main_runs_in_process(monkeypatch, capsys):
    """``launch.serve.main(argv)`` serves in the calling process; ``--smoke``
    loads the reduced config and ``--fused`` reaches the engine."""
    from repro.launch import serve

    monkeypatch.setattr(serve, "enable_compile_cache", lambda: "")
    serve.main(["--arch", "smollm_360m", "--smoke", "--requests", "2",
                "--new-tokens", "3", "--prompt-len", "64", "--kv-mode",
                "paged", "--fused"])
    out = capsys.readouterr().out
    assert "kv_mode=paged policy=awrp loop=jit fused=True" in out
    assert "2 requests, 6 tokens" in out
