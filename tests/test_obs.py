"""Observability-layer property suite (repro.obs, DESIGN.md §11).

Pins the three tentpole contracts:

* **zero-sync registry** — ``Registry.snapshot()`` performs exactly ONE
  ``jax.device_get`` over every mounted provider's device leaves, and the
  decode-loop metric planes count the served tokens exactly (integer
  folds only) and are bit-identical between 1- and N-device row meshes;
* **decision-trace ring** — recording rides the jitted scan carries and
  BY CONSTRUCTION changes no policy decision: twin managers with the
  ring on/off produce bitwise-equal hits, state, and counters, while the
  drained ring reproduces the access stream (wraparound included);
* **OPT-regret feed** — drained traces replayed through the offline
  Belady oracle publish per-tenant regret gauges into the snapshot.

Plus the satellite regression: every ``hit_ratio`` surface shares
``obs.metrics.safe_ratio``, so a fresh (zero-access) engine snapshots
``0.0`` everywhere instead of raising ``ZeroDivisionError``.
"""

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import load_smoke_config
from repro.core import sharding
from repro.models import model as M
from repro.obs import decision_trace as dt
from repro.obs.export import append_jsonl, prometheus_text
from repro.obs.metrics import (HIST_BINS, Derived, Registry, loop_planes,
                               loop_update, safe_ratio, safe_ratio_plane)
from repro.obs.spans import SpanSet
from repro.serve.engine import Request, ServeEngine
from repro.serve.tenancy import AdmissionController, TenantCacheManager

MESH_SIZES = (1, 2, 8)


@pytest.fixture(scope="module")
def cfg_params():
    cfg = load_smoke_config("gemma3_27b")
    cfg = dataclasses.replace(cfg, dtype="float32", param_dtype="float32")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def _mesh_or_skip(n: int):
    if n > sharding.device_count():
        pytest.skip(f"needs {n} XLA host devices "
                    f"(have {sharding.device_count()}; see "
                    f"tools/run_sharded_smoke.py)")
    return sharding.rows_mesh(n)


# ---------------------------------------------------------------------------
# safe_ratio: the ONE guarded division (satellite S1)
# ---------------------------------------------------------------------------


def test_safe_ratio_guards_and_exactness():
    assert safe_ratio(0, 0) == 0.0
    assert safe_ratio(3, 4) == 3 / 4  # exact float64 division, == comparable
    plane = safe_ratio_plane(jnp.asarray([0, 2, 5]), jnp.asarray([0, 4, 5]))
    assert np.array_equal(np.asarray(plane), [0.0, 0.5, 1.0])


def test_fresh_surfaces_report_zero_ratio_not_error():
    """Regression: zero-access telemetry used to divide by zero; every
    surface now routes through ``safe_ratio``."""
    from repro.cache.expert_cache import ExpertCacheRuntime
    from repro.cache.prefix_cache import PrefixCache
    from repro.core.simulator import SimResult

    assert PrefixCache(capacity=2).telemetry()["hit_ratio"] == 0.0
    assert ExpertCacheRuntime(n_layers=1, capacity=2).hit_ratio == 0.0
    assert SimResult("awrp", 4, 1, 0, 0).hit_ratio == 0.0
    mgr = TenantCacheManager({"a": 2, "b": 2})
    assert all(v["hit_ratio"] == 0.0 for v in mgr.telemetry().values())


def test_fresh_engine_snapshot_is_all_zero_ratios(cfg_params):
    """A just-built multi-tenant engine snapshots BEFORE any request."""
    cfg, params = cfg_params
    eng = ServeEngine(cfg, params, max_len=96, tenants={"a": 2, "b": 2})
    t = eng.telemetry()
    assert t["tenant/a/hit_ratio"] == 0.0 and t["tenant/b/hit_ratio"] == 0.0
    assert t["serve/loop/steps"] == 0 and t["serve/loop/tokens"] == 0
    assert t["serve/prefills"] == 0 and t["serve/shed"] == 0


# ---------------------------------------------------------------------------
# registry: flat namespacing + the single-pull protocol
# ---------------------------------------------------------------------------


def test_registry_snapshot_one_device_get(monkeypatch):
    reg = Registry()
    reg.mount("a", lambda: {
        "hits": jnp.int32(3),
        "accesses": jnp.int32(4),
        "hit_ratio": Derived(lambda g: safe_ratio(g["hits"], g["accesses"])),
        "nested": {"plane": jnp.arange(3, dtype=jnp.int32)},
    })
    reg.mount("b", lambda: {"policy": "awrp"})
    reg.set_gauge("c/regret", 0.125)
    calls = []
    orig = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: (calls.append(1), orig(x))[1])
    snap = reg.snapshot()
    assert len(calls) == 1  # ONE batched pull for every device leaf
    assert snap["a/hits"] == 3 and isinstance(snap["a/hits"], int)
    assert snap["a/hit_ratio"] == 3 / 4  # derived AFTER the pull, exact
    assert np.array_equal(snap["a/nested/plane"], [0, 1, 2])
    assert snap["b/policy"] == "awrp"
    assert snap["c/regret"] == 0.125


def test_registry_mount_replace_unmount_and_gauge_shadow():
    reg = Registry()
    reg.mount("x", lambda: {"v": 1})
    reg.mount("x", lambda: {"v": 2})  # replace
    assert reg.snapshot() == {"x/v": 2}
    reg.set_gauge("x/v", 9)  # gauges shadow provider values
    assert reg.snapshot() == {"x/v": 9}
    reg.unmount("x")
    assert reg.snapshot() == {"x/v": 9}  # sticky gauge survives the unmount
    reg.unmount("x")  # no-op, no raise


def test_loop_planes_fold_matches_host_reference():
    vocab, steps, batch = 640, 25, 3
    rng = np.random.RandomState(7)
    toks = rng.randint(0, vocab, size=(steps, batch))
    planes = loop_planes()
    fold = jax.jit(functools.partial(loop_update, vocab=vocab))
    for t in toks:
        planes = fold(planes, jnp.asarray(t))
    hist = np.zeros(HIST_BINS, np.int64)
    for t in toks.reshape(-1):
        hist[min(t * HIST_BINS // vocab, HIST_BINS - 1)] += 1
    assert int(planes["steps"]) == steps
    assert int(planes["tokens"]) == steps * batch
    assert np.array_equal(np.asarray(planes["token_hist"]), hist)


# ---------------------------------------------------------------------------
# decision-trace ring: scatter contract + decision non-interference
# ---------------------------------------------------------------------------


def test_ring_init_validation_and_capacity():
    with pytest.raises(ValueError, match="capacity"):
        dt.ring_init(0)
    ring = dt.ring_init(5)
    assert dt.ring_capacity(ring) == 5
    assert ring.buf.shape == (6, dt.NF)  # +1 scratch lane
    assert len(dt.drain(ring)) == 0


def test_ring_push_drain_roundtrip_and_wraparound():
    ring = dt.ring_init(4)
    for i in range(7):  # 7 events through a 4-slot ring
        ev = dt.pack_events(1, kind=dt.KIND_ACCESS, row=i % 2, key=100 + i,
                            hit=i % 2, weight=1.5 * i)
        ring = dt.ring_push(ring, ev, jnp.ones((1,), dtype=bool))
    rec = dt.drain(ring)
    assert len(rec) == 4  # oldest 3 overwritten
    assert rec["key"].tolist() == [103, 104, 105, 106]  # chronological
    assert rec["hit"].tolist() == [1, 0, 1, 0]
    # float bitcast roundtrip is exact
    assert rec["weight"].tolist() == [4.5, 6.0, 7.5, 9.0]
    assert np.all(rec["admit"] == -1)  # defaulted field


def test_ring_drain_after_multiple_full_wraparounds():
    """Three-plus full laps through the ring with VARYING push batch
    sizes (1, 3, 2, 5, ...): the cursor arithmetic must keep the drained
    window exactly the last ``capacity`` surviving events, oldest first,
    regardless of how pushes straddle the wrap boundary."""
    cap = 8
    ring = dt.ring_init(cap)
    rng = np.random.RandomState(42)
    pushed_keys, pushed_hits = [], []
    serial = 0
    while serial < cap * 4 + 3:  # > 4 full laps, ends mid-lap
        n = int(rng.randint(1, 6))  # batch sizes 1..5 straddle the wrap
        keys = np.arange(serial, serial + n, dtype=np.int32)
        hits = (keys % 3 == 0).astype(np.int32)
        ev = dt.pack_events(n, kind=dt.KIND_ACCESS,
                            row=jnp.asarray(keys % 2),
                            key=jnp.asarray(1000 + keys),
                            hit=jnp.asarray(hits),
                            weight=jnp.asarray(keys, jnp.float32) * 0.25)
        ring = dt.ring_push(ring, ev, jnp.ones((n,), dtype=bool))
        pushed_keys.extend((1000 + keys).tolist())
        pushed_hits.extend(hits.tolist())
        serial += n
    rec = dt.drain(ring)
    assert len(rec) == cap and int(ring.count) == serial
    assert rec["key"].tolist() == pushed_keys[-cap:]  # chronological tail
    assert rec["hit"].tolist() == pushed_hits[-cap:]
    expected_w = [(k - 1000) * 0.25 for k in pushed_keys[-cap:]]
    assert rec["weight"].tolist() == expected_w  # bitcast exact after 4 laps
    # draining is non-destructive: a second drain reads the same window
    rec2 = dt.drain(ring)
    assert rec2["key"].tolist() == rec["key"].tolist()


def test_ring_push_masked_scatter_skips_masked_out_rows():
    ring = dt.ring_init(8)
    ev = dt.pack_events(4, kind=dt.KIND_ACCESS,
                        row=jnp.arange(4, dtype=jnp.int32),
                        key=jnp.asarray([10, 11, 12, 13], jnp.int32))
    ring = dt.ring_push(ring, ev, jnp.asarray([True, False, True, False]))
    rec = dt.drain(ring)
    assert rec["key"].tolist() == [10, 12]  # masked-out rows never land
    assert rec["row"].tolist() == [0, 2]
    assert int(ring.count) == 2


@pytest.mark.parametrize("policy", ["awrp", "arc"])
def test_manager_ring_changes_no_decision(policy):
    """Twin managers, same stream, ring on vs off: every hit bit, every
    state plane, every counter bitwise identical — recording is write-only
    with respect to the policy math."""
    quotas = {"a": 3, "b": 2}
    rng = np.random.RandomState(11)
    tenant_rows = rng.randint(0, 2, size=120).astype(np.int32)
    keys = rng.randint(0, 9, size=120).astype(np.int32)
    plain = TenantCacheManager(quotas, policy)
    traced = TenantCacheManager(quotas, policy, ring_capacity=64)
    h_plain = plain.access_stream(tenant_rows, keys)
    h_traced = traced.access_stream(tenant_rows, keys)
    assert np.array_equal(h_plain, h_traced)
    for a, b in zip(jax.tree.leaves(plain.state), jax.tree.leaves(traced.state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(plain.counters),
                    jax.tree.leaves(traced.counters)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert plain.telemetry() == traced.telemetry()
    # and the drained window reproduces the tail of the stream exactly
    rec = traced.drain_trace()
    assert len(rec) == 64 and np.all(rec["kind"] == dt.KIND_ACCESS)
    assert rec["row"].tolist() == tenant_rows[-64:].tolist()
    assert rec["key"].tolist() == keys[-64:].tolist()
    assert rec["hit"].tolist() == h_traced[-64:].astype(np.int32).tolist()
    with pytest.raises(ValueError, match="ring_capacity"):
        plain.drain_trace()


def test_admission_decide_batch_records_admit_events():
    mgr = TenantCacheManager({"a": 2, "b": 2}, ring_capacity=16)
    # defer_at=0, warmup=0: every request defers (pressure >= 0), none shed
    adm = AdmissionController(defer_at=0.0, shed_at=100.0, warmup=0)
    statuses = adm.decide_batch(mgr, ["a", "b", "a"])
    assert statuses == ["defer", "defer", "defer"]
    rec = mgr.drain_trace()
    assert len(rec) == 3 and np.all(rec["kind"] == dt.KIND_ADMIT)
    assert rec["row"].tolist() == [0, 1, 0]
    assert rec["admit"].tolist() == [1, 1, 1]  # ADMIT_DEFER
    assert np.all(rec["key"] == -1)  # admissions carry no access key


@pytest.mark.parametrize("n_dev", MESH_SIZES)
def test_manager_ring_mesh_parity(n_dev):
    """The ring is replicated next to the sharded rows: its drained
    content is identical on any device count (PR 7 invariant extended to
    the trace path)."""
    mesh = _mesh_or_skip(n_dev)
    rng = np.random.RandomState(5)
    tenant_rows = rng.randint(0, 3, size=80).astype(np.int32)
    keys = rng.randint(0, 7, size=80).astype(np.int32)
    quotas = {"a": 2, "b": 2, "c": 2}
    ref = TenantCacheManager(quotas, "awrp", ring_capacity=32)
    cur = TenantCacheManager(quotas, "awrp", mesh=mesh, ring_capacity=32)
    h_ref = ref.access_stream(tenant_rows, keys)
    h_cur = cur.access_stream(tenant_rows, keys)
    assert np.array_equal(h_ref, h_cur)
    a, b = ref.drain_trace(), cur.drain_trace()
    assert a.dtype == b.dtype and len(a) == len(b)
    for name in a.dtype.names:
        assert np.array_equal(a[name], b[name]), name


# ---------------------------------------------------------------------------
# engine: loop planes, trace + OPT regret
# ---------------------------------------------------------------------------


def test_engine_loop_planes_host_vs_jit_bit_identical(cfg_params):
    """serve/loop/* counts exactly the tokens the donated decode loop
    served: one step and one token per sampling event (the first greedy
    token included), and a token histogram equal to the bincount of the
    generated tokens' buckets (``tok * HIST_BINS // vocab``)."""
    cfg, params = cfg_params
    eng = ServeEngine(cfg, params, max_len=96)
    served = []
    for i, plen in enumerate((16, 16, 32)):
        out = eng.generate([Request(i, list(range(1, plen + 1)),
                                    max_new_tokens=5)])
        served += out[i].tokens
    t = eng.telemetry()
    assert len(served) == 15
    assert t["serve/loop/steps"] == t["serve/loop/tokens"] == len(served)
    want = np.bincount(np.asarray(served) * HIST_BINS // cfg.vocab,
                       minlength=HIST_BINS)
    assert np.array_equal(t["serve/loop/token_hist"], want)


def test_engine_decision_trace_and_opt_regret(cfg_params):
    cfg, params = cfg_params
    eng = ServeEngine(cfg, params, max_len=96, tenants={"a": 4, "b": 2},
                      decision_trace=64)
    loop = list(range(1, 17))
    rng = np.random.RandomState(3)
    for i in range(4):
        eng.generate([Request(i, list(loop), max_new_tokens=2,
                              tenant_id="a")])  # "a" re-uses one prompt
        eng.generate([Request(10 + i,
                              rng.randint(1, cfg.vocab, size=16).tolist(),
                              max_new_tokens=2, tenant_id="b")])
    rec = eng.drain_decision_trace()
    kinds = set(rec["kind"].tolist())
    assert kinds == {dt.KIND_ACCESS, dt.KIND_ADMIT}
    acc = rec[rec["kind"] == dt.KIND_ACCESS]
    assert len(acc) == 8  # one policy access per request
    assert set(acc["row"].tolist()) == {0, 1}
    regret = eng.opt_regret()
    assert set(regret) == {"a", "b", "aggregate"}
    for info in regret.values():
        assert 0.0 <= info["observed"] <= info["opt"] <= 1.0
        assert info["regret"] == info["opt"] - info["observed"]
    # tenant "a" replayed one prompt: even OPT can't miss less than once
    assert regret["a"]["observed"] == 3 / 4 == regret["a"]["opt"]
    assert regret["a"]["regret"] == 0.0
    t = eng.telemetry()
    assert t["tenant/a/opt_regret"] == 0.0
    assert t["tenant/b/opt_regret"] == regret["b"]["regret"]
    assert t["policy/awrp/opt_regret"] == regret["aggregate"]["regret"]
    assert t["span/trace_drain/calls"] >= 1


def test_engine_decision_trace_requires_tenants(cfg_params):
    cfg, params = cfg_params
    with pytest.raises(ValueError, match="tenants"):
        ServeEngine(cfg, params, max_len=96, decision_trace=8)


# ---------------------------------------------------------------------------
# exporters + spans
# ---------------------------------------------------------------------------


def test_prometheus_text_rendering():
    snap = {
        "serve/requests": 4,
        "tenant/a/hit_ratio": 0.5,
        "serve/loop/token_hist": np.asarray([2, 0, 3]),
        "prefix/policy": "awrp",
        "serve/flag": True,
        "serve/junk": [1, 2],
    }
    text = prometheus_text(snap)
    assert "awrp_serve_requests 4\n" in text
    assert "awrp_tenant_a_hit_ratio 0.5\n" in text
    assert 'awrp_serve_loop_token_hist{bucket="2"} 3\n' in text
    assert "# awrp_prefix_policy info: awrp\n" in text
    assert "awrp_serve_flag 1\n" in text
    assert "# awrp_serve_junk skipped: list" in text
    assert text == prometheus_text(snap)  # deterministic (sorted by path)


def test_prometheus_help_type_and_collision_dedupe():
    snap = {
        "serve/requests": 4,
        "serve-requests": 7,  # sanitizes to the SAME metric name
        "tenant/a/hit_ratio": 0.5,
        "prefix/policy": "awrp",
    }
    text = prometheus_text(snap)
    # every numeric metric carries HELP (original path) + TYPE gauge
    assert "# HELP awrp_serve_requests serve-requests\n" in text
    assert "# TYPE awrp_serve_requests gauge\n" in text
    assert "# HELP awrp_tenant_a_hit_ratio tenant/a/hit_ratio\n" in text
    # the post-sanitize collision stays a distinct series, not a silent
    # duplicate sample ("serve-requests" sorts first and keeps the name)
    assert "awrp_serve_requests 7\n" in text
    assert "# HELP awrp_serve_requests_dup1 serve/requests\n" in text
    assert "awrp_serve_requests_dup1 4\n" in text
    # info comments carry no HELP/TYPE (they have no numeric sample)
    assert "# HELP awrp_prefix_policy" not in text
    sample_names = [ln.split()[0] for ln in text.splitlines()
                    if ln and not ln.startswith("#")]
    assert len(sample_names) == len(set(sample_names))  # no dup samples


def _roundtrip_snapshot():
    """Awkward-but-legal values: denormals, huge ints, negative zero,
    non-round floats, multi-bucket arrays."""
    rng = np.random.RandomState(9)
    return {
        "a/exact_ratio": 3 / 7,
        "a/tiny": 5e-324,
        "a/neg": -0.0,
        "a/big_int": 2**53 - 1,
        "a/bool": True,
        "a/hist": rng.randint(0, 1000, size=5),
        "a/plane": rng.rand(4).astype(np.float64),
        "a/np_scalar": np.float32(0.1),
    }


def test_prometheus_roundtrip_values_bit_equal():
    """Property: parsing the exposition text back recovers every numeric
    sample bit-for-bit — ``_fmt`` uses ``repr``, which round-trips."""
    snap = _roundtrip_snapshot()
    parsed = {}
    for ln in prometheus_text(snap).splitlines():
        if ln.startswith("#") or not ln:
            continue
        name, val = ln.rsplit(" ", 1)
        parsed[name] = float(val)
    assert parsed["awrp_a_exact_ratio"] == 3 / 7  # bit-equal, not approx
    assert parsed["awrp_a_tiny"] == 5e-324
    assert parsed["awrp_a_neg"] == 0.0
    assert parsed["awrp_a_big_int"] == 2**53 - 1
    assert parsed["awrp_a_bool"] == 1
    assert parsed["awrp_a_np_scalar"] == float(np.float32(0.1))
    for i, x in enumerate(snap["a/hist"].tolist()):
        assert parsed[f'awrp_a_hist{{bucket="{i}"}}'] == x
    for i, x in enumerate(snap["a/plane"].tolist()):
        assert parsed[f'awrp_a_plane{{bucket="{i}"}}'] == x  # float64 exact


def test_jsonl_roundtrip_values_equal(tmp_path):
    """Same property through the JSONL path: ``json.loads`` of the
    appended line recovers every value exactly (json floats are repr'd
    shortest-round-trip doubles)."""
    snap = _roundtrip_snapshot()
    path = tmp_path / "rt.jsonl"
    append_jsonl(str(path), snap)
    rec = json.loads(path.read_text())
    assert rec["a/exact_ratio"] == 3 / 7
    assert rec["a/tiny"] == 5e-324
    assert rec["a/big_int"] == 2**53 - 1
    assert rec["a/bool"] is True
    assert rec["a/hist"] == snap["a/hist"].tolist()
    assert rec["a/plane"] == snap["a/plane"].tolist()
    assert rec["a/np_scalar"] == float(np.float32(0.1))


def test_append_jsonl_roundtrip(tmp_path):
    path = tmp_path / "obs.jsonl"
    snap = {"serve/requests": np.int32(2),
            "serve/loop/token_hist": np.asarray([1, 2])}
    append_jsonl(str(path), snap, extra={"arch": "gemma3_27b"})
    append_jsonl(str(path), snap)
    lines = path.read_text().splitlines()
    assert len(lines) == 2
    rec = json.loads(lines[0])
    assert rec["arch"] == "gemma3_27b" and rec["serve/requests"] == 2
    assert rec["serve/loop/token_hist"] == [1, 2] and "ts" in rec


def test_spans_accumulate():
    ss = SpanSet()
    with ss.span("decode"):
        pass
    with ss.span("decode"):
        sum(range(1000))
    with pytest.raises(RuntimeError):
        with ss.span("decode"):
            raise RuntimeError("recorded anyway")
    m = ss.metrics()
    assert m["decode"]["calls"] == 3  # the raising span still recorded
    assert m["decode"]["seconds"] >= m["decode"]["max_s"] >= 0.0
