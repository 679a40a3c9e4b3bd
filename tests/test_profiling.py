"""Performance-observability suite (obs.profiling / obs.server —
DESIGN.md §12).

Pins the PR's acceptance contracts:

* **retrace flatness** — the compile sentinels count traces exactly:
  repeated same-shape ``generate()`` batches leave
  ``compile/decode_loop/count`` FLAT (the retrace-regression detector),
  while a new ``steps`` bucket adds exactly one trace and one cache
  entry;
* **sentinel mechanics** — wrap preserves jit semantics (values,
  static_argnames, ``_cache_size``), counts per-shape traces, audits
  jaxpr equation counts lazily from abstract shapes, and aggregates by
  name across instances;
* **phase spans** — p50/p95 percentiles over the recent window and the
  ``sync`` discipline's ``ready()`` hook;
* **live export** — the background HTTP ``/metrics`` endpoint serves
  the same snapshot ``telemetry()`` returns, and the periodic JSONL
  logger appends parseable lines.
"""

import dataclasses
import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import load_smoke_config
from repro.models import model as M
from repro.obs import profiling
from repro.obs.profiling import Sentinel, TraceCapture, count_eqns, instrument
from repro.obs.server import MetricsServer, SnapshotLogger
from repro.obs.spans import SpanSet
from repro.serve.engine import Request, ServeEngine


@pytest.fixture(scope="module")
def cfg_params():
    cfg = load_smoke_config("gemma3_27b")
    cfg = dataclasses.replace(cfg, dtype="float32", param_dtype="float32")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# sentinel mechanics
# ---------------------------------------------------------------------------


def test_sentinel_counts_traces_not_calls():
    s = Sentinel("t_basic")
    f = s.wrap(lambda x: x * 2 + 1)
    a = jnp.arange(4, dtype=jnp.float32)
    for _ in range(5):
        out = f(a)
    assert np.array_equal(np.asarray(out), np.asarray(a) * 2 + 1)
    assert s.calls == 5 and s.traces == 1  # one shape -> one trace
    b = jnp.arange(8, dtype=jnp.float32)  # new shape -> one more trace
    f(b)
    assert s.traces == 2 and s.cache_size == 2
    assert f._cache_size() == 2  # jit-compatible surface for tests
    assert s.last_trace_s > 0.0


def test_sentinel_eqn_audit_is_lazy_and_shape_based():
    s = Sentinel("t_eqns")
    f = s.wrap(lambda x: jnp.sin(x) + jnp.cos(x))
    assert s.eqns == 0  # nothing traced yet
    f(jnp.ones(3))
    m = s.metrics()  # resolves the pending abstract re-trace
    assert m["eqns"] >= 3  # sin + cos + add at minimum
    assert m["count"] == 1 and m["calls"] == 1


def test_instrument_decorator_with_static_argnames():
    @instrument("t_static", static_argnames=("k",))
    def scale(x, *, k):
        return x * k

    a = jnp.ones(2)
    assert np.array_equal(np.asarray(scale(a, k=3)), [3.0, 3.0])
    scale(a, k=3)
    scale(a, k=4)  # new static value -> retrace
    assert scale.sentinel.traces == 2 and scale.sentinel.calls == 3


def test_compiled_text_is_the_last_traced_program():
    """``compiled_text`` re-lowers the last traced call's abstract
    arguments: the program that ran, shapes and all."""
    @instrument("t_text")
    def double(x):
        return x * 2

    with pytest.raises(RuntimeError, match="nothing traced"):
        double.sentinel.compiled_text()
    double(jnp.ones((3,)))
    double(jnp.ones((5, 7)))  # retrace: the newest program wins
    text = double.sentinel.compiled_text()
    assert "f32[5,7]" in text and "f32[3]" not in text


def test_instrument_donate_argnums_preserved():
    @instrument("t_donate", donate_argnums=(0,))
    def bump(x):
        return x + 1

    x = jnp.zeros(4)
    y = bump(x)
    assert np.array_equal(np.asarray(y), np.ones(4))
    # donated input buffer is consumed — jit semantics pass through
    with pytest.raises(RuntimeError):
        np.asarray(x)


def test_compile_metrics_aggregates_by_name():
    a, b = Sentinel("t_shared"), Sentinel("t_shared")
    fa, fb = a.wrap(lambda x: x + 1), b.wrap(lambda x: x - 1)
    fa(jnp.ones(2))
    fb(jnp.ones(2))
    fb(jnp.ones(3))
    agg = profiling.compile_metrics()["t_shared"]
    assert agg["count"] == 3 and agg["calls"] == 3
    assert agg["cache_size"] == 3  # 1 (fa) + 2 (fb)


def test_count_eqns_recurses_scan_bodies():
    def scanned(x):
        def body(c, _):
            return c * 2 + 1, c

        return jax.lax.scan(body, x, None, length=4)

    n = count_eqns(jax.make_jaxpr(scanned)(jnp.float32(1)))
    assert n > 2  # the scan eqn plus its body's eqns


# ---------------------------------------------------------------------------
# engine acceptance: compile/<fn>/count flat across repeated batches
# ---------------------------------------------------------------------------


def test_engine_decode_loop_count_stays_flat_across_batches(cfg_params):
    """THE retrace-regression detector: same-shape request batches must
    reuse the compiled loop — any count growth is the pre-PR-8
    temperature-bug signature (obs_bench gates the same invariant inside
    its timed rounds, with the <=5% overhead gate alongside)."""
    cfg, params = cfg_params
    eng = ServeEngine(cfg, params, max_len=96)
    prompt = list(range(1, 17))
    eng.generate([Request(0, list(prompt), max_new_tokens=4)])
    base = eng._loop_sentinel.traces
    assert base == 1  # first bucket: exactly one trace
    for i in range(1, 4):  # repeated same-shape batches, varied temps
        eng.generate([Request(i, list(prompt), max_new_tokens=4,
                              temperature=0.5 * i)])
    assert eng._loop_sentinel.traces == base  # FLAT
    # a new steps bucket is a legitimate compile: exactly one more
    eng.generate([Request(9, list(prompt), max_new_tokens=6)])
    assert eng._loop_sentinel.traces == base + 1
    tel = eng.telemetry()
    assert tel["compile/decode_loop/count"] >= base + 1  # global aggregate
    assert tel["compile/decode_loop/cache_size"] >= 2  # two buckets live
    assert tel["compile/prefill/count"] >= 1
    assert tel["compile/decode_loop/eqns"] > 0  # always-on audit


def test_engine_tenant_entry_points_report_compile_metrics(cfg_params):
    cfg, params = cfg_params
    eng = ServeEngine(cfg, params, max_len=96, tenants={"a": 2, "b": 2})
    eng.generate([Request(0, list(range(1, 17)), max_new_tokens=3,
                          tenant_id="a")])
    tel = eng.telemetry()
    assert tel["compile/decide_batch/count"] >= 1
    assert tel["compile/tenancy_step/count"] >= 1


# ---------------------------------------------------------------------------
# spans: percentiles + sync discipline
# ---------------------------------------------------------------------------


def test_spans_percentiles_over_recent_window():
    ss = SpanSet(max_samples=64)
    for _ in range(10):
        with ss.span("phase"):
            pass
    m = ss.metrics()["phase"]
    assert m["calls"] == 10
    assert 0.0 <= m["p50_s"] <= m["p95_s"] <= m["max_s"]


def test_spans_sync_mode_blocks_on_ready_values():
    ss = SpanSet(sync=True)
    with ss.span("decode") as sp:
        out = sp.ready(jnp.ones((64, 64)) @ jnp.ones((64, 64)))
    assert np.asarray(out)[0, 0] == 64.0
    assert ss.metrics()["decode"]["calls"] == 1
    # sync=False: ready() is free and nothing is blocked on at close
    ss2 = SpanSet(sync=False)
    with ss2.span("decode") as sp:
        sp.ready(jnp.ones(2))
    assert ss2.metrics()["decode"]["calls"] == 1


def test_span_is_a_trace_annotation_on_the_host_plane(tmp_path):
    """A span opens a profiler annotation of its name: a capture shows it on
    the host plane, on the clock of the device trace."""
    ss = SpanSet()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with ss.span("span_on_the_profiler_clock"):
            jnp.ones(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    host = [e.name for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]
    assert "span_on_the_profiler_clock" in host
    assert ss.metrics()["span_on_the_profiler_clock"]["calls"] == 1


# ---------------------------------------------------------------------------
# op-to-scope maps (Sentinel.op_scopes)
# ---------------------------------------------------------------------------


def test_op_scopes_fusion_spanning_two_scopes_is_mixed():
    """XLA fuses the elementwise chain of two scopes into one loop fusion:
    the fusion's instructions span both scopes, so it maps to ``mixed``."""

    def f(x, y):
        with jax.named_scope("core_a"):
            u = jnp.sin(x) * 2
        with jax.named_scope("core_b"):
            return jnp.cos(u) + y

    s = Sentinel("t_mixed", scopes=("core_a", "core_b"))
    s.wrap(f)(jnp.ones(64), jnp.ones(64))
    fusions = {k: v for k, v in s.op_scopes().items() if "fusion" in k}
    assert list(fusions.values()) == [profiling.MIXED]


def test_op_scopes_is_computed_once_per_traced_program(monkeypatch):
    s = Sentinel("t_scopes_cache", scopes=("core_a",))
    g = s.wrap(lambda x: x * 2)
    g(jnp.ones(4))
    texts = []
    real = s.compiled_text
    monkeypatch.setattr(s, "compiled_text",
                        lambda: texts.append(1) or real())
    first = s.op_scopes()
    assert s.op_scopes() is first and len(texts) == 1
    g(jnp.ones(4))  # same shape: no trace, same map
    assert s.op_scopes() is first and len(texts) == 1
    g(jnp.ones(5))  # a new program: a new map
    s.op_scopes()
    assert len(texts) == 2


# a loop whose body holds every rule of ``op_scopes``: a constant broadcast
# the compiler shares across scopes, a fusion of two scopes' work, a gather
# expansion that keeps its path on the fusion alone, an op with no metadata
# that reads one scope's result, and glue that reads two
_HLO = """\
HloModule m, is_scheduled=true

%fused_shared (p0: s32[8]) -> s32[8] {
  %p0 = s32[8]{0} parameter(0)
  %c = s32[] constant(1)
  %b = s32[8]{0} broadcast(%c), dimensions={}, metadata={op_name="jit(f)/while/body/sweep_arc/broadcast_in_dim"}
  ROOT %add.1 = s32[8]{0} add(%p0, %b), metadata={op_name="jit(f)/while/body/sweep_car/add"}
}

%fused_two (p0.1: s32[8]) -> s32[8] {
  %p0.1 = s32[8]{0} parameter(0)
  %neg.1 = s32[8]{0} negate(%p0.1), metadata={op_name="jit(f)/while/body/sweep_flat/neg"}
  ROOT %abs.1 = s32[8]{0} abs(%neg.1), metadata={op_name="jit(f)/while/body/sweep_arc/abs"}
}

%fused_gather (p0.2: s32[8]) -> s32[8] {
  %p0.2 = s32[8]{0} parameter(0)
  ROOT %gather.1 = s32[8]{0} negate(%p0.2), metadata={op_name="gather"}
}

%body (arg: (s32[], s32[8])) -> (s32[], s32[8]) {
  %arg = (s32[], s32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = s32[8]{0} get-tuple-element(%arg), index=1
  %shared_fusion = s32[8]{0} fusion(%x), kind=kLoop, calls=%fused_shared
  %two_fusion = s32[8]{0} fusion(%shared_fusion), kind=kLoop, calls=%fused_two
  %gather_fusion = s32[8]{0} fusion(%x), kind=kCustom, calls=%fused_gather, metadata={op_name="jit(f)/while/body/sweep_flat/jit(take_along_axis)/gather"}
  %copy.3 = s32[8]{0} copy(%gather_fusion)
  %glue = s32[8]{0} add(%copy.3, %shared_fusion)
  %one = s32[] constant(1)
  %next = s32[] add(%i, %one)
  ROOT %tuple.1 = (s32[], s32[8]{0}) tuple(%next, %glue)
}

%cond (arg.1: (s32[], s32[8])) -> pred[] {
  %arg.1 = (s32[], s32[8]{0}) parameter(0)
  %j = s32[] get-tuple-element(%arg.1), index=0
  %n = s32[] constant(4)
  ROOT %lt = pred[] compare(%j, %n), direction=LT
}

ENTRY %main (a: s32[8]) -> s32[8] {
  %a = s32[8]{0} parameter(0)
  %zero = s32[] constant(0)
  %t = (s32[], s32[8]{0}) tuple(%zero, %a)
  %while.1 = (s32[], s32[8]{0}) while(%t), condition=%cond, body=%body
  ROOT %out = s32[8]{0} get-tuple-element(%while.1), index=1
}
"""


def test_op_scopes_rules_on_hand_written_hlo():
    m = profiling.op_scopes(_HLO, ("sweep_flat", "sweep_arc", "sweep_car"))
    assert m["shared_fusion"] == "sweep_car"  # the shared broadcast left out
    assert m["two_fusion"] == profiling.MIXED
    assert m["gather_fusion"] == "sweep_flat"  # the fusion's own path
    assert m["copy.3"] == "sweep_flat"  # reads one scope's result
    assert m["glue"] == ""  # reads two
    assert m["next"] == m["lt"] == m["while.1"] == ""
    # only ops that run: no fused instruction is in the map
    assert not {"add.1", "neg.1", "gather.1", "b"} & m.keys()


# ---------------------------------------------------------------------------
# trace capture cadence
# ---------------------------------------------------------------------------


def test_trace_capture_cadence_and_files(tmp_path):
    cap = TraceCapture(str(tmp_path / "prof"), every=4)
    seen = []
    for _ in range(5):  # batches of 2: first batch + each crossing of 4
        with cap.maybe(2) as capturing:
            seen.append(capturing)
            jnp.ones(8).sum().block_until_ready()
    # captures: seen==0 (first), 2->4 crossing, 6->8 crossing; 4->6 and
    # 8->10 stay inside a window
    assert seen == [True, True, False, True, False]
    assert cap.captures == 3 and cap.seen == 10
    assert cap.metrics()["captures"] == 3
    # the profiler actually wrote a trace directory
    assert any((tmp_path / "prof").rglob("*"))


def test_trace_capture_that_cannot_start_raises(tmp_path):
    """A capture ``profile_dir`` asked for is never dropped in silence: with
    another profiler session active, the due capture raises."""
    cap = TraceCapture(str(tmp_path / "prof"), every=1)
    jax.profiler.start_trace(str(tmp_path / "other"))
    try:
        with pytest.raises(RuntimeError, match="already been started"):
            with cap.maybe(1):
                pass
    finally:
        jax.profiler.stop_trace()
    assert cap.captures == 0


# ---------------------------------------------------------------------------
# live export: HTTP endpoint + periodic JSONL logger
# ---------------------------------------------------------------------------


def test_metrics_server_serves_prometheus_and_json():
    snap = {"serve/requests": 4, "tenant/a/hit_ratio": 0.5,
            "plane": np.asarray([1, 2])}
    with MetricsServer(lambda: snap, port=0) as srv:
        base = f"http://127.0.0.1:{srv.port}"
        text = urllib.request.urlopen(base + "/metrics").read().decode()
        assert "awrp_serve_requests 4\n" in text
        assert "# HELP awrp_serve_requests serve/requests\n" in text
        body = urllib.request.urlopen(base + "/metrics.json").read()
        doc = json.loads(body)
        assert doc["serve/requests"] == 4 and doc["plane"] == [1, 2]
        ok = urllib.request.urlopen(base + "/healthz").read()
        assert ok == b"ok\n"
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/nope")


def test_metrics_server_snapshot_error_is_500_not_fatal():
    def boom():
        raise RuntimeError("provider exploded")

    with MetricsServer(boom, port=0) as srv:
        base = f"http://127.0.0.1:{srv.port}"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/metrics")
        assert ei.value.code == 500
        # the server survives the error
        assert urllib.request.urlopen(base + "/healthz").read() == b"ok\n"


def test_snapshot_logger_appends_final_line_on_stop(tmp_path):
    path = tmp_path / "snap.jsonl"
    calls = []

    def snap():
        calls.append(1)
        return {"serve/requests": len(calls)}

    lg = SnapshotLogger(snap, str(path), interval_s=60.0,
                        extra={"arch": "x"}).start()
    lg.stop()  # long interval: only the final flush fires
    lines = path.read_text().splitlines()
    assert len(lines) == 1 and lg.lines == 1 and lg.errors == 0
    rec = json.loads(lines[0])
    assert rec["arch"] == "x" and rec["serve/requests"] == 1 and "ts" in rec
