"""The trace reduction on a small trace recorded on a TPU v5e: two calls of
a tiny sweep (2 traces x 200 accesses, awrp and lru at 8 and 16 blocks)
inside ``bench/window``, each followed by 20 ms of annotated host work."""

import gzip
import pathlib
import shutil

import pytest

from bench import trace_reduce as tr

FIXTURE = pathlib.Path(__file__).parent / "data" / "small.xplane.pb.gz"


@pytest.fixture(scope="module")
def path(tmp_path_factory):
    out = tmp_path_factory.mktemp("trace") / "small.xplane.pb"
    with gzip.open(FIXTURE) as src, open(out, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return out


@pytest.fixture(scope="module")
def red(path):
    return tr.reduce_trace(path)


@pytest.fixture(scope="module")
def raw(path):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out = {"ops": [], "modules": [], "window": None}
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                ev = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                if plane.name == "/host:CPU" and e.name == "bench/window":
                    out["window"] = ev
                elif plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    out["ops"].append(ev)
                elif plane.name == "/device:TPU:0" and \
                        line.name == "XLA Modules":
                    out["modules"].append(ev)
    return out


def test_busy_time_is_the_union_of_device_ops(red, raw):
    w0 = min(raw["window"][0], min(s for s, _, _ in raw["ops"]))
    w1 = max(raw["window"][1], max(e for _, e, _ in raw["ops"]))
    busy, end = 0.0, w0
    for s, e, _ in sorted(raw["ops"]):
        s, e = max(s, end, w0), min(e, w1)
        if e > s:
            busy += e - s
            end = e
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    assert red["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-9)
    assert 0 < red["busy_s"] < red["window_s"]


def test_programs_and_kernel_are_named_and_counted(red, raw):
    assert red["module_runs"]["jit__simulate_batched_impl"] == 2
    sweep = [e - s for s, e, n in raw["modules"]
             if n.startswith("jit__simulate_batched_impl(")]
    assert red["modules"]["jit__simulate_batched_impl"] == pytest.approx(
        sum(sweep) * 1e-9)
    t, n = tr.kernel(red, "awrp_select_rows")
    launches = [e - s for s, e, name in raw["ops"]
                if tr.op_name(name).startswith("awrp_select_rows")]
    assert n == len(launches) == 2 * 200  # one launch a scan step
    assert t == pytest.approx(sum(launches) * 1e-9)
    # leaf ops only: the while loop that holds them is not counted
    assert not any(k.startswith("while") for k in red["ops"])
    assert len(red["top_ops"]) == 10
    assert red["top_ops"] == sorted(red["top_ops"], key=lambda x: -x[1])


def test_idle_gaps_go_to_what_the_host_was_doing(red):
    gaps = dict(red["idle_gaps"])
    assert len(red["idle_gaps"]) <= 10
    # the two 20 ms sleeps are the longest idle stretches
    top, secs = red["idle_gaps"][0]
    assert "sleep" in top or top == "bench/host_work"
    assert secs >= 0.04
    assert sum(gaps.values()) <= red["window_s"] - red["busy_s"] + 1e-9


def test_names():
    assert tr.op_name("%awrp_select_rows.8 = s32[2048,1] custom-call(x)") \
        == "awrp_select_rows.8"
    assert tr.base_name("fusion.134") == "fusion"
    assert tr.module_name("jit_loop(1124802)") == "jit_loop"
