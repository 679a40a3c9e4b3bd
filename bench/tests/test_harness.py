"""The harness on the CPU: discovery by name, the refusal of a device that
is not a TPU, every cell's rehearsal, and the faults its check catches."""

import collections
import json
import os
import shutil
import subprocess
import sys
import types

import jax
import pytest

from bench import harness, traffic

ROOT = harness.ROOT
CELLS = [c["name"] for c in harness.load_benchmark()["workloads"]]

#: serving cells whose files are under bench/ but that BENCHMARK.json
#: lacks; a run of one gets its entries in a temporary copy
SERVE = {
    "config": {"name": "smollm-360m-pagedkv", "source": "x", "reduced": [],
               "file": "bench/configs/smollm-360m-pagedkv.json", "why": "x"},
    "cells": {"serve.longprompt": {
        "name": "serve.longprompt", "config": "smollm-360m-pagedkv",
        "traffic": "longprompt", "chips": 1, "why": "x"}},
}


def _root_with(workload, tmp_path):
    """A checkout whose BENCHMARK.json has ``workload``."""
    if workload in CELLS:
        return ROOT
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    (tmp_path / "src").symlink_to(ROOT / "src")
    bench = harness.load_benchmark()
    if SERVE["config"]["name"] not in [c["name"] for c in bench["configs"]]:
        bench["configs"].append(SERVE["config"])
    bench["workloads"].append(SERVE["cells"][workload])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def _run(*args, env=None, cwd=ROOT):
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return env


def test_measuring_run_refuses_a_device_that_is_not_a_tpu():
    p = _run("bench/run.py", "--workload", CELLS[0], "--seed", "1",
             "--seconds", "1", "--trace", "0", env=_cpu_env())
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not p.stdout.strip()


def test_a_kind_missing_from_the_peaks_table_is_an_error(monkeypatch):
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v9 x")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    with pytest.raises(KeyError):
        harness.device_info(1, rehearse=False)


def test_without_the_program_a_run_fails_and_prints_nothing(tmp_path):
    """Only BENCHMARK.json and bench/: even past the look for a chip
    (``--rehearse``), the run finds no system to drive."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = _run("bench/run.py", "--workload", CELLS[0], "--seed", "1",
             "--seconds", "1", "--rehearse", cwd=tmp_path, env=_cpu_env())
    assert p.returncode != 0 and not p.stdout.strip()
    assert "repro" in p.stderr


@pytest.mark.parametrize("workload", sorted(set(CELLS) | set(SERVE["cells"])))
def test_every_cell_rehearses_correct_and_prints_no_metric(workload,
                                                           tmp_path):
    out, record = harness.run_cell(workload, 2**31 + 77, 0.5, False,
                                   rehearse=True,
                                   root=_root_with(workload, tmp_path))
    assert out["correct"], out["checks"]
    assert "metrics" not in out and out["rehearsal"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert record["compiles_in_window"] == 0


def _cells_with_setup_alone(bench):
    """Cells whose only end-to-end metric is ``setup_s``."""
    return [c["name"] for c in bench["workloads"]
            if {m["name"] for m in harness.cell_metrics(
                bench, c["name"], "end_to_end")} <= {"setup_s"}]


def test_every_cell_reports_an_end_to_end_metric_besides_setup():
    assert _cells_with_setup_alone(harness.load_benchmark()) == []


def test_a_serving_cell_without_serve_tok_s_reports_setup_alone():
    bench = harness.load_benchmark()
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tok_s":
            m["workloads"].remove("serve.longctx")
    assert _cells_with_setup_alone(bench) == ["serve.longctx"]


@pytest.mark.parametrize("workload", CELLS)
def test_the_driver_computes_every_end_to_end_metric_of_its_cell(workload):
    """The names a cell's end-to-end metrics declare, less ``setup_s``,
    which the harness takes itself, are what its driver's ``end_to_end``
    returns, here on a window record whose every number is 1."""
    bench = harness.load_benchmark()
    cell, entry = harness.find_cell(bench, workload)
    cfg = harness.load_config(entry)
    run = harness.driver(cfg).Run(cfg, traffic.load_mix(cell["traffic"]),
                                  1, False)
    got = run.end_to_end(collections.defaultdict(lambda: 1))
    want = {m["name"] for m in harness.cell_metrics(bench, workload,
                                                    "end_to_end")}
    assert want - {"setup_s"} <= set(got)
    assert all(v > 0 for v in got.values())


@pytest.mark.parametrize("metric", [m["name"] for m in
                                    harness.load_benchmark()["per_layer"]])
def test_every_per_layer_metric_moves_an_end_to_end_metric_of_its_cells(
        metric):
    bench = harness.load_benchmark()
    m = {m["name"]: m for m in bench["per_layer"]}[metric]
    assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    for workload in m.get("workloads", CELLS):
        assert m["moves"] in {e["name"] for e in harness.cell_metrics(
            bench, workload, "end_to_end")}
    assert (ROOT / "bench" / "metrics" / f"{metric}.py").is_file()


def _hashes(root):
    return {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}


def test_new_config_mix_and_metric_are_found_from_new_files(tmp_path):
    """A later PR adds a configuration, a traffic mix, a cell and a metric
    by adding files and entries in BENCHMARK.json alone."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    (tmp_path / "src").symlink_to(ROOT / "src")
    before = _hashes(tmp_path / "bench")

    cfg = json.loads((ROOT / "bench/configs/table1-grid.json").read_text())
    cfg.update(name="grid-lru-fifo", policies=["lru", "fifo"],
               capacities=[16, 32])
    (tmp_path / "bench/configs/grid-lru-fifo.json").write_text(
        json.dumps(cfg))
    mix = json.loads((ROOT / "bench/traffic/paper64.json").read_text())
    mix.update(n_traces=4, length=500)
    (tmp_path / "bench/traffic/tiny4.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/sweep.calls.py").write_text(
        "def read(ctx):\n    return ctx.record['calls']\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "grid-lru-fifo", "source": "x",
                             "file": "bench/configs/grid-lru-fifo.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "sweep.tiny", "config":
                               "grid-lru-fifo", "traffic": "tiny4",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "sweep.calls", "unit": "calls",
                               "better": "higher", "source":
                               "program_counter", "layer": "sweep driver",
                               "moves": "sweep_accesses_per_s",
                               "workloads": ["sweep.tiny"]})
    bench["end_to_end"][0]["workloads"].append("sweep.tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    p = _run("bench/run.py", "--workload", "sweep.tiny", "--seed", "5",
             "--seconds", "0.2", "--rehearse", cwd=tmp_path, env=_cpu_env())
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] and out["checks"]["mismatched_rows"]["value"] == 0
    assert [m["name"] for m in harness.cell_metrics(
        harness.load_benchmark(tmp_path), "sweep.tiny", "per_layer")] == \
        ["sweep.calls"]
    ctx = types.SimpleNamespace(record={"calls": 3})
    assert harness.reader("sweep.calls", tmp_path).read(ctx) == 3
    after = _hashes(tmp_path / "bench")
    assert all(after[p] == b for p, b in before.items())


# -- faults of the timed path, planted under the harness --------------------


def _flip_hits(monkeypatch):
    from repro.core import jax_policies

    real = jax_policies.simulate_trace_batched

    def altered(*a, **k):
        hits = real(*a, **k)
        return hits.at[:, :, :, -1].set(~hits[:, :, :, -1])

    monkeypatch.setattr(jax_policies, "simulate_trace_batched", altered)


def _frozen_sweep_state(monkeypatch):
    from repro.core import policy_core

    real = policy_core.FlatCore.on_access

    def frozen(self, state, *a, **k):
        _, hit = real(self, state, *a, **k)
        return state, hit

    monkeypatch.setattr(policy_core.FlatCore, "on_access", frozen)


def _bfloat16_control(monkeypatch):
    """The control in the program's place: the plain reference with every
    quotient in bfloat16 serves the hits."""
    import jax.numpy as jnp
    import numpy as np

    from bench.refs import policies as ref
    from repro.core import jax_policies

    def control(traces, policies, caps, **_):
        return jnp.asarray(np.array([[[ref.hits(p, t, c, "bfloat16")
                                       for c in caps] for p in policies]
                                     for t in np.asarray(traces)]))

    monkeypatch.setattr(jax_policies, "simulate_trace_batched", control)


def _altered_token(monkeypatch):
    from repro.serve import engine

    real = engine.sample_traced

    def altered(logits, key, temperature, vocab):
        t = real(logits, key, temperature, vocab=vocab)
        return (t + 1) % vocab

    monkeypatch.setattr(engine, "sample_traced", altered)


def _frozen_pool(monkeypatch):
    from repro.cache import paged_kv

    monkeypatch.setattr(paged_kv, "insert_token",
                        lambda pool, *a, **k: pool)


FAULTS = [("sweep.table1", _flip_hits), ("sweep.table1", _frozen_sweep_state),
          ("serve.longctx", _altered_token), ("serve.longctx", _frozen_pool)]


@pytest.mark.parametrize("workload,plant", FAULTS,
                         ids=[f.__name__ for _, f in FAULTS])
def test_a_broken_timed_path_reads_not_correct(workload, plant, monkeypatch,
                                              tmp_path):
    root = _root_with(workload, tmp_path)
    jax.clear_caches()
    plant(monkeypatch)
    out, _ = harness.run_cell(workload, 31337, 0.2, False, rehearse=True,
                              root=root)
    jax.clear_caches()
    assert not out["correct"], out["checks"]


def test_the_bfloat16_control_reads_not_correct_at_the_cells_size(
        monkeypatch, tmp_path):
    """The sweep's control, put in the program's place, under the whole
    harness at ``sweep.table1``'s own traffic (64 traces x 1000)."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    (tmp_path / "src").symlink_to(ROOT / "src")
    cell, _ = harness.find_cell(harness.load_benchmark(), "sweep.table1")
    path = tmp_path / "bench" / "traffic" / f"{cell['traffic']}.json"
    mix = json.loads(path.read_text())
    del mix["rehearsal"]
    path.write_text(json.dumps(mix))
    _bfloat16_control(monkeypatch)
    out, _ = harness.run_cell("sweep.table1", 2**31 + 4242, 0.2, False,
                              rehearse=True, root=tmp_path)
    assert not out["correct"], out["checks"]
    assert out["checks"]["mismatched_rows"]["value"] >= 1
