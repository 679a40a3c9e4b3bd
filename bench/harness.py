"""The harness: finds a cell's configuration, traffic mix, driver and
per-layer metric readers by the names in ``BENCHMARK.json``, runs the
cell once, and assembles the result line.

Everything that belongs to one configuration, mix or metric is a file of
its own, found by name:

* ``bench/configs/<config>.json`` (the entry's ``file``) names its driver,
  ``bench/drivers/<driver>.py``, which builds what the window drives;
* ``bench/traffic/<traffic>.json`` is read by ``bench/traffic.py``;
* ``bench/metrics/<metric>.py`` defines ``read(ctx)``, which returns the
  metric's value or ``None`` where it finds nothing to read.

A driver module defines ``Run(cfg, mix, seed, rehearse)`` with
``setup()``, ``window(seconds, max_calls)`` (returns the window record),
``end_to_end(record)``, ``release()`` and ``check()`` (returns
``(name, value, limit)`` triples; the run is correct when every value is
at most its limit).
"""

from __future__ import annotations

import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
import types

from bench import peaks, trace_reduce, traffic

ROOT = pathlib.Path(__file__).resolve().parents[1]


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, workload: str):
    """``(cell, config entry)`` of ``workload``."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r}; have "
                         f"{sorted(cells)}")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return cell, config


def load_config(entry: dict, root: pathlib.Path = ROOT, *,
                rehearsal: bool = False) -> dict:
    """A configuration's file; ``rehearsal`` applies its tiny CPU sizes."""
    cfg = json.loads((root / entry["file"]).read_text())
    if rehearsal:
        for key, val in cfg.get("rehearsal", {}).items():
            cfg[key] = {**cfg[key], **val} if isinstance(val, dict) else val
    cfg.pop("rehearsal", None)
    return cfg


def driver(cfg: dict, root: pathlib.Path = ROOT):
    return _module(root / "bench" / "drivers" / f"{cfg['driver']}.py",
                   f"bench_driver_{cfg['driver']}")


def reader(name: str, root: pathlib.Path = ROOT):
    return _module(root / "bench" / "metrics" / f"{name}.py",
                   f"bench_metric_{name.replace('.', '_')}")


def cell_metrics(bench: dict, workload: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that ``workload``
    reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def device_info(chips: int, rehearse: bool) -> dict:
    """The device as JAX reports it; a measuring run without enough TPU
    chips of a kind in the peaks table exits non-zero."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if not rehearse:
        if d.platform != "tpu":
            raise SystemExit(f"bench: needs a TPU; JAX found {d.platform!r}")
        if len(devs) < chips:
            raise SystemExit(f"bench: the cell needs {chips} chips; JAX "
                             f"found {len(devs)}")
        peaks.peaks(d.device_kind)  # a kind with no peaks is an error
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def memory_peak(chips: int):
    import jax

    peaks_ = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks_.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_) if peaks_ else None


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             rehearse: bool = False, root: pathlib.Path = ROOT,
             t_start: float | None = None):
    """Run one cell once; returns the result (``checks`` last) and the
    window's record."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = load_benchmark(root)
    cell, entry = find_cell(bench, workload)
    cfg = load_config(entry, root, rehearsal=rehearse)
    mix = traffic.load_mix(cell["traffic"], rehearsal=rehearse, root=root)
    device = device_info(cell["chips"], rehearse)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    run = driver(cfg, root).Run(cfg, mix, seed, rehearse)
    run.setup()
    setup_s = time.perf_counter() - t_start
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tdir)
    try:
        with annotate(trace_reduce.WINDOW):
            record = run.window(seconds, mix.get("trace_calls") if trace
                                else None)
    finally:
        if trace:
            jax.profiler.stop_trace()
    record["memory_peak_bytes"] = memory_peak(cell["chips"])
    red = None
    if trace:
        found = sorted(pathlib.Path(tdir).rglob("*.xplane.pb"))
        red = trace_reduce.reduce_trace(found[-1])
        shutil.rmtree(tdir, ignore_errors=True)
    e2e = run.end_to_end(record)
    run.release()
    gc.collect()
    t_check = time.perf_counter()
    checks = run.check()
    record.update(setup_s=setup_s, check_s=time.perf_counter() - t_check)

    out = {"correct": all(v <= lim for _, v, lim in checks),
           "attempted": record["attempted"], "failed": record["failed"]}
    if rehearse:
        out["rehearsal"] = True
    elif trace:
        ctx = types.SimpleNamespace(trace=red, record=record, config=cfg,
                                    mix=mix, device_kind=device["kind"],
                                    peaks=peaks.peaks(device["kind"]))
        metrics = {}
        for m in cell_metrics(bench, workload, "per_layer"):
            val = reader(m["name"], root).read(ctx)
            if val is not None:
                metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
        out["metrics"] = metrics
    else:
        values = dict(e2e, setup_s=setup_s)
        out["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell_metrics(bench, workload, "end_to_end")}
    if not rehearse:
        out["device"] = dict(device,
                             memory_peak_bytes=record["memory_peak_bytes"])
        if trace:
            out["device"].update(busy_s=red["busy_s"],
                                 window_s=red["window_s"])
            out["breakdown"] = {"device_ops": red["top_ops"],
                                "idle_gaps": red["idle_gaps"]}
    out["checks"] = {name: {"value": value, "limit": limit}
                     for name, value, limit in checks}
    return out, record


def main(args, t_start: float) -> int:
    out, record = run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), rehearse=args.rehearse,
                           t_start=t_start)
    print(f"bench: {args.workload} seed {args.seed}: "
          + json.dumps({k: v for k, v in record.items()
                        if isinstance(v, (int, float, str))}),
          file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
