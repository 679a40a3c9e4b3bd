"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

What it reads (as the TPU runtime writes it): planes ``/device:TPU:<n>``
with the lines ``XLA Modules`` (one event per program run, named
``<jit name>(<hash>)``) and ``XLA Ops`` (one event per HLO op run, named
by its HLO text ``%<op> = ...``; a ``while``, ``conditional`` or ``call``
op spans the ops it runs), and the line of the host plane that holds the
harness's ``bench/...`` ``jax.profiler.TraceAnnotation`` spans, where the
Python calls of that thread appear too.  Host and device events share one
clock, to within about a millisecond.

What it gives, over the window: the ``bench/window`` annotation's span,
widened to take in every device event of the trace (the harness starts
the trace after set-up, so all of them are the window's):

* ``busy_s``: the union of the device's op intervals, averaged over chips;
* ``modules``: device seconds per program (jit name without the hash),
  and ``module_runs``;
* ``ops``/``op_runs``: device seconds and runs per op, leaf ops only
  (``while``/``conditional``/``call`` are left out, their ops are in);
* ``top_ops``: the ten leaf ops that took most time;
* ``idle_gaps``: the idle time between device ops, by what the host was
  doing: each gap of at least ``GAP_NS`` goes to the innermost host span
  around its midpoint; shorter gaps are summed under one name.
"""

from __future__ import annotations

import collections
import re

import numpy as np

GAP_NS = 10_000
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")
WINDOW = "bench/window"


def op_name(text: str) -> str:
    """``%fusion.12 = s32[...] fusion(...)`` -> ``fusion.12``."""
    m = re.match(r"^%?([^\s=]+)", text)
    return m.group(1) if m else text


def base_name(name: str) -> str:
    """``awrp_select_rows.8`` -> ``awrp_select_rows``."""
    return re.sub(r"\.\d+$", "", name)


def module_name(text: str) -> str:
    """``jit_loop(1124...)`` -> ``jit_loop``."""
    return text.split("(", 1)[0]


def _union(starts: np.ndarray, ends: np.ndarray):
    """Merged intervals (sorted, disjoint) of the given ones."""
    if not len(starts):
        return np.zeros(0), np.zeros(0)
    o = np.argsort(starts, kind="stable")
    s, e = starts[o], ends[o]
    run_end = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.append(run_end[idx[1:] - 1], run_end[-1])


def reduce_trace(path: str) -> dict:
    """The reduction above, as a plain dict (seconds unless named)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    host = []  # (start, end, name) of every event of the harness's thread
    devices = []
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            lines = [[(e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in line.events] for line in plane.lines]
            host = next((evs for evs in lines
                         if any(n.startswith("bench/") for _, _, n in evs)),
                        [])
        elif _DEVICE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            devices.append({
                name: [(e.start_ns, e.duration_ns, e.name)
                       for e in lines[name].events] if name in lines else []
                for name in ("XLA Modules", "XLA Ops")})
    if not devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    # the trace starts after set-up, so every device event in it belongs
    # to the window; the window takes them all in
    allev = [(s, s + d) for dev in devices for s, d, _ in dev["XLA Ops"]]
    w0, w1 = min(a for a, _ in allev), max(b for _, b in allev)
    for s, e, n in host:
        if n == WINDOW:
            w0, w1 = min(w0, s), max(w1, e)
            break

    busy, modules, mod_runs = [], collections.Counter(), collections.Counter()
    ops, op_runs = collections.Counter(), collections.Counter()
    gaps = []
    for dev in devices:
        for s, d, n in dev["XLA Modules"]:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                modules[module_name(n)] += (b - a) * 1e-9
                mod_runs[module_name(n)] += 1
        ev = dev["XLA Ops"]
        st = np.array([s for s, _, _ in ev], np.float64)
        en = st + np.array([d for _, d, _ in ev], np.float64)
        st, en = np.clip(st, w0, w1), np.clip(en, w0, w1)
        for (s, d, n), a, b in zip(ev, st, en):
            name = op_name(n)
            if b > a and not _CONTAINER.match(name):
                ops[name] += (b - a) * 1e-9
                op_runs[name] += 1
        us, ue = _union(st[en > st], en[en > st])
        busy.append(float((ue - us).sum()) * 1e-9)
        g0 = np.concatenate([[w0], ue])
        g1 = np.concatenate([us, [w1]])
        keep = g1 > g0
        gaps.append((g0[keep], g1[keep]))

    # idle gaps by the innermost host span around each gap's midpoint
    idle = collections.Counter()
    hs = np.array([h[0] for h in host], np.float64)
    he = np.array([h[1] for h in host], np.float64)
    for g0, g1 in gaps:
        short = (g1 - g0) < GAP_NS
        idle[f"gaps under {GAP_NS // 1000} us"] += float(
            (g1 - g0)[short].sum()) * 1e-9 / len(devices)
        for a, b in zip(g0[~short], g1[~short]):
            mid = (a + b) / 2
            inside = np.flatnonzero((hs <= mid) & (he >= mid))
            if inside.size:
                k = inside[np.argmin(he[inside] - hs[inside])]
                name = host[k][2]
            else:
                name = "no host span"
            idle[name] += (b - a) * 1e-9 / len(devices)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": float(np.mean(busy)),
        "devices": len(devices),
        "modules": {k: v / len(devices) for k, v in modules.items()},
        "module_runs": dict(mod_runs),
        "ops": {k: v / len(devices) for k, v in ops.items()},
        "op_runs": dict(op_runs),
        "top_ops": [[k, v / len(devices)] for k, v in ops.most_common(10)],
        "idle_gaps": [[k, v] for k, v in idle.most_common(10)],
    }


def kernel(red: dict, base: str):
    """``(device seconds, runs)`` of every op whose base name is ``base``."""
    t = sum(v for k, v in red["ops"].items() if base_name(k) == base)
    n = sum(v for k, v in red["op_runs"].items() if base_name(k) == base)
    return t, n
