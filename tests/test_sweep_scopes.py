"""The sweep scan step's policy-core scopes and the benchmark's readers of
them.

``core.jax_policies`` wraps each policy core's call in the scan step in a
``jax.named_scope`` (``CORE_SCOPES``); ``Sentinel.op_scopes()`` maps every
op of the compiled sweep program to the core it belongs to; the
benchmark's ``bench/metrics/sweep.{flat,arc,car,unscoped}_step_us.py``
readers split a traced window's device time by that map.
"""

import importlib.util
import pathlib
import re
import types

import numpy as np
import pytest

from repro.core import sharding
from repro.core.jax_policies import (
    CORE_SCOPES,
    _simulate_batched_impl,
    simulate_trace_batched,
)
from repro.obs import profiling

ROOT = pathlib.Path(__file__).resolve().parents[1]
POLICIES = ("awrp", "lru", "fifo", "lfu", "arc", "car")
SCOPES = tuple(CORE_SCOPES.values())


@pytest.fixture(scope="module", params=["unsharded", "rows_mesh", "renorm"])
def sweep_program(request):
    """The tiny grid's compiled text and op map: unsharded, through the
    one-device rows mesh (``_sharded_groups_scan``), and with the adaptive
    rows' stamp renormalization in the step (a ``lax.cond``).  The trace
    shape is this module's own, so the call traces and the map is its
    program's."""
    s = _simulate_batched_impl.sentinel
    before = s.traces
    mesh = sharding.rows_mesh(1) if request.param == "rows_mesh" else None
    renorm = 64 if request.param == "renorm" else None
    traces = np.random.default_rng(7).integers(0, 9, (3, 37))
    simulate_trace_batched(traces, POLICIES, (4, 6), mesh=mesh,
                           _renorm_at=renorm)
    assert s.traces == before + 1
    return s.compiled_text(), s.op_scopes()


def test_op_scopes_names_each_core_of_the_sweep_scan(sweep_program):
    _, scopes = sweep_program
    assert set(SCOPES) <= set(scopes.values())


def test_op_scopes_maps_every_fusion_and_custom_call(sweep_program):
    """Every fusion and custom call of the entry computation, of each loop
    body and of each conditional branch is in the map: the ops a device
    trace names."""
    text, scopes = sweep_program
    comps = dict(re.findall(r"^(?:ENTRY )?%(\S+) [^\n]*\{\n(.*?)^\}",
                            text, re.M | re.S))
    entry = re.search(r"^ENTRY %(\S+) ", text, re.M).group(1)
    bodies = set(re.findall(r"\bbody=%([^\s,]+)", text))
    for group in re.findall(r"\bbranch_computations=\{([^}]*)\}", text):
        bodies |= {c.strip().lstrip("%") for c in group.split(",")}
    ops = [name for comp in {entry} | bodies for name in re.findall(
        r"^\s+(?:ROOT )?%(\S+) = [^\n]*? (?:fusion|custom-call)\(",
        comps[comp], re.M)]
    assert bodies and ops
    assert set(ops) <= scopes.keys()


def _reader(name):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


PROGRAM = "jit__simulate_batched_impl"
STEPS = 1000
# device seconds over a traced window of STEPS scan steps
OPS = {"fusion.1": 4e-3, "awrp_select_rows.2": 2e-3, "fusion.3": 1e-3,
       "fusion.4": 1.5e-3, "fusion.5": 0.5e-3, "copy.6": 0.25e-3,
       "fusion.7": 2e-6}  # the last: another program's op
MAP = {"fusion.1": "sweep_flat", "awrp_select_rows.2": "sweep_flat",
       "fusion.3": "sweep_arc", "fusion.4": "sweep_car",
       "fusion.5": profiling.MIXED, "copy.6": "", "while.8": ""}
MAPPED = sum(v for k, v in OPS.items() if k in MAP)
# microseconds a step each reader should read
WANT = {"sweep.flat_step_us": 6.0, "sweep.arc_step_us": 1.0,
        "sweep.car_step_us": 1.5, "sweep.unscoped_step_us": 0.75}


def _ctx(module_s=MAPPED):
    return types.SimpleNamespace(
        trace={"ops": dict(OPS), "modules": {PROGRAM: module_s}},
        record={"steps": STEPS})


@pytest.fixture
def op_map(monkeypatch):
    """Stand the sentinel's map in for a traced program's."""
    def use(scopes):
        monkeypatch.setattr(_simulate_batched_impl.sentinel, "op_scopes",
                            scopes)
    return use


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_gives_its_scope_per_step(name, op_map):
    op_map(lambda: MAP)
    assert _reader(name).read(_ctx()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("covered", [1.0, 0.97])
def test_readers_sum_to_step_us(covered, op_map):
    """The program's time in no op of its own (loop control between ops)
    goes to the unscoped rest, so the four sum to ``sweep.step_us``."""
    op_map(lambda: MAP)
    ctx = _ctx(MAPPED / covered)
    step_us = _reader("sweep.step_us").read(ctx)
    assert sum(_reader(n).read(ctx) for n in WANT) == pytest.approx(step_us)
    gap_us = MAPPED * (1 / covered - 1) / STEPS * 1e6
    assert _reader("sweep.unscoped_step_us").read(ctx) == pytest.approx(
        WANT["sweep.unscoped_step_us"] + gap_us)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_with_an_empty_map_reads_none(name, op_map):
    op_map(dict)
    assert _reader(name).read(_ctx()) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_under_95_percent_coverage_reads_none(name, op_map):
    op_map(lambda: MAP)
    assert _reader(name).read(_ctx(MAPPED / 0.949)) is None
    assert _reader(name).read(_ctx(MAPPED / 0.951)) is not None


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_of_a_program_without_op_scopes_reads_none(name, op_map):
    """A sentinel with no ``op_scopes`` (a program from before the map)
    gives no reading, and raises nothing."""
    op_map(None)
    assert _reader(name).read(_ctx()) is None
