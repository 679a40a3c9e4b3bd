"""Compile/retrace sentinels + profiler capture hooks (DESIGN.md §12).

The performance half of observability: the serving stack must be able to
see its own COMPILE behavior.  A silent retrace regression (the pre-PR-8
temperature bug: every new sampling temperature recompiled the whole
decode loop) shows up in wall-clock time but not in any counter — unless
tracing itself is counted.  This module wraps jitted entry points in a
*sentinel* layer that counts traces, measures trace wall time, reads the
jit compilation-cache size, and audits the traced program's jaxpr
equation count (the PR 8 bench's dispatch-count idea promoted to a
first-class always-on metric), all mounted on the PR 9 metrics registry
as ``compile/<fn>/{count,calls,cache_size,last_trace_s,eqns}`` gauges.

How counting works: ``Sentinel.wrap(fun, **jit_kwargs)`` interposes a
host-side counter that increments whenever the *python body* of ``fun``
executes — which under ``jax.jit`` happens exactly at trace time — and
returns a callable that behaves like ``jax.jit(fun, **jit_kwargs)``.
The wrapper costs one python-level indirection per call (measured in the
``obs_bench`` ≤5% overhead gate) and NOTHING inside compiled code.

The jaxpr equation audit is LAZY: a detected trace stores only the
abstract shapes of the call's arguments (``jax.ShapeDtypeStruct`` — no
buffers are retained, donation-safe), and the next ``compile_metrics()``
read re-traces the function abstractly to count equations.  Audits
therefore cost one abstract trace per (entry point × new input shape),
paid at the snapshot boundary, never on the hot path.

Sentinels register themselves in a process-global weak set, aggregated
by name: the serving engine, the tenancy manager, the sweep scan and the
fused-kernel wrappers all mount through the one ``compile_metrics``
provider, and two engines wrapping the same entry point sum into one
series (the Prometheus convention for process-global counters).

``TraceCapture`` is the opt-in ``jax.profiler`` hook:
``ServeEngine(profile_dir=...)`` captures one annotated device trace per
N requests into a directory TensorBoard/perfetto can open.

``PHASES`` is the module-global ``SpanSet`` for phase timers that have
no engine to live on (the sweep path); engine-scoped phases stay on
``ServeEngine.spans``.

``op_scopes`` joins a device trace to the program's layers: a profiler
trace names each device op by its instruction name in the compiled module
and carries no scope, while the module's optimized HLO text carries each
op's ``op_name`` path, where ``jax.named_scope`` components appear.
``Sentinel.op_scopes()`` maps the ops of the last traced program to the
scopes the sentinel was given.
"""

from __future__ import annotations

import contextlib
import functools
import re
import time
import weakref
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import numpy as np

from repro.obs.spans import SpanSet

__all__ = [
    "Sentinel",
    "instrument",
    "compile_metrics",
    "count_eqns",
    "op_scopes",
    "TraceCapture",
    "PHASES",
]

#: module-global phase spans for code with no engine to mount on (the
#: sweep path records its "sweep" phase here; ``launch/serve.py`` mounts
#: this next to the engine's own spans)
PHASES = SpanSet()

#: every live sentinel, aggregated by name in ``compile_metrics``
_ALL: "weakref.WeakSet[Sentinel]" = weakref.WeakSet()


def count_eqns(jaxpr) -> int:
    """Total equations in a (closed) jaxpr, recursing into nested jaxprs
    in eqn params (scan/cond/jit bodies) but NOT into a ``pallas_call``'s
    kernel — the kernel body runs inside ONE launch, so its equations are
    not separate dispatches.  This is the bench's per-step dispatch-count
    metric (DESIGN.md §10), shared with the always-on sentinel audits."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    n = len(jaxpr.eqns)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for item in (v if isinstance(v, (tuple, list)) else (v,)):
                if hasattr(item, "eqns") or hasattr(item, "jaxpr"):
                    n += count_eqns(item)
    return n


#: scope of a fusion whose fused instructions lie under two or more scopes
MIXED = "mixed"

_HEADER = re.compile(r"^(ENTRY )?%(\S+) .*\{$")
_INSTR = re.compile(r"^\s+(?:ROOT )?%(\S+) = ")
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
_OPERAND = re.compile(r"%([^\s,()]+)")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([^\s,]+)")
# computations whose instructions run as ops of their own: loop bodies and
# conditions, conditional branches
_CONTROL = re.compile(
    r"\b(?:condition|body|true_computation|false_computation)=%([^\s,]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
# fused instructions that do no work of their own scope: the compiler
# shares one constant or broadcast among every scope that uses it
_SHARED = frozenset({"parameter", "constant", "broadcast", "iota"})


def op_scopes(hlo_text: str, scopes: Sequence[str]) -> Dict[str, str]:
    """Map each op of a compiled module that runs as an op of its own (the
    instructions of the entry computation and of every loop body,
    condition and branch they reach) to the scope of
    ``scopes`` it belongs to, ``MIXED`` or ``""``.

    An instruction's scope is the innermost component of its ``op_name``
    that is one of ``scopes``.  An op takes, in this order:

    1. for a fusion (or async op), the scopes of the instructions of the
       computation it calls, nested fusions included, parameters, constants
       and broadcasts left out: the one scope they lie under, ``MIXED``
       where they span two or more;
    2. its own scope (a gather the compiler expands keeps its path on the
       fusion alone);
    3. where the compiler left it no scope (a scatter it rewrites), the one
       scope of the instructions whose results it reads, where they agree;
    4. ``""``.

    ``hlo_text`` is a module's HLO text with metadata, as
    ``Compiled.as_text()`` prints it."""
    scopes = frozenset(scopes)
    comps: Dict[str, list] = {}
    entry, body = None, None
    for line in hlo_text.splitlines():
        m = _HEADER.match(line)
        if m:
            body = comps.setdefault(m.group(2), [])
            entry = m.group(2) if m.group(1) else entry
        elif line.startswith("}"):
            body = None
        elif body is not None and (m := _INSTR.match(line)):
            body.append((m.group(1), line))

    def own(line: str) -> str:
        m = _OP_NAME.search(line)
        found = [c for c in m.group(1).split("/") if c in scopes] if m else []
        return found[-1] if found else ""

    fused: Dict[str, frozenset] = {}

    def work(line: str) -> frozenset:
        """Scopes of the work a fusion does (step 1)."""
        m = _CALLS.search(line)
        if not m:
            return frozenset()
        comp = m.group(1)
        if comp not in fused:
            found = set()
            for _, inner in comps.get(comp, ()):
                op = _OPCODE.search(inner.split(" = ", 1)[1])
                if _CALLS.search(inner):
                    found |= work(inner)
                elif not op or op.group(1) not in _SHARED:
                    found.add(own(inner))
            fused[comp] = frozenset(found - {""})
        return fused[comp]

    out: Dict[str, str] = {}
    todo, seen = [entry] if entry else [], set()
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        local: Dict[str, str] = {}
        for name, line in comps.get(comp, ()):
            found = work(line)
            if len(found) > 1:
                local[name] = MIXED
            elif found:
                local[name] = next(iter(found))
            elif scope := own(line):
                local[name] = scope
            else:
                read = {local[o] for o in _OPERAND.findall(
                    line.split(" = ", 1)[1]) if o in local} - {""}
                local[name] = read.pop() if len(read) == 1 else ""
            todo += _CONTROL.findall(line)
            for group in _BRANCHES.findall(line):
                todo += [c.strip().lstrip("%") for c in group.split(",")]
        out.update(local)
    return out


def _abstract(x: Any) -> Any:
    """Array leaves -> ``ShapeDtypeStruct`` (no buffer retained; the lazy
    audit and ``compiled_text`` re-trace with these), keeping the placement
    of arrays committed to one (so the re-lowered program is the one that
    ran); everything else passes through (static operands, python scalars,
    meshes)."""
    if isinstance(x, (jax.Array, np.ndarray)):
        concrete = not isinstance(x, jax.core.Tracer)
        sharding = x.sharding if concrete and getattr(
            x, "committed", False) else None
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
    return x


class Sentinel:
    """Compile/retrace counters for one named jitted entry point.

    Mutable host object; one sentinel can wrap several jitted callables
    (the decode loop wraps one program per ``steps`` bucket under the ONE
    ``decode_loop`` sentinel — ``cache_size`` sums across them).  Metrics
    surface (per name, after aggregation):

    * ``count`` — traces ever taken (flat across repeated same-shape
      batches; growth without new shapes IS a retrace regression);
    * ``calls`` — wrapped calls ever made;
    * ``cache_size`` — live jit-cache entries (compiled program count);
    * ``last_trace_s`` — wall seconds of the most recent traced call
      (trace + lowering + compile, as the caller experienced it);
    * ``eqns`` — jaxpr equation count of the most recent trace (lazy
      audit; ``-1`` when the abstract re-trace failed).

    ``scopes`` names the ``jax.named_scope``s inside the program that
    ``op_scopes()`` maps its ops to.
    """

    def __init__(self, name: str, scopes: Sequence[str] = ()):
        self.name = str(name)
        self.scopes = tuple(scopes)
        self.calls = 0
        self.traces = 0
        self.last_trace_s = 0.0
        self.eqns = 0
        self._jits: list = []
        self._pending: Optional[tuple] = None
        self._last: Optional[tuple] = None
        self._op_scopes: Dict[str, str] = {}
        self._op_scopes_of: Optional[tuple] = None
        _ALL.add(self)

    @property
    def cache_size(self) -> int:
        """Total live jit-cache entries across every wrapped callable."""
        return sum(j._cache_size() for j in self._jits)

    def wrap(self, fun: Callable, *, audit_eqns: bool = True,
             **jit_kwargs) -> Callable:
        """``jax.jit(fun, **jit_kwargs)`` with this sentinel's counter
        layer interposed.  The returned callable dispatches exactly like
        the bare jit (donation, static args and sharding untouched) and
        exposes ``_cache_size()`` and ``.sentinel`` for tests."""

        @functools.wraps(fun)
        def traced(*a, **k):
            # executes only while jax traces `fun` — this IS the counter
            self.traces += 1
            return fun(*a, **k)

        jfn = jax.jit(traced, **jit_kwargs)
        self._jits.append(jfn)

        @functools.wraps(fun)
        def call(*a, **k):
            before = self.traces
            t0 = time.perf_counter()
            out = jfn(*a, **k)
            self.calls += 1
            if self.traces != before:
                self.last_trace_s = time.perf_counter() - t0
                self._last = (jfn, jax.tree.map(_abstract, a),
                              jax.tree.map(_abstract, k))
                if audit_eqns:
                    self._pending = self._last
            return out

        call._cache_size = jfn._cache_size
        call.sentinel = self
        return call

    def audit(self) -> None:
        """Resolve a pending equation audit: re-trace the last traced
        call's abstract shapes and store the jaxpr equation count.  Cost
        is one abstract trace (no compile, no execution); no-op when
        nothing traced since the last audit."""
        if self._pending is None:
            return
        jfn, a, k = self._pending
        self._pending = None
        try:
            self.eqns = count_eqns(jfn.trace(*a, **k).jaxpr)
        except Exception:  # noqa: BLE001 — audit must never break serving
            self.eqns = -1

    def compiled_text(self) -> str:
        """Optimized HLO text of the most recently traced program, compiled
        again from its abstract arguments (a persistent-cache hit when the
        compilation cache is on).  Shows what the compiler made of the
        program, e.g. whether a Pallas kernel lowered to
        ``tpu_custom_call``."""
        if self._last is None:
            raise RuntimeError(f"{self.name}: nothing traced yet")
        jfn, a, k = self._last
        return jfn.lower(*a, **k).compile().as_text()

    def op_scopes(self) -> Dict[str, str]:
        """``op_scopes`` of the most recently traced program over this
        sentinel's ``scopes``: op name (as a device trace names it) ->
        scope, ``MIXED`` or ``""``.  Computed from ``compiled_text()``
        once per traced program; nothing runs on the hot path."""
        if self._op_scopes_of is not self._last:
            self._op_scopes = op_scopes(self.compiled_text(), self.scopes)
            self._op_scopes_of = self._last
        return self._op_scopes

    def metrics(self) -> Dict[str, Any]:
        """This sentinel's gauge dict (resolves any pending audit)."""
        self.audit()
        return {
            "count": self.traces,
            "calls": self.calls,
            "cache_size": self.cache_size,
            "last_trace_s": self.last_trace_s,
            "eqns": self.eqns,
        }


def instrument(name: str, fun: Optional[Callable] = None, *,
               audit_eqns: bool = True, scopes: Sequence[str] = (),
               **jit_kwargs):
    """Wrap ``fun`` in a fresh named sentinel: ``instrument("decode",
    fn, donate_argnums=(1,))`` replaces ``jax.jit(fn, ...)`` and mounts
    the compile counters under ``compile/decode/...``.  Usable as a
    decorator via ``functools.partial(instrument, "name", **jit_kwargs)``
    in place of ``functools.partial(jax.jit, **jit_kwargs)``.  ``scopes``
    is the sentinel's (``Sentinel.op_scopes``)."""
    if fun is None:
        return functools.partial(instrument, name, audit_eqns=audit_eqns,
                                 scopes=scopes, **jit_kwargs)
    return Sentinel(name, scopes).wrap(fun, audit_eqns=audit_eqns,
                                       **jit_kwargs)


def compile_metrics() -> Dict[str, Dict[str, Any]]:
    """Registry provider aggregating every live sentinel by name:
    ``{name: {count, calls, cache_size, last_trace_s, eqns}}`` — mounted
    as the ``compile`` namespace, so snapshots carry
    ``compile/<fn>/count`` etc.  Counts SUM across same-named sentinels
    (several engines wrapping the same entry point are one series);
    ``last_trace_s`` takes the max, ``eqns`` the latest non-zero audit.
    Host values only — nothing to pull."""
    agg: Dict[str, Dict[str, Any]] = {}
    for s in sorted(_ALL, key=lambda s: s.name):
        m = s.metrics()
        d = agg.setdefault(s.name, {"count": 0, "calls": 0, "cache_size": 0,
                                    "last_trace_s": 0.0, "eqns": 0})
        d["count"] += m["count"]
        d["calls"] += m["calls"]
        d["cache_size"] += m["cache_size"]
        d["last_trace_s"] = max(d["last_trace_s"], m["last_trace_s"])
        if m["eqns"]:
            d["eqns"] = m["eqns"]
    return agg


class TraceCapture:
    """Opt-in ``jax.profiler`` capture: one annotated device trace per
    ``every`` requests, written under ``profile_dir`` (open the directory
    with TensorBoard's profile plugin or perfetto).

    ``maybe(n)`` is the per-``generate`` hook: a context manager that
    either runs the body inside ``jax.profiler.trace`` +
    ``StepTraceAnnotation`` (when the request counter crosses a capture
    boundary) or is a no-op.  A capture that cannot start (an
    already-active profiler session, an unwritable directory) raises: a
    trace that ``profile_dir`` asked for is never dropped in silence."""

    def __init__(self, profile_dir: str, every: int = 16):
        self.dir = str(profile_dir)
        self.every = max(int(every), 1)
        self.seen = 0
        self.captures = 0

    @contextlib.contextmanager
    def maybe(self, n: int = 1):
        """Capture-or-passthrough for one request batch of size ``n``
        (the first batch always captures; later batches capture each time
        another ``every`` requests have passed).  Yields True when this
        batch is being captured."""
        due = self.seen // self.every != (self.seen + n) // self.every \
            or self.seen == 0
        self.seen += n
        if not due:
            yield False
            return
        jax.profiler.start_trace(self.dir)
        try:
            with jax.profiler.StepTraceAnnotation(
                "generate", step_num=self.captures
            ):
                yield True
        finally:
            jax.profiler.stop_trace()
            self.captures += 1

    def metrics(self) -> Dict[str, Any]:
        """Registry provider: capture cadence and totals (host values)."""
        return {"dir": self.dir, "every": self.every,
                "requests_seen": self.seen, "captures": self.captures}
