"""Host-side wall-clock timing spans, registry-mounted (DESIGN.md §11).

The overhead half of the paper's low-overhead claim needs the serving
stack to observe ITSELF: ``SpanSet.span(name)`` is a context manager
accumulating call counts and wall seconds per named section (prefill,
decode, rebalance, drain, sweep), and ``metrics()`` is a registry
provider so the totals ride the same flat snapshot as the cache counters
(``span/<name>/calls``, ``span/<name>/seconds``, ``span/<name>/max_s``,
``span/<name>/p50_s``, ``span/<name>/p95_s``).

These are HOST timings around device work — they include dispatch and
any sync the wrapped section performs, which is the serving-relevant
number.  Spans never appear inside jitted code.  Each span is also a
``jax.profiler.TraceAnnotation`` of its name, so a profiler capture
(``ServeEngine(profile_dir=...)``, the benchmark's ``--trace 1``) shows it
on the host plane, on the device trace's clock, where device idle gaps can
be laid to it.

Sync discipline (DESIGN.md §12): jax dispatch is async, so a span around
a bare jitted call times only ENQUEUE unless something inside it blocks.
Every phase span in the serving stack therefore either (a) contains the
host pull that serving itself performs (``np.asarray`` of the result —
the honest end-to-end number), or (b) in profiling mode (``sync=True``,
from ``ServeEngine(profile_phases=True)``) calls ``ready(x)`` on the
phase's outputs so the close blocks via ``jax.block_until_ready`` and
the timing isolates the phase's own device time.  ``sync=False`` makes
``ready`` free, so call sites don't branch.
"""

from __future__ import annotations

import contextlib
import time
from collections import deque
from typing import Any, Deque, Dict


class _Span:
    """Handle yielded by ``SpanSet.span``: ``ready(x)`` registers device
    values the span must wait on at close when the owning set has
    ``sync=True`` (no-op otherwise — call sites never branch)."""

    __slots__ = ("_pending", "_sync")

    def __init__(self, sync: bool):
        self._sync = sync
        self._pending: list = []

    def ready(self, x: Any) -> Any:
        """Mark ``x`` (array / pytree) to be blocked on at span close in
        sync mode; returns ``x`` unchanged so it nests in expressions."""
        if self._sync:
            self._pending.append(x)
        return x


class SpanSet:
    """Accumulates per-name wall-clock spans: ``calls`` / ``seconds`` /
    ``max_s`` plus ``p50_s`` / ``p95_s`` over a bounded window of the
    most recent ``max_samples`` durations (bounded so a long-lived server
    can't grow without limit; percentiles are therefore RECENT, which is
    what a dashboard wants anyway).  Mutable host object — use one per
    engine; not thread-safe (the serving engine is single-threaded by
    construction)."""

    def __init__(self, *, max_samples: int = 512, sync: bool = False):
        self._acc: Dict[str, list] = {}
        self._samples: Dict[str, Deque[float]] = {}
        self._max_samples = int(max_samples)
        self.sync = bool(sync)

    @contextlib.contextmanager
    def span(self, name: str):
        """Time one ``with``-scoped section under ``name``; exceptions
        propagate but the elapsed time is still recorded.  Yields a
        handle whose ``ready(x)`` enrolls device values to block on at
        close in sync mode (see the module docstring).  The section is
        also a ``jax.profiler.TraceAnnotation`` named ``name``, so a
        profiler capture shows it on the device trace's clock."""
        import jax

        h = _Span(self.sync)
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield h
            finally:
                if h._pending:
                    jax.block_until_ready(h._pending)
                dt = time.perf_counter() - t0
                acc = self._acc.setdefault(name, [0, 0.0, 0.0])
                acc[0] += 1
                acc[1] += dt
                acc[2] = max(acc[2], dt)
                self._samples.setdefault(
                    name, deque(maxlen=self._max_samples)
                ).append(dt)

    @staticmethod
    def _pct(xs: list, q: float) -> float:
        """Nearest-rank percentile of a sorted sample list."""
        return xs[min(int(q * (len(xs) - 1) + 0.5), len(xs) - 1)]

    def metrics(self) -> Dict[str, Dict[str, float]]:
        """Registry provider: ``{name: {calls, seconds, max_s, p50_s,
        p95_s}}`` (host values — nothing to pull).  Percentiles cover the
        recent-sample window only."""
        out: Dict[str, Dict[str, float]] = {}
        for name, (c, s, m) in self._acc.items():
            xs = sorted(self._samples.get(name, ()))
            out[name] = {
                "calls": c,
                "seconds": s,
                "max_s": m,
                "p50_s": self._pct(xs, 0.50) if xs else 0.0,
                "p95_s": self._pct(xs, 0.95) if xs else 0.0,
            }
        return out
