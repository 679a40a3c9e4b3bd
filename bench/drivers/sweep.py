"""Driver of the policy-grid sweep: ``repro.core.simulate_trace_batched``
over (traces x policies x capacities), as a cache researcher runs it.

A call simulates one pre-generated batch of traces under every policy and
capacity and pulls the hit count of each (trace, policy, capacity) row to
the host; the window runs whole calls, cycling through the batches.  The
last call's per-access hits stay on the device for the check: every row
(or every row of a sample of traces drawn from the seed, where the mix
sets ``check_traces``) is compared access by access, and by its pulled
count, with the plain host reference.
"""

from __future__ import annotations

import itertools
import time

import numpy as np

from bench import harness, traffic
from bench.refs import policies as ref


class Run:
    def __init__(self, cfg: dict, mix: dict, seed: int, rehearse: bool):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.policies = tuple(cfg["policies"])
        self.caps = tuple(cfg["capacities"])

    def _call(self, traces):
        hits = self.simulate(traces, self.policies, self.caps,
                             num_sets=self.cfg["num_sets"])
        return hits, np.asarray(hits.sum(-1))

    def setup(self, warm: bool = True) -> None:
        from repro.core.jax_policies import (
            _simulate_batched_impl,
            simulate_trace_batched,
        )

        self.simulate = simulate_trace_batched
        self.sentinel = _simulate_batched_impl.sentinel
        self.batches = traffic.make_batches(self.mix, self.seed)
        self.setup_parts = {}
        if warm:
            t0 = time.perf_counter()
            self._call(self.batches[-1])  # warm-up: the one extra batch
            self.setup_parts["warmup_s"] = time.perf_counter() - t0

    def window(self, seconds: float, max_calls=None) -> dict:
        traces0 = self.sentinel.traces
        calls, t0, each = 0, time.perf_counter(), []
        while True:
            b = calls % self.mix["batches"]
            with harness.annotate("bench/call"):
                hits, counts = self._call(self.batches[b])
            calls += 1
            each.append(time.perf_counter() - t0 - sum(each))
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds or (max_calls and calls >= max_calls):
                break
        self.last = (b, hits, counts)
        n, length = self.batches[0].shape
        rows = n * len(self.policies) * len(self.caps)
        return {"calls": calls, "elapsed_s": elapsed, "rows": rows,
                "steps": calls * length, "accesses": calls * rows * length,
                "flat_rows": n * sum(p in ref.FLAT for p in self.policies)
                * len(self.caps),
                "lanes": max(self.caps) // self.cfg["num_sets"],
                "compiles_in_window": self.sentinel.traces - traces0,
                "call_s_min": min(each), "call_s_max": max(each),
                "attempted": calls, "failed": 0, **self.setup_parts}

    def end_to_end(self, record: dict) -> dict:
        return {"sweep_accesses_per_s": record["accesses"]
                / record["elapsed_s"]}

    def release(self) -> None:
        pass  # the last call's hits are what the check reads

    def _rows(self):
        """The check's rows of the last call: every (policy, capacity) row
        of every trace, or of ``check_traces`` traces drawn from the seed
        where the mix sets it."""
        b, hits, counts = self.last
        traces = self.batches[b]
        pick = np.arange(len(traces))
        if self.mix.get("check_traces"):
            pick = np.sort(traffic.stream(self.seed, 2**31 - 1).choice(
                len(traces), self.mix["check_traces"], replace=False))
        for i, pi, ci in itertools.product(pick, range(len(self.policies)),
                                           range(len(self.caps))):
            yield traces[i], self.policies[pi], self.caps[ci], (i, pi, ci)

    def check(self) -> list:
        _, hits, counts = self.last
        hits = np.asarray(hits)
        bad = 0
        for trace, policy, cap, at in self._rows():
            want = ref.hits(policy, trace, cap, self.cfg["precision"])
            bad += int(not np.array_equal(hits[at], want)
                       or counts[at] != want.sum())
        return [("mismatched_rows", bad,
                 self.cfg["limits"]["mismatched_rows"])]

    def control(self) -> dict:
        """The control's reading on the check's rows: how many differ when
        the reference computes in bfloat16."""
        bad = sum(int(not np.array_equal(
            ref.hits(policy, trace, cap, "bfloat16"),
            ref.hits(policy, trace, cap, self.cfg["precision"])))
            for trace, policy, cap, _ in self._rows())
        return {"control_rows": bad}
