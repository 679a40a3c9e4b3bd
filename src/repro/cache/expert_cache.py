"""MoE expert cache: host->HBM expert paging with pluggable policy.

Serving MoE models under tight HBM keeps only ``capacity`` experts resident
per layer; the router's top-k choices form the access stream and AWRP decides
which expert to evict on a miss (a miss = host->device weight transfer, the
cost we count).  This is the paper's policy applied to multi-gigabyte cache
"blocks" — frequency matters (hot experts), recency matters (phase changes in
the request mix), which is AWRP's exact design point.

``simulate_router_trace`` reuses the core simulator so AWRP/LRU/FIFO/CAR/ARC
numbers are apples-to-apples with the paper's Table 1 methodology; the study
(studies/expert_cache_bench.py) reports miss-rate == transfer volume.

``ExpertCacheRuntime`` has two execution paths behind one accounting
surface:

* **host** (default): one ``repro.core.policies`` oracle per layer, built
  through the serving factory (``policy_core.make_cache_policy``).
* **device** (``device=True``): ONE unified-core instance
  (``policy_core.make_core``) holding all layers as a ``(n_layers,)``-row
  batch — ``route_step`` feeds every layer's router choices as batched
  engine steps instead of a Python loop of dict oracles, and per-layer
  ``route`` calls become row-masked accesses against the same state.  The
  device path accepts every ``DEVICE_POLICIES`` name, including true
  arc/car (decisions bit-identical to the host oracles; parity-tested in
  tests/test_serving.py).
"""

from __future__ import annotations

from typing import Dict, Iterable

import numpy as np

from repro.core.simulator import SimResult, simulate
from repro.obs.metrics import safe_ratio


def router_trace_from_logits(expert_idx: np.ndarray) -> np.ndarray:
    """(steps, k) router top-k choices -> flat access stream."""
    return np.asarray(expert_idx).reshape(-1).astype(np.int64)


def simulate_router_trace(
    policies: Iterable[str],
    trace: np.ndarray,
    capacity: int,
    expert_bytes: int = 0,
) -> Dict[str, dict]:
    """Returns {policy: {hit_ratio, transfers, transfer_bytes}}."""
    out = {}
    for p in policies:
        res: SimResult = simulate(p, trace, capacity)
        misses = res.accesses - res.hits
        out[p] = {
            "hit_ratio": res.hit_ratio,
            "transfers": misses,
            "transfer_bytes": misses * expert_bytes,
        }
    return out


class ExpertCacheRuntime:
    """Online variant used by the engine: track residency per layer and count
    transfers as the router stream arrives."""

    def __init__(self, n_layers: int, capacity: int, policy: str = "awrp",
                 *, device: bool = False):
        self.n_layers = int(n_layers)
        self.capacity = int(capacity)
        self.policy_name = policy if isinstance(policy, str) else policy.name
        self.device = bool(device)
        self.transfers = 0
        self.accesses = 0
        if device:
            import jax

            from repro.core.policy_core import make_core

            if not isinstance(policy, str):
                raise ValueError(
                    "the device path takes a policy NAME (one of "
                    "DEVICE_POLICIES), not a prebuilt instance"
                )

            self.core = make_core(policy, rows=self.n_layers,
                                  num_sets=1, ways=self.capacity)
            self.state = self.core.init()
            self._step = jax.jit(
                lambda st, ids, act: self.core.on_access(st, ids, active=act)
            )
        else:
            from repro.core.policy_core import make_cache_policy

            if not isinstance(policy, str) and self.n_layers > 1:
                # a prebuilt instance cannot back multiple layers — they
                # would share (and corrupt) one residency set
                raise ValueError(
                    "pass a policy NAME for n_layers > 1; a prebuilt "
                    "instance would be shared across layers"
                )
            self.layers = [
                make_cache_policy(policy, self.capacity)
                for _ in range(self.n_layers)
            ]

    # -- device-path internals ---------------------------------------------
    def _device_accesses(self, ids_seq, active_seq) -> int:
        """Run a sequence of (n_layers,)-row engine steps; returns #hits."""
        hits = 0
        for ids, act in zip(ids_seq, active_seq):
            self.state, h = self._step(self.state, ids, act)
            hits += int(np.asarray(h).sum())
        return hits

    # -- public -------------------------------------------------------------
    def route(self, layer: int, experts: Iterable[int]) -> int:
        """Record router choices for one layer-step; returns #misses."""
        experts = [int(e) for e in experts]
        if self.device:
            ids = np.zeros((len(experts), self.n_layers), np.int32)
            ids[:, layer] = experts
            act = np.zeros((self.n_layers,), bool)
            act[layer] = True
            hits = self._device_accesses(ids, [act] * len(experts))
            misses = len(experts) - hits
        else:
            misses = 0
            for e in experts:
                if not self.layers[layer].access(e):
                    misses += 1
        self.accesses += len(experts)
        self.transfers += misses
        return misses

    def route_step(self, expert_idx) -> int:
        """Record one full model step's router choices for ALL layers at
        once: ``expert_idx`` is ``(n_layers, k)`` top-k expert ids.  On the
        device path this is k batched ``(n_layers,)``-row engine steps (one
        jitted call each) instead of a Python loop of n_layers*k dict-oracle
        accesses; decisions and accounting are identical to calling
        ``route`` per layer.  Returns total #misses across layers."""
        expert_idx = np.asarray(expert_idx, dtype=np.int32)
        if expert_idx.ndim != 2 or expert_idx.shape[0] != self.n_layers:
            raise ValueError(
                f"expert_idx must be (n_layers={self.n_layers}, k), "
                f"got {expert_idx.shape}"
            )
        k = expert_idx.shape[1]
        if self.device:
            act = np.ones((self.n_layers,), bool)
            hits = self._device_accesses(expert_idx.T, [act] * k)
            misses = self.n_layers * k - hits
        else:
            misses = 0
            for layer in range(self.n_layers):
                for e in expert_idx[layer]:
                    if not self.layers[layer].access(int(e)):
                        misses += 1
        self.accesses += self.n_layers * k
        self.transfers += misses
        return misses

    @property
    def hit_ratio(self) -> float:
        """Fraction of expert accesses served without an HBM transfer
        (0.0 before any access — the shared ``obs.metrics.safe_ratio``
        guard)."""
        return safe_ratio(self.accesses - self.transfers, self.accesses)

    def telemetry(self) -> dict:
        """Uniform per-cache stats (the serving engine's one code path)."""
        return {
            "policy": self.policy_name,
            "backend": "device" if self.device else "host",
            "accesses": self.accesses,
            "transfers": self.transfers,
            "hit_ratio": self.hit_ratio,
        }
