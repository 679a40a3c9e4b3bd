"""Multi-tenant cache tenancy subsystem (DESIGN.md §8).

One batched policy core, one row per tenant: the masked dead-lane encoding
the sweep engine uses for mixed capacities becomes the quota mechanism —
``FlatCore(ways=quotas)`` / ``AdaptiveCore(caps=quotas)`` mounts every
tenant's cache as an independent row of the SAME device program, and
per-tenant request streams are replayed as masked ``on_access`` calls
(rows of inactive tenants are bit-exact no-ops).  Per-tenant accounting
comes from the core itself (``row_telemetry``), so the numbers the serving
engine reports are the numbers the sweep engine would measure on the
demuxed streams — property-tested against the host oracles.

Three layers:

* ``TenantCacheManager`` — the core mount: routing, accounting, the
  eviction-pressure EWMA, AWRP-ranked quota rebalancing.  Tenants are
  ranked by the paper's own eq. (1) lifted one altitude: ``W_t =
  F_t / (N − R_t)`` where F_t is the tenant's access count, R_t the clock
  of its last access and N the manager clock — the coldest tenant (lowest
  weight) donates quota lanes first, exactly the rule AWRP applies to
  cache lines.
* ``AdmissionController`` — maps the pressure signal to accept / defer /
  shed decisions for the serving engine.  Decisions can run per request on
  host (``decide``) or as one jitted scan over a whole request batch on
  device (``decide_batch``) — bit-identical by construction, because the
  pressure EWMA lives in the core's ``RowCounters.pressure`` plane
  (DESIGN.md §9) and the host only ever reads pulled copies of it.
* ``TenantPrefixCache`` — the prefix cache on top of the manager: one
  payload store per tenant, policy residency and store contents coherent
  per row (the same invariant ``PrefixCache`` keeps for one tenant).

Quota rebalancing is supported for flat cores (awrp/lru/fifo/lfu): a
shrink keeps the row's best blocks by its own policy ranking and compacts
them into the surviving quota lanes (evicted ids are returned so payload
stores stay coherent).  Adaptive rows (arc/car) carry ghost directories
whose invariants (``|T1|+|B1| ≤ c``, total ≤ 2c) do not survive a cap
change without replaying history, so their quotas are fixed — construct
the manager with the quotas you mean to keep.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sharding
from repro.core.policy_core import (
    ADAPTIVE_POLICIES,
    ADMIT_SHED,
    JAX_POLICIES,
    POLICY_IDS,
    AdaptiveCore,
    FlatCore,
    RowCounters,
    admission_decay,
    admission_decide,
)
from repro.obs import decision_trace as _dt
from repro.obs import profiling
from repro.obs.metrics import safe_ratio

__all__ = [
    "TenantCacheManager",
    "AdmissionController",
    "TenantPrefixCache",
    "ACCEPT",
    "DEFER",
    "SHED",
]

ACCEPT, DEFER, SHED = "accept", "defer", "shed"


class TenantCacheManager:
    """One batched policy core with one row per tenant (quota = row ways).

    ``quotas`` is an ordered ``{tenant: capacity}`` mapping; ``policy`` any
    device policy name (flat: awrp/lru/fifo/lfu; adaptive: arc/car).  Flat
    cores pad every row to ``lanes = sum(quotas)`` so rebalancing can grow
    any tenant up to the whole pool without changing plane shapes.

    ``mesh`` (a ``core.sharding`` rows mesh) places the tenant rows across
    devices: state and counters are built with the rows axis sharded, and
    every jitted step (``access`` / ``access_stream`` / ``decide_batch``)
    then runs under the mesh.  Tenant counts rarely divide the device
    count, so the core pads its rows up to a multiple
    (``sharding.pad_rows_to``) with minimum-quota rows no access ever
    activates — masked no-ops keep them empty, so accounting and decisions
    are bit-identical to the unsharded manager (tests/test_sharding.py).
    """

    def __init__(
        self,
        quotas: Dict[str, int],
        policy: str = "awrp",
        *,
        pressure_alpha: float = 0.1,
        mesh=None,
        ring_capacity: int = 0,
    ):
        if not quotas:
            raise ValueError("need at least one tenant")
        for t, q in quotas.items():
            if int(q) <= 0:
                raise ValueError(f"tenant {t!r} quota must be positive, got {q}")
        self.tenants: List[str] = list(quotas)
        self._row_of = {t: i for i, t in enumerate(self.tenants)}
        self.policy_name = policy
        self.quotas = {t: int(q) for t, q in quotas.items()}
        self.pressure_alpha = float(pressure_alpha)
        self.mesh = mesh
        self._core_rows = (
            sharding.pad_rows_to(len(self.tenants), mesh.devices.size)
            if mesh is not None
            else len(self.tenants)
        )
        # host mirror of the device pressure plane (RowCounters.pressure).
        # Always a PULLED writable copy, never recomputed host-side: XLA's
        # FMA contraction makes a host float32 replay of the EWMA diverge
        # within a few steps, and admission bit-identity (host decide vs
        # device decide_batch) depends on both reading the same bits.
        self._pressure = np.zeros(self._core_rows, dtype=np.float32)
        # tenant-altitude AWRP metadata for ranking: F_t / R_t / clock N
        self._tf = np.zeros(len(self.tenants), dtype=np.int64)
        self._tr = np.zeros(len(self.tenants), dtype=np.int64)
        self._tclock = 0
        # optional decision-trace ring (obs.decision_trace): every access
        # and admission decision is recorded device-side, zero host syncs,
        # drained via ``drain_trace``.  Replicated (never sharded) — it is
        # byte-sized and the push order is the scan order either way.
        self.ring = _dt.ring_init(ring_capacity) if ring_capacity else None
        # per-manager compile sentinels (obs.profiling): created ONCE so
        # trace counts stay monotone across rebalances (a rebalance rebuilds
        # the jitted programs under the same sentinel — the recompile shows
        # up as compile/<fn>/count growth, which is exactly the point)
        self._step_sentinel = profiling.Sentinel("tenancy_step")
        self._stream_sentinel = profiling.Sentinel("access_stream")
        self.core = self._build_core()
        self.state = self.core.init(mesh=mesh)
        self.counters: RowCounters = self.core.init_counters(mesh=mesh)
        self._step = self._jit_step()
        self._stream = self._jit_stream()

    # -- core mount ---------------------------------------------------------
    @property
    def rows(self) -> int:
        """Number of tenant rows (static per manager).  Under a mesh the
        core itself may carry extra never-activated padding rows —
        ``self.core.rows >= rows`` — so array-shaped ops use the core's
        count while tenant iteration uses this one."""
        return len(self.tenants)

    @property
    def is_adaptive(self) -> bool:
        """True for arc/car mounts (ghost directories, fixed quotas)."""
        return self.policy_name in ADAPTIVE_POLICIES

    def _build_core(self):
        q = tuple(self.quotas[t] for t in self.tenants)
        # mesh padding: rows beyond the tenant count are minimum-quota rows
        # no access ever activates, so they stay empty and unaccounted
        q += (1,) * (self._core_rows - len(q))
        if self.policy_name in JAX_POLICIES:
            return FlatCore(
                pids=(POLICY_IDS[self.policy_name],) * len(q),
                ways=q,
                lanes=sum(self.quotas.values()),
            )
        if self.policy_name in ADAPTIVE_POLICIES:
            return AdaptiveCore(kind=self.policy_name, caps=q)
        raise ValueError(
            f"not a device policy: {self.policy_name!r}; "
            f"have {JAX_POLICIES + ADAPTIVE_POLICIES}"
        )

    def _jit_step(self):
        """One jitted masked step for the host `access` path (the eager
        adaptive step functions are dispatch-bound per access; the jit is
        compiled once per core spec — i.e. once per rebalance).  The
        pressure EWMA alpha is baked in: the step updates the device
        pressure plane alongside the hit/miss/eviction counters."""
        core, alpha = self.core, self.pressure_alpha
        if self.ring is not None:
            return self._step_sentinel.wrap(
                lambda st, ctr, ids, act, ring: core.on_access_counted(
                    st, ctr, ids, active=act, pressure_alpha=alpha, ring=ring
                )
            )
        return self._step_sentinel.wrap(
            lambda st, ctr, ids, act: core.on_access_counted(
                st, ctr, ids, active=act, pressure_alpha=alpha
            )
        )

    def _jit_stream(self):
        """The whole-stream replay as ONE jitted program (the
        ``access_stream`` entry point): a scan of masked
        ``on_access_counted`` steps carrying state, counters (and the
        decision-trace ring).  Compiled once per core spec × stream
        length; sentinel-wrapped (``compile/access_stream/...``), so a
        retrace storm from wildly varying stream lengths is visible as
        count growth instead of silent recompiles."""
        core, R = self.core, self.core.rows
        alpha = self.pressure_alpha
        if self.ring is not None:
            def stream(state, ctr, ring, rows, keys):
                def body(carry, xs):
                    st, c, rg = carry
                    row, key = xs
                    active = jnp.arange(R) == row
                    st, c, hit, rg = core.on_access_counted(
                        st, c, jnp.full((R,), key, dtype=jnp.int32),
                        active=active, pressure_alpha=alpha, ring=rg,
                    )
                    return (st, c, rg), hit[row]

                (state, ctr, ring), hits = jax.lax.scan(
                    body, (state, ctr, ring), (rows, keys)
                )
                return state, ctr, ring, hits

            return self._stream_sentinel.wrap(stream)

        def stream(state, ctr, rows, keys):
            def body(carry, xs):
                st, c = carry
                row, key = xs
                active = jnp.arange(R) == row
                st, c, hit = core.on_access_counted(
                    st, c, jnp.full((R,), key, dtype=jnp.int32),
                    active=active, pressure_alpha=alpha,
                )
                return (st, c), hit[row]

            (state, ctr), hits = jax.lax.scan(
                body, (state, ctr), (rows, keys)
            )
            return state, ctr, hits

        return self._stream_sentinel.wrap(stream)

    def _pull_pressure(self) -> None:
        """Refresh the host mirror from the device plane (writable copy)."""
        self._pressure = np.array(self.counters.pressure)

    def row(self, tenant: str) -> int:
        """Core row index of ``tenant`` (raises KeyError for unknowns)."""
        try:
            return self._row_of[tenant]
        except KeyError:
            raise KeyError(
                f"unknown tenant {tenant!r}; have {self.tenants}"
            ) from None

    # -- access -------------------------------------------------------------
    def _resident_ids(self, state, r: int) -> set:
        if self.is_adaptive:
            blocks = np.asarray(state.blocks[r, 0])
            res = np.asarray(self.core.resident_mask(state)[r, 0])
            return set(blocks[res].tolist())
        blocks = np.asarray(state.blocks[r])
        return set(blocks[blocks >= 0].tolist())

    def access(self, tenant: str, key: int) -> Tuple[bool, List[int]]:
        """One access of ``key`` by ``tenant``: a masked single-row step of
        the shared core.  Returns ``(hit, evicted_keys)`` — evicted keys are
        what the row's policy displaced, for payload-store coherence.

        Mutates ``state``/``counters`` (including the device pressure EWMA,
        updated inside the same jitted step) and the host mirrors
        (``_pressure``, tenant-altitude F/R/clock).  Host path: pulls state
        to report evicted keys, so it syncs the device every call — use
        ``access_stream`` for throughput."""
        r = self.row(tenant)
        before = self._resident_ids(self.state, r)
        active = jnp.arange(self.core.rows) == r
        ids = jnp.full((self.core.rows,), int(key), dtype=jnp.int32)
        if self.ring is not None:
            self.state, self.counters, hit, self.ring = self._step(
                self.state, self.counters, ids, active, self.ring
            )
        else:
            self.state, self.counters, hit = self._step(
                self.state, self.counters, ids, active
            )
        after = self._resident_ids(self.state, r)
        evicted = sorted(before - after)
        # pressure EWMA advanced on device by the step itself; pull mirror
        self._pull_pressure()
        self._tclock += 1
        self._tf[r] += 1
        self._tr[r] = self._tclock
        return bool(np.asarray(hit)[r]), evicted

    def access_stream(
        self, tenant_rows: np.ndarray, keys: np.ndarray
    ) -> np.ndarray:
        """Replay a whole interleaved stream device-side: one jitted scan of
        masked ``on_access_counted`` steps (access i activates only row
        ``tenant_rows[i]``; the ring, when on, rides the scan carry next
        to the counters — zero per-access syncs).  Returns the per-access
        hit bits.  State and counters advance exactly as ``access`` would,
        including the pressure EWMA — it folds per access INSIDE the scan,
        so batch order matters exactly as on the host path (evicted-key
        reporting still needs the host path).  Mutates
        ``state``/``counters`` and the host mirrors; one device sync at
        the end, none per access."""
        tenant_rows = np.asarray(tenant_rows, dtype=np.int32)
        keys = np.asarray(keys, dtype=np.int32)
        if tenant_rows.shape != keys.shape or tenant_rows.ndim != 1:
            raise ValueError(
                f"tenant_rows {tenant_rows.shape} and keys {keys.shape} must "
                "be equal-length 1-D arrays"
            )
        ctr_before = jax.tree.map(np.asarray, self.counters)

        xs_dev = (jnp.asarray(tenant_rows), jnp.asarray(keys))
        if self.ring is not None:
            self.state, self.counters, self.ring, hits = self._stream(
                self.state, self.counters, self.ring, *xs_dev
            )
        else:
            self.state, self.counters, hits = self._stream(
                self.state, self.counters, *xs_dev
            )
        self._pull_pressure()
        # tenant-altitude AWRP metadata: F from the counter deltas, R from
        # the stream's own order
        ctr_after = jax.tree.map(np.asarray, self.counters)
        d_acc = (ctr_after.hits + ctr_after.misses) - (
            ctr_before.hits + ctr_before.misses
        )
        for r in range(self.rows):
            self._tf[r] += int(d_acc[r])
        base = self._tclock
        self._tclock += len(tenant_rows)
        for i, r in enumerate(tenant_rows.tolist()):
            self._tr[r] = base + i + 1
        return np.asarray(hits)

    # -- signals ------------------------------------------------------------
    def accesses(self, tenant: str) -> int:
        """Host-side access count for ``tenant`` (the tenant-altitude F_t)
        — no device sync, unlike ``row_telemetry`` (the admission hot
        path's warmup check reads this per request)."""
        return int(self._tf[self.row(tenant)])

    def pressure(self, tenant: str) -> float:
        """Eviction-pressure EWMA: evictions per access of this tenant,
        exponentially weighted (``pressure_alpha``).  1.0 = every recent
        access displaced a resident entry (the quota is thrashing).  Reads
        the host mirror (no device sync); the mirror is refreshed by every
        mutating call (``access``/``access_stream``/``decay_pressure``/
        ``rebalance``/``AdmissionController.decide_batch``)."""
        return float(self._pressure[self.row(tenant)])

    def decay_pressure(self, tenant: str) -> float:
        """One EWMA step toward 0 without an access.  The EWMA only updates
        on the tenant's own accesses, so a fully shed tenant would otherwise
        stay above the shed threshold forever — the serving engine calls
        this when it sheds, so refused work doubles as probation time.
        Mutates the device pressure plane (``admission_decay`` on this
        tenant's row) and refreshes the host mirror."""
        r = self.row(tenant)
        mask = np.zeros(self.core.rows, dtype=bool)
        mask[r] = True
        self.counters = self.counters._replace(
            pressure=admission_decay(
                self.counters.pressure, mask, self.pressure_alpha
            )
        )
        self._pull_pressure()
        return float(self._pressure[r])

    def tenant_weights(self) -> Dict[str, float]:
        """Paper eq. (1) at tenant altitude: ``W_t = F_t / (N − R_t)``,
        the ranking the rebalancer uses (never-accessed tenants weigh 0)."""
        out = {}
        for t in self.tenants:
            r = self.row(t)
            dt = max(self._tclock - self._tr[r], 1)
            out[t] = float(self._tf[r]) / float(dt) if self._tf[r] else 0.0
        return out

    def rank_tenants(self) -> List[str]:
        """Tenants coldest-first (lowest AWRP weight; ties by row order) —
        the order quota lanes are reclaimed in."""
        w = self.tenant_weights()
        return sorted(self.tenants, key=lambda t: (w[t], self.row(t)))

    # -- quota rebalancing (flat cores) -------------------------------------
    def _flat_keep_order(self, r: int) -> np.ndarray:
        """Occupied lanes of row ``r`` in eviction order (first = evicted
        first) under the row's own policy — the flat victim rule on host."""
        st = self.state
        blocks = np.asarray(st.blocks[r])
        f = np.asarray(st.f[r]).astype(np.float64)
        rr = np.asarray(st.r[r]).astype(np.float64)
        clock = float(np.asarray(st.clock[r]))
        occ = np.where(blocks >= 0)[0]
        if self.policy_name == "awrp":
            # weights at clock N+1 — the clock every live victim decision is
            # made at (`_flat_victim` receives state.clock + 1)
            key = f[occ] / np.maximum((clock + 1.0) - rr[occ], 1.0)
            order = np.lexsort((occ, key))
        elif self.policy_name in ("lru", "fifo"):
            order = np.lexsort((occ, rr[occ]))
        else:  # lfu: min F, ties by recency then lane
            order = np.lexsort((occ, rr[occ], f[occ]))
        return occ[order]

    def rebalance(
        self, to: str, n: int = 1, *, min_quota: int = 1
    ) -> Tuple[int, Dict[str, List[int]]]:
        """Move up to ``n`` quota lanes to tenant ``to``, reclaiming them
        from the lowest-AWRP-ranked tenants first (never below
        ``min_quota``, never from ``to`` itself).  Shrunk rows evict their
        policy's worst blocks and compact the rest.  Returns ``(moved,
        evicted_by)`` — the lane count actually moved (a donor with spare
        empty lanes moves quota without evicting anything, so the dict
        alone cannot signal success) and the evicted keys per tenant for
        payload-store coherence.  Flat cores only — adaptive quotas are
        fixed (see module docstring)."""
        if self.is_adaptive:
            raise NotImplementedError(
                "adaptive (arc/car) tenant quotas are fixed: ghost-directory "
                "invariants do not survive a capacity change"
            )
        if n <= 0:
            raise ValueError(f"n must be positive, got {n}")
        moved, evicted_by = 0, {}
        for donor in self.rank_tenants():
            if donor == to:
                continue
            while moved < n and self.quotas[donor] > min_quota:
                self.quotas[donor] -= 1
                self.quotas[to] += 1
                moved += 1
            if moved >= n:
                break
        if moved == 0:
            return 0, {}
        # rebuild the core for the new ways tuple, then repair shrunk rows
        old_ways = self.core.ways
        self.core = self._build_core()
        self._step = self._jit_step()
        self._stream = self._jit_stream()
        for t in self.tenants:
            r = self.row(t)
            new_w = self.quotas[t]
            if new_w >= old_ways[r]:
                continue
            ev = self._shrink_flat_row(r, new_w)
            if ev:
                evicted_by[t] = ev
                # fold the shrink's evictions into the DEVICE pressure plane
                # (same shape as one access evicting len(ev) entries), then
                # refresh the mirror
                a = jnp.float32(self.pressure_alpha)
                p = self.counters.pressure
                p_r = (1.0 - a) * p[r] + a * jnp.float32(len(ev))
                self.counters = self.counters._replace(
                    pressure=p.at[r].set(p_r)
                )
        self._pull_pressure()
        return moved, evicted_by

    def _shrink_flat_row(self, r: int, new_ways: int) -> List[int]:
        """Drop the row to ``new_ways`` live lanes: evict the policy's worst
        blocks (host replay of the flat victim rule), compact survivors into
        lanes ``[0, new_ways)`` preserving lane order, clear the rest."""
        order = self._flat_keep_order(r)  # eviction order, worst first
        n_drop = max(len(order) - new_ways, 0)
        dropped, kept = order[:n_drop], np.sort(order[n_drop:])
        st = self.state
        blocks = np.asarray(st.blocks[r]).copy()
        f = np.asarray(st.f[r]).copy()
        rr = np.asarray(st.r[r]).copy()
        evicted = blocks[dropped].tolist()
        W = blocks.shape[0]
        nb = np.full(W, -1, dtype=np.int32)
        nf = np.zeros(W, dtype=np.int32)
        nr = np.zeros(W, dtype=np.int32)
        k = len(kept)
        nb[:k], nf[:k], nr[:k] = blocks[kept], f[kept], rr[kept]
        self.state = st._replace(
            blocks=st.blocks.at[r].set(nb),
            f=st.f.at[r].set(nf),
            r=st.r.at[r].set(nr),
        )
        return evicted

    # -- telemetry ----------------------------------------------------------
    def row_metrics(self) -> Dict[str, jax.Array]:
        """The core's per-row accounting as UN-pulled ``(rows,)`` device
        arrays — the obs registry's provider surface (the snapshot batches
        these into its single ``device_get``).  Read-only; zero syncs."""
        return self.core.row_telemetry(self.state, self.counters)

    def row_telemetry(self) -> Dict[str, np.ndarray]:
        """The core's per-row accounting, pulled to host: hits / misses /
        evictions / accesses / occupancy / capacity / pressure, each
        ``(rows,)``.  Read-only; ONE batched ``jax.device_get`` over the
        whole dict, never one sync per key."""
        return jax.device_get(self.row_metrics())

    def telemetry(self) -> Dict[str, dict]:
        """Per-tenant stats dicts, same shape for every tenant — the one
        code path ``ServeEngine.telemetry`` reports tenancy from."""
        rows = self.row_telemetry()
        out = {}
        for t in self.tenants:
            r = self.row(t)
            out[t] = {
                "policy": self.policy_name,
                "quota": self.quotas[t],
                "occupancy": int(rows["occupancy"][r]),
                "hits": int(rows["hits"][r]),
                "misses": int(rows["misses"][r]),
                "evictions": int(rows["evictions"][r]),
                "accesses": int(rows["accesses"][r]),
                "hit_ratio": safe_ratio(
                    int(rows["hits"][r]), int(rows["accesses"][r])
                ),
                "pressure": float(self._pressure[r]),
            }
        return out

    def drain_trace(self) -> np.ndarray:
        """Pull the decision-trace ring to host as a structured record array
        (chronological; see ``obs.decision_trace.drain``).  Requires the
        manager to have been built with ``ring_capacity > 0``."""
        if self.ring is None:
            raise ValueError(
                "decision tracing is off; construct the manager with "
                "ring_capacity > 0"
            )
        return _dt.drain(self.ring)


@dataclasses.dataclass
class AdmissionController:
    """Pressure → accept / defer / shed.

    ``defer_at`` and ``shed_at`` are thresholds on the manager's
    eviction-pressure EWMA; below ``warmup`` accesses a tenant is always
    accepted (the EWMA hasn't seen enough of the stream to mean anything).
    Deferred work is retried by the caller after the pressured tenant's
    EWMA has had time to decay; shed work is refused outright."""

    defer_at: float = 0.5
    shed_at: float = 0.85
    warmup: int = 8

    def __post_init__(self):
        if not 0.0 <= self.defer_at <= self.shed_at:
            raise ValueError(
                f"need 0 <= defer_at <= shed_at, got {self.defer_at} / "
                f"{self.shed_at}"
            )

    def decide(self, manager: TenantCacheManager, tenant: str) -> str:
        """One host-side decision for ``tenant``: ``"accept"`` inside the
        warmup window, else thresholds on the pulled pressure mirror.
        Read-only — mutates neither the manager nor the controller (the
        caller applies ``decay_pressure`` on shed; ``decide_batch`` does
        both in one device pass)."""
        if manager.accesses(tenant) < self.warmup:
            return ACCEPT
        p = manager.pressure(tenant)
        if p >= self.shed_at:
            return SHED
        if p >= self.defer_at:
            return DEFER
        return ACCEPT

    def decide_batch(
        self, manager: TenantCacheManager, tenants: List[str]
    ) -> List[str]:
        """Device admission for a whole request batch: one jitted
        sequential scan of ``admission_decide`` + decay-on-shed over the
        batch, bit-identical to calling ``decide`` per request and
        ``manager.decay_pressure`` on each shed (later requests see the
        pressure decayed by earlier sheds, exactly like that per-request
        loop, which tests keep as the reference).

        Bit-identity holds because both paths read the same float32
        pressure plane: the host mirror is a pulled copy of
        ``RowCounters.pressure`` and the threshold compares cannot disagree
        across the float64 host cast (no float32 lies strictly between a
        threshold and its float32 rounding).

        Mutates ``manager.counters.pressure`` (the sheds' decays) and
        refreshes the mirror; returns one ``"accept"/"defer"/"shed"``
        string per request, in order.

        When the manager carries a decision-trace ring, each admission is
        also recorded as one KIND_ADMIT event (row, pressure before/after
        the decision's decay, the ADMIT_* code) inside the same jitted
        scan — recording changes no decision (the codes are computed from
        the identical pressure carry either way)."""
        rows = np.asarray([manager.row(t) for t in tenants], dtype=np.int32)
        if rows.size == 0:
            return []
        fn = _decide_batch_fn(
            self.defer_at,
            self.shed_at,
            self.warmup,
            manager.pressure_alpha,
            manager.core.rows,
            manager.ring is not None,
        )
        acc = manager.counters.hits + manager.counters.misses
        if manager.ring is not None:
            codes, new_p, manager.ring = fn(
                manager.counters.pressure, acc, jnp.asarray(rows), manager.ring
            )
        else:
            codes, new_p = fn(manager.counters.pressure, acc, jnp.asarray(rows))
        manager.counters = manager.counters._replace(pressure=new_p)
        manager._pull_pressure()
        order = (ACCEPT, DEFER, SHED)  # indexed by ADMIT_* codes
        return [order[int(c)] for c in np.asarray(codes)]


@functools.lru_cache(maxsize=None)
def _decide_batch_fn(defer_at, shed_at, warmup, alpha, rows, with_ring=False):
    """Jitted batch-admission program, cached per (thresholds, alpha, rows).

    Sequential by construction: the scan carries the pressure plane so a
    shed's probation decay is visible to every later request in the batch —
    the same ordering contract as the host per-request loop.  With
    ``with_ring`` the decision-trace ring rides the carry too and each
    request appends one KIND_ADMIT event; the decision math is untouched.
    Sentinel-wrapped (one ``decide_batch`` sentinel per cached config,
    aggregated by name in ``compile/decide_batch/...``)."""

    def decide_one(p, accesses, r):
        code = admission_decide(
            p[r],
            accesses[r],
            defer_at=defer_at,
            shed_at=shed_at,
            warmup=warmup,
        )
        shed_here = (jnp.arange(rows) == r) & (code == ADMIT_SHED)
        return admission_decay(p, shed_here, alpha), code

    if with_ring:

        @functools.partial(profiling.instrument, "decide_batch")
        def fn(pressure, accesses, req_rows, ring):
            def body(carry, r):
                p, rg = carry
                p_new, code = decide_one(p, accesses, r)
                ev = _dt.pack_events(
                    1, kind=_dt.KIND_ADMIT, row=r, key=-1,
                    p_before=p[r], p_after=p_new[r], admit=code,
                )
                rg = _dt.ring_push(rg, ev, jnp.ones((1,), dtype=bool))
                return (p_new, rg), code

            (p_final, ring), codes = jax.lax.scan(
                body, (pressure, ring), req_rows
            )
            return codes, p_final, ring

        return fn

    @functools.partial(profiling.instrument, "decide_batch")
    def fn(pressure, accesses, req_rows):
        def body(p, r):
            return decide_one(p, accesses, r)

        p_final, codes = jax.lax.scan(body, pressure, req_rows)
        return codes, p_final

    return fn


class TenantPrefixCache:
    """Per-tenant prefix/prompt cache over one ``TenantCacheManager`` row
    per tenant: quota-bounded payload stores whose residency is exactly the
    shared core's per-row resident set (the ``PrefixCache`` coherence
    invariant, one row per tenant).  Exactly ONE policy access is issued
    per request — on the hit at ``lookup`` or on the miss at ``insert`` —
    so the per-row counters reproduce a host oracle run on the demuxed
    per-tenant stream bit-for-bit."""

    def __init__(self, quotas: Dict[str, int], policy: str = "awrp", **kw):
        self.manager = TenantCacheManager(quotas, policy, **kw)
        self.stores: Dict[str, Dict[int, Any]] = {
            t: {} for t in self.manager.tenants
        }

    def lookup(self, tenant: str, tokens) -> Optional[Any]:
        """Payload for this tenant+prompt, or None.  A hit issues the one
        policy access (mutating the shared core row); a miss mutates
        NOTHING — the miss is accounted when the caller ``insert``s, so a
        shed request that never inserts leaves no trace."""
        key = _prompt_key(tokens)
        store = self.stores[tenant]
        if key in store:
            self.manager.access(tenant, key)  # policy hit
            return store[key]
        return None  # the miss is accounted when the caller inserts

    def insert(self, tenant: str, tokens, payload: Any) -> None:
        """Store ``payload`` under the prompt's key: issues the miss-side
        policy access and drops payloads the row's policy evicted (store ==
        row residency stays exact).  Mutates the core row and this tenant's
        store."""
        key = _prompt_key(tokens)
        store = self.stores[tenant]
        _, evicted = self.manager.access(tenant, key)
        for ev in evicted:
            store.pop(ev, None)
        store[key] = payload

    def rebalance(self, to: str, n: int = 1, **kw) -> Tuple[int, Dict[str, List[int]]]:
        """Manager rebalance + payload-store coherence for shrunk tenants."""
        moved, evicted_by = self.manager.rebalance(to, n, **kw)
        for t, keys in evicted_by.items():
            for k in keys:
                self.stores[t].pop(k, None)
        return moved, evicted_by

    def telemetry(self) -> Dict[str, dict]:
        """Manager telemetry plus per-tenant payload-store ``entries``
        (read-only; one device sync via the manager)."""
        out = self.manager.telemetry()
        for t, d in out.items():
            d["entries"] = len(self.stores[t])
        return out


def _prompt_key(tokens) -> int:
    """Non-negative int32 prompt key: the device core's id planes are int32
    (host ``PrefixCache`` keys are 63-bit; here the key must round-trip the
    row's ``blocks`` plane).  ``% INT_MAX`` also keeps INT_MAX itself free —
    it's the adaptive cores' never-seen probe id."""
    from repro.cache.prefix_cache import prompt_key

    return prompt_key(tokens) % (2**31 - 1)
