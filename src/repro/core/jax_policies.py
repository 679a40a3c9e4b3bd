"""Vectorized, functional cache-replacement policies in pure JAX.

This is the paper's core contribution adapted to TPU: AWRP's state is two
integer vectors ``(F, R)`` plus a scalar clock; the weight ``W = F/(N-R)`` is
one VPU elementwise pass and the eviction decision one masked min-reduction.
No lists, no pointers, no per-hit data movement — which is precisely the
overhead argument the paper makes against LRU/ARC/CAR, realized on SIMD
hardware.

The policy *decision logic* lives in ``repro.core.policy_core`` — the
uniform ``PolicyState`` protocol (``make_core / init / on_access / victim``)
shared with the serving caches (DESIGN.md §7).  This module keeps the
single-cache convenience API and the batched sweep engine, both now thin
drivers over that core:

API::

    state = init_state(capacity)
    state, hit = access(state, block, policy="awrp")      # single access
    hits = simulate_trace(trace, capacity, policy="awrp") # lax.scan, jittable
    # batched (e.g. one cache per sequence in a serving batch):
    states, hits = jax.vmap(partial(access, policy="awrp"))(states, blocks)

Batched sweep engine (the Table-1 grid as ONE device program)::

    # (n_traces, n_policies, n_caps, T) hit bits, single jit + lax.scan:
    hits = simulate_trace_batched(traces, ["awrp", "lru"], [30, 60, 240],
                                  num_sets=4)

The engine's state is set-associative: per-config ``PolicyState`` planes of
shape ``(rows, num_sets, ways)`` with set index ``block % num_sets``, and
every config in the (trace, policy, capacity) grid flattened onto one
leading rows axis.  Smaller capacities are padded to the widest config's
``ways`` with dead lanes that are masked out of both the first-empty fill
and the victim reduction.  Batching is explicit (flattened grid) rather
than nested ``vmap`` so AWRP victim selection can route through the Pallas
kernel (``repro.kernels.awrp_select_rows``) in its native ``(B, P)`` layout
— a core-level dispatch (``policy_core.awrp_victim_rows``), one kernel
invocation per trace step covering the entire grid.

Decision parity with ``repro.core.policies`` oracles is property-tested
bit-exactly (same float32 weight arithmetic, same first-index ordering).

ARC and CAR — the paper's headline adaptive competitors — ALSO run on the
device engine: their pointer-based lists are re-expressed as fixed-capacity
array state (``policy_core.AdaptiveState``; see DESIGN.md §2/§7).  There is
no trace-length limit: the adaptive stamp counter renormalizes in place
before it can overflow.  Only 2Q/OPT/RANDOM remain host-only.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.core import sharding
from repro.core.policy_core import (
    ADAPTIVE_POLICIES,
    DEVICE_POLICIES,
    INT_MAX,
    JAX_POLICIES,
    POLICY_IDS,
    AdaptiveCore,
    AdaptiveState,
    FlatCore,
    FlatState,
    _GridMasks,
    _make_masks,
    awrp_weights,
    init_adaptive_state,
)
from repro.obs import profiling

__all__ = [
    "CacheState",
    "init_state",
    "access",
    "simulate_trace",
    "awrp_weights",
    "victim_slot",
    "JAX_POLICIES",
    "ADAPTIVE_POLICIES",
    "DEVICE_POLICIES",
    "POLICY_IDS",
    "SetCacheState",
    "AdaptiveState",
    "init_adaptive_state",
    "init_set_state",
    "access_sets",
    "simulate_trace_sets",
    "simulate_trace_batched",
]


class CacheState(NamedTuple):
    """One cache's state; all policies share the layout (unused fields cost
    nothing after DCE in jit)."""

    blocks: jax.Array  # (C,) int32, -1 = empty
    f: jax.Array  # (C,) int32 frequency counters
    r: jax.Array  # (C,) int32 last-access clock
    ins: jax.Array  # (C,) int32 insertion clock (FIFO)
    clock: jax.Array  # () int32 global access clock N


def init_state(capacity: int) -> CacheState:
    """Empty flat sweep-engine state for a (configs, sets, ways) grid —
    kept for the pre-PR-3 call sites; new code uses ``policy_core.init``."""
    return CacheState(
        blocks=jnp.full((capacity,), -1, dtype=jnp.int32),
        f=jnp.zeros((capacity,), dtype=jnp.int32),
        r=jnp.zeros((capacity,), dtype=jnp.int32),
        ins=jnp.zeros((capacity,), dtype=jnp.int32),
        clock=jnp.zeros((), dtype=jnp.int32),
    )


def victim_slot(state: CacheState, policy: str) -> jax.Array:
    """Index of the eviction victim under ``policy`` (assumes a full cache;
    empty slots are masked out so a partially-filled cache is also safe)."""
    occ = state.blocks >= 0
    if policy == "awrp":
        w = awrp_weights(state.f, state.r, state.clock)
        w = jnp.where(occ, w, jnp.inf)
        return jnp.argmin(w)
    if policy == "lru":
        return jnp.argmin(jnp.where(occ, state.r, INT_MAX))
    if policy == "fifo":
        return jnp.argmin(jnp.where(occ, state.ins, INT_MAX))
    if policy == "lfu":
        # lexicographic (frequency, recency) in exact integer arithmetic
        fmasked = jnp.where(occ, state.f, INT_MAX)
        minf = jnp.min(fmasked)
        cand = fmasked == minf
        return jnp.argmin(jnp.where(cand, state.r, INT_MAX))
    if policy in ADAPTIVE_POLICIES:
        raise ValueError(
            f"{policy!r} has no flat CacheState form — its T1/T2/B1/B2 lists "
            "live in AdaptiveState planes inside the policy core; use "
            "simulate_trace / simulate_trace_sets / simulate_trace_batched"
        )
    raise ValueError(f"unknown device policy {policy!r}; have {JAX_POLICIES}")


@functools.partial(jax.jit, static_argnames=("policy",))
def access(
    state: CacheState, block: jax.Array, *, policy: str = "awrp"
) -> Tuple[CacheState, jax.Array]:
    """One access. Fully branch-free (select-based) — scan/vmap friendly."""
    block = block.astype(jnp.int32)
    clock = state.clock + 1

    match = state.blocks == block
    is_hit = jnp.any(match)
    hit_slot = jnp.argmax(match)

    empty = state.blocks < 0
    has_empty = jnp.any(empty)
    first_empty = jnp.argmax(empty)

    # victim selection sees the incremented clock, as the host oracle does
    # (AWRP's dt = N - R_i uses the clock of the access being served)
    victim = victim_slot(state._replace(clock=clock), policy)
    slot = jnp.where(is_hit, hit_slot, jnp.where(has_empty, first_empty, victim))

    new_f = jnp.where(is_hit, state.f[slot] + 1, 1).astype(jnp.int32)
    new_ins = jnp.where(is_hit, state.ins[slot], clock).astype(jnp.int32)
    new_state = CacheState(
        blocks=state.blocks.at[slot].set(block),
        f=state.f.at[slot].set(new_f),
        r=state.r.at[slot].set(clock),
        ins=state.ins.at[slot].set(new_ins),
        clock=clock,
    )
    return new_state, is_hit


@functools.partial(jax.jit, static_argnames=("capacity", "policy"))
def _simulate_trace_flat(
    trace: jax.Array, capacity: int, *, policy: str = "awrp"
) -> jax.Array:
    def step(state, block):
        state, hit = access(state, block, policy=policy)
        return state, hit

    _, hits = jax.lax.scan(step, init_state(capacity), trace.astype(jnp.int32))
    return hits


def simulate_trace(trace, capacity: int, *, policy: str = "awrp") -> jax.Array:
    """Run a whole trace through one cache with ``lax.scan``; returns the
    per-access hit bitvector (device-resident, differentiable-free).

    Flat-state policies run the jitted single-cache scan; ARC/CAR dispatch to
    the batched engine (B=1), which holds their array-encoded list state."""
    if policy in ADAPTIVE_POLICIES:
        return simulate_trace_sets(trace, capacity, policy=policy)
    return _simulate_trace_flat(jnp.asarray(trace), capacity, policy=policy)


# ---------------------------------------------------------------------------
# Batched set-associative sweep engine — a thin scan driver over the
# PolicyState cores (repro.core.policy_core; design notes in DESIGN.md §2/§7)
# ---------------------------------------------------------------------------


#: Set-associative cache state for the incremental single-cache API
#: (``init_set_state``/``access_sets``): ``(num_sets, ways)`` planes with a
#: ``(num_sets,)`` clock — exactly the core's ``FlatState`` layout, so the
#: two are one type (field-for-field duplication would just drift).
SetCacheState = FlatState


def init_set_state(
    capacity: int, num_sets: int = 1, *, max_ways: int | None = None
) -> SetCacheState:
    """State for one set-associative cache: ``num_sets`` independent policy
    instances of ``capacity // num_sets`` ways each (the host simulator's
    mapping).  ``max_ways`` pads the ways axis for mixed-capacity batching."""
    if capacity % num_sets:
        raise ValueError(f"capacity {capacity} not divisible by num_sets {num_sets}")
    ways = capacity // num_sets
    W = ways if max_ways is None else max_ways
    if W < ways:
        raise ValueError(f"max_ways {W} < ways {ways}")
    return SetCacheState(
        blocks=jnp.full((num_sets, W), -1, dtype=jnp.int32),
        f=jnp.zeros((num_sets, W), dtype=jnp.int32),
        r=jnp.zeros((num_sets, W), dtype=jnp.int32),
        clock=jnp.zeros((num_sets,), dtype=jnp.int32),
    )


#: ``jax.named_scope`` of each policy core's call in the sweep scan step: a
#: compiled op's ``op_name`` names the core it belongs to, which
#: ``Sentinel.op_scopes`` reads (scope metadata only; the program is unchanged)
CORE_SCOPES = {"flat": "sweep_flat", "arc": "sweep_arc", "car": "sweep_car"}


# sentinel-wrapped jit (obs.profiling): the sweep scan's trace count,
# cache size and jaxpr eqn audit surface as compile/sweep_scan/... gauges
@functools.partial(
    profiling.instrument,
    "sweep_scan",
    scopes=tuple(CORE_SCOPES.values()),
    static_argnames=(
        "policy_ids", "ways", "num_sets", "use_kernel", "unroll", "renorm_at",
        "mesh",
    ),
)
def _simulate_batched_impl(
    traces: jax.Array,  # (N, T) int32
    policy_ids: Tuple[int, ...],
    ways: Tuple[int, ...],  # per-capacity ways
    num_sets: int,
    use_kernel: bool,
    unroll: int,
    renorm_at: Optional[int],
    mesh,
) -> jax.Array:
    N, T = traces.shape
    P, C = len(policy_ids), len(ways)
    PC = P * C
    maxW = max(ways)
    W = maxW
    if use_kernel:
        W += (-W) % 128  # pre-align lanes so the kernel wrapper's pad is a no-op

    # grid flattening: b = (n*P + p)*C + c  (capacity axis fastest).  Rows
    # partition statically by state layout: flat-state (awrp/lru/fifo/lfu)
    # rows share one FlatCore; arc and car rows each get an AdaptiveCore.
    # Hits re-interleave with a static gather.
    pids = np.tile(np.repeat(np.asarray(policy_ids, np.int32), C), N)
    ways_b = np.tile(np.asarray(ways, np.int32), N * P)
    simple_idx = np.flatnonzero(
        np.isin(pids, [POLICY_IDS[p] for p in JAX_POLICIES])
    )
    arc_idx = np.flatnonzero(pids == POLICY_IDS["arc"])
    car_idx = np.flatnonzero(pids == POLICY_IDS["car"])
    inv = jnp.asarray(np.argsort(np.concatenate([simple_idx, arc_idx, car_idx])))
    Bs, Ba, Bc = len(simple_idx), len(arc_idx), len(car_idx)
    take_s, take_a, take_c = map(jnp.asarray, (simple_idx, arc_idx, car_idx))

    L = 2 * maxW  # adaptive directory lanes (cache + ghosts)
    xs = traces.T.astype(jnp.int32)  # (T, N)

    if mesh is not None:
        hits = _sharded_groups_scan(
            xs, mesh,
            num_sets=num_sets, use_kernel=use_kernel, unroll=unroll,
            renorm_at=renorm_at, pids=pids, ways_b=ways_b,
            simple_idx=simple_idx, arc_idx=arc_idx, car_idx=car_idx,
            W=W, L=L, maxW=maxW, PC=PC,
        )
        return jnp.moveaxis(hits[:, inv], 0, -1).reshape(N, P, C, T)

    flat_core = (
        FlatCore(
            pids=tuple(int(p) for p in pids[simple_idx]),
            ways=tuple(int(w) for w in ways_b[simple_idx]),
            num_sets=num_sets,
            lanes=W,
            use_kernel=use_kernel,
        )
        if Bs
        else None
    )
    arc_core = (
        AdaptiveCore(
            kind="arc",
            caps=tuple(int(w) for w in ways_b[arc_idx]),
            num_sets=num_sets,
            lanes=L,
            renorm_at=renorm_at,
        )
        if Ba
        else None
    )
    car_core = (
        AdaptiveCore(
            kind="car",
            caps=tuple(int(w) for w in ways_b[car_idx]),
            num_sets=num_sets,
            lanes=L,
            renorm_at=renorm_at,
        )
        if Bc
        else None
    )

    def step(carry, block_n):
        flat_st, arc_st, car_st = carry
        block = jnp.repeat(block_n, PC)
        outs = []
        if flat_core is not None:
            ids = block[take_s]
            with jax.named_scope(CORE_SCOPES["flat"]):
                flat_st, h = flat_core.on_access(flat_st, ids)
            outs.append(h)
        if arc_core is not None:
            ids = block[take_a]
            with jax.named_scope(CORE_SCOPES["arc"]):
                arc_st, h = arc_core.on_access(arc_st, ids)
            outs.append(h)
        if car_core is not None:
            ids = block[take_c]
            with jax.named_scope(CORE_SCOPES["car"]):
                car_st, h = car_core.on_access(car_st, ids)
            outs.append(h)
        hits = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
        return (flat_st, arc_st, car_st), hits

    carry0 = (
        flat_core.init() if flat_core is not None else (),
        arc_core.init() if arc_core is not None else (),
        car_core.init() if car_core is not None else (),
    )
    _, hits = jax.lax.scan(step, carry0, xs, unroll=unroll)

    # (T, concat-of-groups) -> original row order -> (N, P, C, T)
    return jnp.moveaxis(hits[:, inv], 0, -1).reshape(N, P, C, T)


def _sharded_groups_scan(
    xs: jax.Array,  # (T, N) int32
    mesh,
    *,
    num_sets: int,
    use_kernel: bool,
    unroll: int,
    renorm_at: Optional[int],
    pids: np.ndarray,  # (B,) grid policy ids
    ways_b: np.ndarray,  # (B,) grid per-row ways
    simple_idx: np.ndarray,
    arc_idx: np.ndarray,
    car_idx: np.ndarray,
    W: int,
    L: int,
    maxW: int,
    PC: int,
) -> jax.Array:
    """Mesh-sharded grid scan (DESIGN.md §4): the whole sweep inside ONE
    ``shard_map`` over the rows mesh.

    Each state-layout group (flat / arc / car) pads its rows up to a
    device-count multiple (``sharding.pad_rows_to``; the pad rows run real
    accesses whose hits are sliced off) and every per-row constant — the
    flat grid masks, the adaptive capacities, each row's trace index — is
    passed in as a *sharded operand* rather than closed over, so each
    device's trace sees only its own rows.  That makes the two patterns
    GSPMD partitions badly shard-local instead: the flat cores' per-row
    scatters stay device-local, and CAR's clock-hand ``while_loop``
    terminates on the device's own rows (a per-shard ``jnp.any``, not a
    per-iteration collective).  The scan body has no cross-row reductions,
    so the program has ZERO per-step collectives; decisions are
    bit-identical to the unsharded scan because per-row arithmetic is
    untouched — only the partitioning changes (tests/test_sharding.py).

    Returns ``(T, Bs+Ba+Bc)`` hits in the unsharded path's
    group-concatenated row order."""
    n = mesh.devices.size
    rows_p = PartitionSpec(sharding.ROWS_AXIS)
    operands, specs_in, group_meta = [], [], []

    def add_group(kind: str, idx: np.ndarray) -> None:
        B = len(idx)
        if not B:
            return
        Bp = sharding.pad_rows_to(B, n)
        k = Bp // n
        tr = np.zeros((Bp,), np.int32)
        tr[:B] = (idx // PC).astype(np.int32)
        if kind == "flat":
            pids_p = np.full((Bp,), POLICY_IDS["lru"], np.int32)
            pids_p[:B] = pids[idx]
            ways_p = np.ones((Bp,), np.int32)
            ways_p[:B] = ways_b[idx]
            # the template fixes only the SHARD's row count and layout;
            # policy identity/capacity come from the sharded masks operand
            tmpl = FlatCore(
                pids=(POLICY_IDS["lru"],) * k, ways=(1,) * k,
                num_sets=num_sets, lanes=W, use_kernel=use_kernel,
            )
            state0 = FlatCore(
                pids=tuple(int(p) for p in pids_p),
                ways=tuple(int(w) for w in ways_p),
                num_sets=num_sets, lanes=W, use_kernel=use_kernel,
            ).init()
            aux = _make_masks(pids_p, ways_p, W)
            aux_spec = _GridMasks(
                lru_or_fifo=PartitionSpec(sharding.ROWS_AXIS, None),
                lfu=PartitionSpec(sharding.ROWS_AXIS, None),
                awrp_row=rows_p,
                fifo_row=rows_p,
                dead=PartitionSpec(sharding.ROWS_AXIS, None),
                iota=PartitionSpec(None, None),
            )
        else:
            caps_p = np.ones((Bp,), np.int32)
            caps_p[:B] = ways_b[idx]
            tmpl = AdaptiveCore(
                kind=kind, caps=(maxW,) * k, num_sets=num_sets, lanes=L,
                renorm_at=renorm_at,
            )
            state0 = init_adaptive_state(Bp, num_sets, L)
            aux = jnp.asarray(caps_p)
            aux_spec = rows_p
        operands.extend([state0, aux, jnp.asarray(tr)])
        specs_in.extend([sharding.state_spec(state0), aux_spec, rows_p])
        group_meta.append((kind, tmpl, B))

    add_group("flat", simple_idx)
    add_group("arc", arc_idx)
    add_group("car", car_idx)

    def run(*ops):
        xs_l = ops[3 * len(group_meta)]

        def step(carry, block_n):
            new_states, outs = [], []
            for g, (kind, tmpl, _) in enumerate(group_meta):
                ids = block_n[ops[3 * g + 2]]
                with jax.named_scope(CORE_SCOPES[kind]):
                    if kind == "flat":
                        st, h = tmpl.on_access(carry[g], ids, masks=ops[3 * g + 1])
                    else:
                        st, h = tmpl.on_access(carry[g], ids, caps=ops[3 * g + 1])
                new_states.append(st)
                outs.append(h)
            return tuple(new_states), tuple(outs)

        carry0 = tuple(ops[3 * g] for g in range(len(group_meta)))
        _, hits = jax.lax.scan(step, carry0, xs_l, unroll=unroll)
        return hits

    hits = jax.shard_map(
        run,
        mesh=mesh,
        in_specs=tuple(specs_in) + (PartitionSpec(None, None),),
        out_specs=tuple(
            PartitionSpec(None, sharding.ROWS_AXIS) for _ in group_meta
        ),
        check_vma=False,
    )(*operands, xs)
    return jnp.concatenate(
        [h[:, :B] for h, (_, _, B) in zip(hits, group_meta)], axis=1
    )


def simulate_trace_batched(
    traces,
    policies: Sequence[str],
    capacities: Sequence[int],
    *,
    num_sets: int = 1,
    use_kernel: bool | None = None,
    unroll: int = 1,
    mesh=None,
    _renorm_at: Optional[int] = None,
) -> jax.Array:
    """Run the full (trace, policy, capacity) grid as ONE jitted program.

    Args:
      traces: ``(T,)`` or ``(N, T)`` non-negative block ids (equal lengths —
        pad/trim on the host if needed; padding would perturb cache state).
      policies: device policy names (subset of ``DEVICE_POLICIES`` —
        flat-state awrp/lru/fifo/lfu plus array-encoded arc/car).
      capacities: total cache capacities; each must divide by ``num_sets``.
        Mixed sizes batch together — smaller caches get dead padding lanes
        masked out of both fill and eviction.
      num_sets: set-associative mapping ``set = block % num_sets`` (the host
        simulator's convention); per-set clocks match one host policy
        instance per set.
      use_kernel: route AWRP victim selection through the Pallas rows kernel
        (``repro.kernels.awrp_select_rows``).  Default: True on TPU (kernel
        runs native), False elsewhere — interpret-mode emulation adds
        per-step overhead the inline bit-pattern min-reduction avoids.
        Decisions are identical either way (property-tested).
      unroll: ``lax.scan`` unroll factor.
      mesh: optional ``jax.sharding.Mesh`` with a ``"rows"`` axis
        (``core.sharding.rows_mesh``): the flattened (trace, policy,
        capacity) grid axis is sharded across its devices via ``shard_map``
        — each device scans only its own rows (groups pad to a device-count
        multiple internally), with zero per-step collectives, so
        mixed-capacity sweeps scale with the number of devices backed by
        real cores.  The step functions are row-local (no cross-row
        reductions), so decisions are bit-identical to the unsharded
        engine — property-tested in tests/test_sharding.py.  ``None``
        (default) runs unsharded.
      _renorm_at: test hook — override the adaptive stamp-renormalization
        threshold (forcing frequent renormalizations); None picks it
        automatically (and elides the check entirely for traces short
        enough that the stamp counter cannot approach int32 range).

    Returns:
      bool array ``(n_traces, n_policies, n_capacities, T)`` of per-access
      hits, bit-identical to the host oracles' decisions.  Trace length is
      unbounded: adaptive rows renormalize their stamp planes in place
      before the stamp counter could overflow (decision-preserving; see
      ``policy_core._renorm_stamps``).
    """
    tr = np.asarray(traces)
    if tr.ndim == 1:
        tr = tr[None, :]
    if tr.ndim != 2:
        raise ValueError(f"traces must be (T,) or (N, T), got shape {tr.shape}")
    if tr.size and (tr.min() < 0 or tr.max() > INT_MAX):
        raise ValueError(
            "block ids must fit int32 (0 <= id <= 2**31-1); rebase or hash "
            "the address space first"
        )
    policies = tuple(policies)
    capacities = tuple(int(c) for c in capacities)
    unknown = [p for p in policies if p not in POLICY_IDS]
    if unknown:
        raise ValueError(f"not device policies: {unknown}; have {DEVICE_POLICIES}")
    if not policies or not capacities:
        raise ValueError("need at least one policy and one capacity")
    ways = []
    for c in capacities:
        if c % num_sets:
            raise ValueError(f"capacity {c} not divisible by num_sets {num_sets}")
        ways.append(c // num_sets)
    renorm_at = _renorm_at
    if renorm_at is None and any(p in ADAPTIVE_POLICIES for p in policies):
        # ARC/CAR grant at most ways+2 stamps per access; when the whole
        # trace cannot approach the renormalization ceiling, elide the
        # per-step check statically (it costs nothing on Table-1 traces)
        auto = AdaptiveCore(kind="arc", caps=(max(ways),)).renorm_at
        if tr.shape[1] * (max(ways) + 2) >= auto:
            renorm_at = auto
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    return _simulate_batched_impl(
        jnp.asarray(tr, dtype=jnp.int32),
        tuple(POLICY_IDS[p] for p in policies),
        tuple(ways),
        int(num_sets),
        bool(use_kernel),
        int(unroll),
        renorm_at,
        mesh,
    )


def simulate_trace_sets(
    trace, capacity: int, *, policy: str = "awrp", num_sets: int = 1,
    use_kernel: bool | None = None,
) -> jax.Array:
    """Single-config set-associative trace simulation (batched engine, B=1)."""
    hits = simulate_trace_batched(
        np.asarray(trace)[None, :], (policy,), (capacity,),
        num_sets=num_sets, use_kernel=use_kernel,
    )
    return hits[0, 0, 0]


@functools.partial(jax.jit, static_argnames=("policy", "use_kernel"))
def access_sets(
    state: SetCacheState, block: jax.Array, *, policy: str = "awrp",
    use_kernel: bool = False,
) -> Tuple[SetCacheState, jax.Array]:
    """One access against a single ``(num_sets, ways)`` state (incremental
    API, e.g. a serving-side set-associative pool).  All lanes are live; for
    mixed-capacity batches use ``simulate_trace_batched``.  Flat-state
    policies only — ARC/CAR carry ``AdaptiveState`` and run through the
    policy core (``policy_core.make_core``) or the batched engine."""
    if policy not in JAX_POLICIES:
        raise ValueError(
            f"access_sets supports the flat-state policies {JAX_POLICIES}; "
            f"adaptive policies {ADAPTIVE_POLICIES} run via the policy core"
        )
    num_sets, W = state.blocks.shape
    core = FlatCore(
        pids=(POLICY_IDS[policy],), ways=(W,), num_sets=num_sets,
        lanes=W, use_kernel=use_kernel,
    )
    if num_sets == 1:
        # the (S=1, W) planes already ARE the core's squeezed (rows=1, W)
        state, is_hit = core.on_access(
            state, jnp.asarray(block, jnp.int32)[None]
        )
    else:
        # adapt the single-cache (S, W) layout to the core's (rows=1, S, W)
        fstate = FlatState(
            blocks=state.blocks[None], f=state.f[None], r=state.r[None],
            clock=state.clock[None],
        )
        fstate, is_hit = core.on_access(
            fstate, jnp.asarray(block, jnp.int32)[None]
        )
        state = SetCacheState(
            blocks=fstate.blocks[0], f=fstate.f[0], r=fstate.r[0],
            clock=fstate.clock[0],
        )
    return state, is_hit[0]
