"""Unified device policy core: one ``PolicyState`` API powering the batched
sweep engine AND the serving caches (DESIGN.md §7).

The paper's pitch is AWRP as a *live* replacement policy with low overhead.
This module is where that claim is made structural: every device-capable
policy — the flat-state quartet (awrp/lru/fifo/lfu) and the array-encoded
adaptive pair (arc/car) — is implemented ONCE here, behind a uniform
protocol, and every consumer (the Table-1 sweep engine in
``repro.core.jax_policies``, the paged-KV pool in ``repro.cache.paged_kv``,
the MoE expert cache in ``repro.cache.expert_cache``) is a thin driver over
the same step functions.  Decisions are bit-identical to the host oracles in
``repro.core.policies`` — the existing parity suites are the contract.

Protocol::

    core = make_core(policy, rows, num_sets, ways)   # static spec
    state = core.init()                              # PolicyState pytree
    state, hit = core.on_access(state, ids)          # ids: (rows,) int32
    lane = core.victim(state)                        # advisory next victim

``rows`` is a free batch axis of independent policy instances — one per
(trace, policy, capacity) grid config in the sweep engine, one per sequence
in the paged-KV pool, one per layer in the expert cache.  ``on_access``
accepts an optional ``active`` row mask so serving callers can issue masked
no-op accesses (rows where ``active`` is False keep their state, tick no
clock, and report no hit).

Two state layouts implement the protocol:

* ``FlatState`` — ``(rows, num_sets, ways)`` planes ``blocks/F/R`` plus a
  per-set clock.  One slot array is the whole state; R doubles as FIFO's
  insertion clock (DESIGN.md §2).
* ``AdaptiveState`` — ARC/CAR's pointer lists re-expressed as
  ``tag/stamp/ref`` planes over ``L = 2*ways`` lanes plus per-set ``p`` and
  a stamp counter (DESIGN.md §2).  Long runs are safe: when ``ctr`` nears
  the int32 range the stamps are renormalized in place (dense-ranked per
  row-set, which preserves every within-list order and therefore every
  decision) — there is no trace-length limit.

Victim *reductions* also live here (``first_min``, ``awrp_victim_rows``):
the Pallas ``awrp_select_rows`` route is a core-level dispatch
(``use_kernel``), so kernels are an implementation detail of the core, not
of its callers.  No argmin anywhere — every selection is a chain of
vectorizable min-reductions over bit-pattern keys (DESIGN.md §2).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sharding

__all__ = [
    "INT_MAX",
    "JAX_POLICIES",
    "ADAPTIVE_POLICIES",
    "DEVICE_POLICIES",
    "POLICY_IDS",
    "FlatState",
    "AdaptiveState",
    "PolicyState",
    "RowCounters",
    "ADMIT_ACCEPT",
    "ADMIT_DEFER",
    "ADMIT_SHED",
    "admission_decide",
    "admission_decay",
    "FlatCore",
    "AdaptiveCore",
    "PolicyCore",
    "make_core",
    "init",
    "div_rn",
    "awrp_weights",
    "first_min",
    "awrp_victim_rows",
    "make_cache_policy",
]

INT_MAX = np.iinfo(np.int32).max

#: flat-state policies: one (blocks, F, R) slot array is their entire state.
JAX_POLICIES = ("awrp", "lru", "fifo", "lfu")

#: list-structured adaptive policies, device-capable via the array encoding.
ADAPTIVE_POLICIES = ("arc", "car")

#: everything the device core (and therefore every driver) accepts.
DEVICE_POLICIES = JAX_POLICIES + ADAPTIVE_POLICIES

#: stable integer encoding of the device policies; consumed by name via
#: ``_make_masks``, so the numbering is arbitrary but must stay stable
#: within a jitted program.
POLICY_IDS = {name: i for i, name in enumerate(DEVICE_POLICIES)}


_MASK24 = (1 << 24) - 1


def _mant_exp(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``x = m * 2**e`` exactly for a positive normal float32 ``x``: int32
    ``(m, e)`` with ``2**23 <= m < 2**24``."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return (b & 0x7FFFFF) | 0x800000, ((b >> 23) & 0xFF) - 150


def _mul24(a: jax.Array, d: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``a * d`` as base-2**24 limbs ``(hi, lo)`` in int32, for
    ``0 <= a < 2**25`` and ``0 <= d < 2**24`` (12-bit partial products)."""
    a0, a1 = a & 0xFFF, a >> 12
    d0, d1 = d & 0xFFF, d >> 12
    mid = a0 * d1 + a1 * d0
    t = a0 * d0 + ((mid & 0xFFF) << 12)
    return a1 * d1 + (mid >> 12) + (t >> 24), t & _MASK24


def div_rn(x: jax.Array, y: jax.Array) -> jax.Array:
    """``x / y`` in float32, rounded to nearest-even exactly as IEEE (and
    numpy, so the host oracles) rounds it — on every backend.

    The TPU's float32 divide is not correctly rounded, and every decision
    here compares quotients bit for bit (AWRP's ``F/(N-R)``, ARC/CAR's
    ``p`` step, the paged-KV reference threshold ``1/residents``), so a
    one-ulp difference changes which block is evicted.  The backend's
    quotient is corrected with exact integer arithmetic
    (``_round_quotient``).

    Domain: ``x >= 0`` and ``y > 0`` normal floats (``x`` may be 0) with a
    normal quotient — every caller divides a count by a count >= 1."""
    # one shape for both: a TPU kernel cannot bitcast a scalar
    x, y = jnp.broadcast_arrays(jnp.asarray(x, jnp.float32),
                                jnp.asarray(y, jnp.float32))
    return _round_quotient(x, y, x / y)


def _round_quotient(x: jax.Array, y: jax.Array, q0: jax.Array) -> jax.Array:
    """``div_rn``'s correction: the float32 nearest ``x / y`` (ties to
    even), given a quotient ``q0 = m_q 2**e_q`` within 2 ulp of it.

    With ``u = 2**e_q``, ``x/y - q0 = u R / m_y`` for the integer ``R =
    m_x 2**(e_x - e_y - e_q) - m_q m_y`` (exact: base-2**24 limbs, and
    ``|R| < 2**26`` near ``q0``).  The floats from ``q0 - 2`` to ``q0 + 2``
    ulp sit at known multiples of ``u/4`` (spacing halves below a power of
    two), so ``x/y`` lies above the midpoint of two neighbours, ``S u/8``
    from ``q0``, iff ``8 R > S m_y``.  The result is ``q0 - 2`` ulp plus
    the number of those four midpoints below ``x/y`` (a quotient of two
    floats is never a midpoint, so there are no ties to break)."""
    b0 = jax.lax.bitcast_convert_type(q0, jnp.int32)
    mx, ex = _mant_exp(x)
    my, ey = _mant_exp(y)
    mq, eq = _mant_exp(q0)
    s = ex - ey - eq  # 22 <= s <= 25 near q0
    hi_l = jnp.where(s < 24, mx >> jnp.clip(24 - s, 0, 31),
                     mx << jnp.clip(s - 24, 0, 7))
    lo_l = jnp.where(s < 24, (mx << jnp.clip(s, 0, 31)) & _MASK24, 0)
    hi_r, lo_r = _mul24(mq, my)
    r8 = ((hi_l - hi_r) * (1 << 24) + (lo_l - lo_r)) * 8
    # neighbours' offsets from q0 in quarter-ulps: +4, +8 (+12 past the top
    # of the binade), -4, -8 (-2, -4 / -4, -6 just above its bottom)
    top = (mq == _MASK24).astype(jnp.int32)
    bot0 = (mq == 1 << 23).astype(jnp.int32)
    bot1 = (mq == (1 << 23) + 1).astype(jnp.int32)
    pos_m1 = -4 + 2 * bot0
    pos_m2 = -8 + 4 * bot0 + 2 * bot1
    above = jnp.zeros_like(b0)
    for mid in (pos_m2 + pos_m1, pos_m1, 4, 12 + 4 * top):
        above = above + (r8 > mid * my).astype(jnp.int32)
    bits = jnp.where(x > 0, b0 - 2 + above, 0)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def awrp_weights(f: jax.Array, r: jax.Array, clock: jax.Array) -> jax.Array:
    """Paper eq. (1): W_i = F_i / (N - R_i), float32 (``div_rn``, so
    bitwise the host oracle's), residents only (callers mask empties to
    +inf)."""
    dt = jnp.maximum(clock - r, 1).astype(jnp.float32)
    return div_rn(f.astype(jnp.float32), dt)


# ---------------------------------------------------------------------------
# victim reductions (shared by the core, the serving decision points, and —
# through the use_kernel dispatch — the Pallas kernels)
# ---------------------------------------------------------------------------


def first_min(key: jax.Array) -> jax.Array:
    """First index achieving the row minimum of ``key`` (..., P) int32 —
    ``argmin`` semantics as two vectorizable min-reductions."""
    P = key.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, key.shape, key.ndim - 1)
    m = jnp.min(key, axis=-1, keepdims=True)
    return jnp.min(jnp.where(key == m, lane, P), axis=-1).astype(jnp.int32)


def awrp_victim_rows(
    f: jax.Array,  # (B, P) int32
    r: jax.Array,  # (B, P) int32
    clock: jax.Array,  # (B,) int32
    valid: jax.Array,  # (B, P) bool
    *,
    use_kernel: bool = False,
) -> jax.Array:
    """Core-level AWRP victim dispatch: the Pallas ``awrp_select_rows``
    kernel (TPU) or the inline bit-pattern min-reduction — identical
    decisions either way (property-tested).  ``w >= 0`` always, so IEEE
    float order == int32 bit order."""
    if use_kernel:
        from repro.kernels.ops import awrp_select_rows

        return awrp_select_rows(f, r, clock, valid.astype(jnp.int32))
    w = awrp_weights(f, r, clock[:, None])
    bits = jax.lax.bitcast_convert_type(w, jnp.int32)
    return first_min(jnp.where(valid, bits, INT_MAX))


# ---------------------------------------------------------------------------
# flat-state policies (awrp / lru / fifo / lfu)
# ---------------------------------------------------------------------------


class FlatState(NamedTuple):
    """Per-row flat policy state.  Set-associative cores carry
    ``(rows, num_sets, ways)`` planes with a ``(rows, num_sets)`` clock;
    single-set cores (``num_sets == 1`` — the sweep engine's layout and
    every serving caller) DROP the sets axis: ``(rows, ways)`` planes,
    ``(rows,)`` clock.  The squeeze is not cosmetic — scatter updates that
    round-trip a reshape defeat XLA's in-place scan-carry optimization and
    cost ~20% of the engine's step budget on CPU.  ``blocks == -1`` marks
    an empty lane; dead lanes (capacity padding in a mixed-ways batch) are
    identified by the core's mask, never a sentinel."""

    blocks: jax.Array  # (B[, S], W) int32, -1 = empty
    f: jax.Array  # (B[, S], W) int32 frequency counters
    r: jax.Array  # (B[, S], W) int32 recency clock (insertion clock for FIFO)
    clock: jax.Array  # (B[, S]) int32 per-set access clock N


class _GridMasks(NamedTuple):
    """Per-row constants of a flat-core batch (closed over by scan bodies)."""

    lru_or_fifo: jax.Array  # (B, 1) bool
    lfu: jax.Array  # (B, 1) bool
    awrp_row: jax.Array  # (B,) bool
    fifo_row: jax.Array  # (B,) bool
    dead: jax.Array  # (B, W) bool — capacity-padding lanes
    iota: jax.Array  # (1, W) int32 lane indices


def _make_masks(pids: np.ndarray, ways_b: np.ndarray, W: int) -> _GridMasks:
    pids = np.asarray(pids)
    return _GridMasks(
        lru_or_fifo=jnp.asarray(
            (pids == POLICY_IDS["lru"]) | (pids == POLICY_IDS["fifo"])
        )[:, None],
        lfu=jnp.asarray(pids == POLICY_IDS["lfu"])[:, None],
        awrp_row=jnp.asarray(pids == POLICY_IDS["awrp"]),
        fifo_row=jnp.asarray(pids == POLICY_IDS["fifo"]),
        dead=jnp.asarray(~(np.arange(W)[None, :] < np.asarray(ways_b)[:, None])),
        iota=jnp.arange(W, dtype=jnp.int32)[None, :],
    )


def _flat_victim(
    row_f: jax.Array,  # (B, W) int32
    row_r: jax.Array,  # (B, W) int32
    clk: jax.Array,  # (B,) int32 — the clock the decision is made at
    masks: _GridMasks,
    use_kernel: bool,
) -> jax.Array:
    """Policy-keyed victim selection over one (B, W) row batch.  Also
    performs empty-lane fill: an empty lane has F = R = 0, so its key beats
    every occupied lane under all four policies and ties break to the lowest
    lane index — exactly the host oracles' first-empty order (DESIGN.md §2)."""
    iota = masks.iota
    # stage 1: policy-selected primary key, min over lanes
    if use_kernel:
        v_awrp = awrp_victim_rows(row_f, row_r, clk, ~masks.dead, use_kernel=True)
        prim = jnp.where(masks.lfu, row_f, row_r)  # awrp rows: unused filler
    else:
        w = awrp_weights(row_f, row_r, clk[:, None])
        wbits = jax.lax.bitcast_convert_type(w, jnp.int32)
        prim = jnp.where(
            masks.lru_or_fifo, row_r, jnp.where(masks.lfu, row_f, wbits)
        )
    prim = jnp.where(masks.dead, INT_MAX, prim)
    m1 = jnp.min(prim, axis=-1)
    # stage 2: tie-break key (recency for LFU, lane index otherwise)
    sec = jnp.where(masks.lfu, row_r, iota)
    k2 = jnp.where(prim == m1[:, None], sec, INT_MAX)
    m2 = jnp.min(k2, axis=-1)
    # stage 3: first lane achieving (m1, m2)
    W = row_f.shape[-1]
    victim = jnp.min(jnp.where(k2 == m2[:, None], iota, W), axis=-1)
    if use_kernel:
        victim = jnp.where(masks.awrp_row, v_awrp, victim)
    return victim


def _row_step(
    row_blocks: jax.Array,  # (B, W) int32
    row_f: jax.Array,  # (B, W) int32
    row_r: jax.Array,  # (B, W) int32
    clk: jax.Array,  # (B,) int32 — this access's clock value per row
    block: jax.Array,  # (B,) int32
    masks: _GridMasks,
    use_kernel: bool,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Shared per-access decision logic -> (slot, is_hit, new_f, new_r)."""
    W = row_blocks.shape[-1]
    iota = masks.iota

    # hit detection: one vectorized min-reduce (W = miss sentinel)
    match = row_blocks == block[:, None]
    hit_k = jnp.min(jnp.where(match, iota, W), axis=-1)
    is_hit = hit_k < W

    victim = _flat_victim(row_f, row_r, clk, masks, use_kernel)
    slot = jnp.where(is_hit, hit_k, victim)
    old_f = jnp.take_along_axis(row_f, slot[:, None], -1)[:, 0]
    old_r = jnp.take_along_axis(row_r, slot[:, None], -1)[:, 0]
    new_f = jnp.where(is_hit, old_f + 1, 1).astype(jnp.int32)
    # FIFO keeps its insertion clock in R: freeze R on hits for FIFO rows
    new_r = jnp.where(is_hit & masks.fifo_row, old_r, clk).astype(jnp.int32)
    return slot, is_hit, new_f, new_r


# ---------------------------------------------------------------------------
# adaptive (ARC/CAR) array-encoded policies
# ---------------------------------------------------------------------------
#
# The pointer structures of ARC (four LRU lists + p) and CAR (two clocks with
# reference bits + two LRU ghost lists + p) become five planes over L = 2*ways
# lanes (ARC's |T1|+|T2|+|B1|+|B2| <= 2c invariant bounds occupancy; CAR's
# directory obeys the same bound):
#
#   tag    — list membership: 0 free, 1 T1, 2 T2, 3 B1, 4 B2
#   stamp  — within-list order from a per-(row, set) monotone counter; a
#            list's LRU / clock hand is its min-stamp lane, its MRU / tail
#            the max.  Every insertion, MRU-move, clock rotation and ghost
#            append grants a fresh stamp, so stamps are unique per row-set
#            and every list op is a masked min-reduction — no argmin, no
#            data-dependent list surgery.
#   ref    — CAR's reference bits (unused by ARC rows)
#   p      — the adaptation target, float32 (same IEEE ops as the host
#            oracles, whose p is maintained in float32 for exactly this
#            reason: int(p) comparisons match bit-for-bit)
#   ctr    — the stamp counter (bounded by ~(ways+2) grants per access;
#            renormalized in place before it can overflow — see
#            ``AdaptiveCore.renorm_at``)
#
# CAR's clock-hand sweep (`CAR._replace`'s while loop) promotes/rotates at
# most |T1| + #ref-bits-set + 1 <= ways + 1 pages before evicting, so it runs
# as a lax.while_loop with masked per-row no-ops, bounded by max_ways + 1.

_FREE, _TAG_T1, _TAG_T2, _TAG_B1, _TAG_B2 = 0, 1, 2, 3, 4

#: POLICY_IDS values of the flat-state policies (the engine's partition)
_SIMPLE_IDS = tuple(POLICY_IDS[p] for p in JAX_POLICIES)


class AdaptiveState(NamedTuple):
    """Array-encoded ARC/CAR state for a batch of policy instances; shapes
    ``(B, num_sets, L)`` planes and ``(B, num_sets)`` scalars, L = 2*ways
    (padded to the widest config in a mixed-capacity batch — the
    first-free-lane insertion rule keeps occupancy inside each row's own
    2*ways prefix, so no dead-lane mask is needed)."""

    blocks: jax.Array  # (B, S, L) int32 block ids, -1 = free lane
    tag: jax.Array  # (B, S, L) int32 list membership (_FREE.._TAG_B2)
    stamp: jax.Array  # (B, S, L) int32 within-list order
    ref: jax.Array  # (B, S, L) int32 CAR reference bits (0/1)
    p: jax.Array  # (B, S) float32 ARC/CAR adaptation target
    ctr: jax.Array  # (B, S) int32 stamp counter


PolicyState = Union[FlatState, AdaptiveState]


def init_adaptive_state(batch: int, num_sets: int, lanes: int) -> AdaptiveState:
    """Empty ``AdaptiveState`` for ``rows x num_sets`` ARC/CAR instances with
    per-row capacities ``caps`` (L = 2*max(caps) lanes; dead lanes masked)."""
    return AdaptiveState(
        blocks=jnp.full((batch, num_sets, lanes), -1, dtype=jnp.int32),
        tag=jnp.zeros((batch, num_sets, lanes), dtype=jnp.int32),
        stamp=jnp.zeros((batch, num_sets, lanes), dtype=jnp.int32),
        ref=jnp.zeros((batch, num_sets, lanes), dtype=jnp.int32),
        p=jnp.zeros((batch, num_sets), dtype=jnp.float32),
        ctr=jnp.zeros((batch, num_sets), dtype=jnp.int32),
    )


def _list_counts(tag: jax.Array):
    """Per-list (T1, T2, B1, B2) sizes as one stacked ``(4, R)`` reduction.

    The (4, 1, 1) tag stack is built with ``broadcasted_iota`` rather than a
    module-level numpy constant so the whole step stays constant-free and can
    be traced inside a ``pallas_call`` body (kernels/policy_attn.py)."""
    stack = _TAG_T1 + jax.lax.broadcasted_iota(jnp.int32, (4, 1, 1), 0)
    return jnp.sum(tag[None] == stack, axis=-1)


def _keyed_head(tag: jax.Array, stamp: jax.Array, want: jax.Array) -> jax.Array:
    """One-hot ``(R, L)`` mask of the min-stamp lane whose tag equals the
    per-row target ``want`` (R,) — the selected list's LRU end / clock hand.
    All-False for rows whose target list is empty (or ``want`` is the -1
    no-op sentinel: no lane carries tag -1).  One keyed min-reduction covers
    what would otherwise be a head computation per list: the step logic only
    ever consumes ONE head per row, so the target list id is selected first
    and the scan stays a single ``(R, L)`` pass — the per-step cost floor is
    memory bandwidth over the planes, not the reduction count."""
    in_list = tag == want[:, None]
    m = jnp.min(jnp.where(in_list, stamp, INT_MAX), axis=-1, keepdims=True)
    return in_list & (stamp == m)


def _ghost_ratio(in_b1: jax.Array, n3f: jax.Array, n4f: jax.Array) -> jax.Array:
    """The ghost-hit ratio of ARC/CAR's ``p`` step: ``|B2| / max(|B1|, 1)``
    on a B1 hit, ``|B1| / max(|B2|, 1)`` otherwise (read only on a B2
    hit) — one ``div_rn`` serves both directions."""
    one = jnp.float32(1.0)
    return div_rn(jnp.where(in_b1, n4f, n3f),
                  jnp.maximum(jnp.where(in_b1, n3f, n4f), one))


def _arc_step(
    blocks: jax.Array,  # (R, L) int32
    tag: jax.Array,  # (R, L) int32
    stamp: jax.Array,  # (R, L) int32
    p: jax.Array,  # (R,) float32
    ctr: jax.Array,  # (R,) int32
    cap: jax.Array,  # (R,) int32 per-row capacity c
    x: jax.Array,  # (R,) int32 accessed block
    iota: jax.Array,  # (1, L) int32
    lanes: int,
) -> Tuple[jax.Array, ...]:
    """One ARC access, vectorized over rows; mirrors ``policies.ARC.access``
    decision-for-decision (float32 p, int truncation, LRU-by-min-stamp)."""
    xcol = x[:, None]
    present = (blocks == xcol) & (tag != _FREE)
    tag_x = jnp.max(jnp.where(present, tag, 0), axis=-1)  # 0 when absent
    counts = _list_counts(tag)
    n1, n2, n3, n4 = counts[0], counts[1], counts[2], counts[3]
    hit = (tag_x == _TAG_T1) | (tag_x == _TAG_T2)
    in_b1 = tag_x == _TAG_B1
    in_b2 = tag_x == _TAG_B2
    miss_new = tag_x == 0

    # ghost-hit adaptation (host updates p BEFORE _replace; B1/B2 still
    # contain x here) — float32, op order identical to the host oracle
    one = jnp.float32(1.0)
    capf = cap.astype(jnp.float32)
    n3f, n4f = n3.astype(jnp.float32), n4.astype(jnp.float32)
    step = jnp.maximum(_ghost_ratio(in_b1, n3f, n4f), one)
    p_inc = jnp.minimum(capf, p + step)
    p_dec = jnp.maximum(jnp.float32(0.0), p - step)
    p_new = jnp.where(in_b1, p_inc, jnp.where(in_b2, p_dec, p))

    # complete-miss directory maintenance + REPLACE trigger
    l1 = n1 + n3
    total = n1 + n2 + n3 + n4
    cm1a = miss_new & (l1 == cap) & (n1 < cap)  # pop B1 LRU, then replace
    cm1b = miss_new & (l1 == cap) & (n1 == cap)  # discard T1 LRU outright
    cm2 = miss_new & (l1 != cap)
    do_repl = in_b1 | in_b2 | cm1a | (cm2 & (total >= cap))
    pop_b2 = cm2 & (total == 2 * cap)

    # the three pop targets are mutually exclusive per row, so one keyed
    # head reduction covers them (-1 = no pop this access)
    pop_want = jnp.where(
        cm1a, _TAG_B1, jnp.where(pop_b2, _TAG_B2, jnp.where(cm1b, _TAG_T1, -1))
    )
    pop = _keyed_head(tag, stamp, pop_want)
    new_tag = jnp.where(pop, _FREE, tag)
    new_blocks = jnp.where(pop, -1, blocks)

    # REPLACE: demote T1's LRU to B1 iff T1 nonempty and (|T1| > int(p), or
    # x in B2 with |T1| == int(p)); else demote T2's LRU to B2.  The demoted
    # page is restamped — ghost lists append at their MRU end.  (Computed on
    # the pre-pop planes: pops touch B1/B2/T1-discard lanes, never a
    # replace's T1/T2 head — T1-discard rows don't replace.)
    ip = p_new.astype(jnp.int32)
    cond_t1 = (n1 >= 1) & ((in_b2 & (n1 == ip)) | (n1 > ip))
    dem_t1 = do_repl & cond_t1
    dem_t2 = do_repl & ~cond_t1 & (n2 >= 1)
    dem_want = jnp.where(dem_t1, _TAG_T1, jnp.where(dem_t2, _TAG_T2, -1))
    dem = _keyed_head(tag, stamp, dem_want)
    stamp_dem = (ctr + 1)[:, None]
    stamp_x = (ctr + 2)[:, None]
    new_tag = jnp.where(dem, jnp.where(dem_t1, _TAG_B1, _TAG_B2)[:, None], new_tag)
    new_stamp = jnp.where(dem, stamp_dem, stamp)

    # x's own transition: T1-hit and ghost hits land at T2's MRU; a T2 hit
    # restamps in place (move_to_end)
    to_t2 = (tag_x == _TAG_T1) | in_b1 | in_b2
    new_tag = jnp.where(present & to_t2[:, None], _TAG_T2, new_tag)
    new_stamp = jnp.where(
        present & (hit | in_b1 | in_b2)[:, None], stamp_x, new_stamp
    )

    # complete miss: insert at T1's MRU in the first free lane (post-pop)
    free = new_tag == _FREE
    ins = jnp.min(jnp.where(free, iota, lanes), axis=-1)
    ins_oh = (iota == ins[:, None]) & miss_new[:, None]
    new_tag = jnp.where(ins_oh, _TAG_T1, new_tag)
    new_blocks = jnp.where(ins_oh, xcol, new_blocks)
    new_stamp = jnp.where(ins_oh, stamp_x, new_stamp)
    return new_blocks, new_tag, new_stamp, p_new, ctr + 2, hit


def _car_step(
    blocks: jax.Array,  # (R, L) int32
    tag: jax.Array,
    stamp: jax.Array,
    ref: jax.Array,
    p: jax.Array,  # (R,) float32
    ctr: jax.Array,  # (R,) int32
    cap: jax.Array,  # (R,) int32
    x: jax.Array,  # (R,) int32
    iota: jax.Array,  # (1, L)
    lanes: int,
    max_iters: int,  # static bound on the clock-hand sweep: max_ways + 1
) -> Tuple[jax.Array, ...]:
    """One CAR access, vectorized over rows; mirrors ``policies.CAR.access``.
    The clock-hand sweep runs as a masked ``lax.while_loop`` — each iteration
    either promotes T1's head to T2's tail, rotates T2's head (clearing its
    reference bit), or evicts to a ghost list and retires the row."""
    xcol = x[:, None]
    present = (blocks == xcol) & (tag != _FREE)
    tag_x = jnp.max(jnp.where(present, tag, 0), axis=-1)
    hit = (tag_x == _TAG_T1) | (tag_x == _TAG_T2)
    in_b1 = tag_x == _TAG_B1
    in_b2 = tag_x == _TAG_B2
    miss_new = tag_x == 0
    resident = jnp.sum((tag == _TAG_T1) | (tag == _TAG_T2), axis=-1)
    full = resident == cap

    # cache hit: set the reference bit; nothing else moves
    ref = jnp.where(present & hit[:, None], 1, ref)

    # REPLACE (only when the cache is full): bounded clock-hand sweep
    need = ~hit & full
    ip = jnp.maximum(1, p.astype(jnp.int32))  # host: max(1, int(p))

    def sweep_cond(carry):
        i, _, _, _, _, live = carry
        return (i < max_iters) & jnp.any(live)

    def sweep_body(carry):
        i, tag_c, stamp_c, ref_c, ctr_c, live = carry
        n1c = jnp.sum(tag_c == _TAG_T1, axis=-1)
        use_t1 = n1c >= ip  # T1 hand while |T1| >= max(1, int(p))
        want = jnp.where(live, jnp.where(use_t1, _TAG_T1, _TAG_T2), -1)
        head = _keyed_head(tag_c, stamp_c, want)
        head_ref = jnp.max(jnp.where(head, ref_c, 0), axis=-1)
        evict = live & (head_ref == 0)
        snew = (ctr_c + 1)[:, None]
        # ref==0 head: evict to the matching ghost list (restamp = MRU
        # append); ref==1 T1 head: promote to T2 tail; ref==1 T2 head:
        # rotate to tail.  All three clear the ref bit and restamp.
        tag_c = jnp.where(
            head & (evict & use_t1)[:, None],
            _TAG_B1,
            jnp.where(
                head & (evict & ~use_t1)[:, None],
                _TAG_B2,
                jnp.where(head & (~evict & use_t1)[:, None], _TAG_T2, tag_c),
            ),
        )
        ref_c = jnp.where(head, 0, ref_c)
        stamp_c = jnp.where(head, snew, stamp_c)
        ctr_c = jnp.where(live, ctr_c + 1, ctr_c)
        return (i + 1, tag_c, stamp_c, ref_c, ctr_c, live & ~evict)

    _, tag, stamp, ref, ctr, _ = jax.lax.while_loop(
        sweep_cond, sweep_body, (jnp.int32(0), tag, stamp, ref, ctr, need)
    )

    # post-replace list lengths (x still resident in its ghost list)
    counts_p = _list_counts(tag)
    n1p, n2p, n3p, n4p = counts_p[0], counts_p[1], counts_p[2], counts_p[3]

    # complete-miss directory discards (host order: only when full, after
    # the sweep, before the insert; the two pops are mutually exclusive)
    dir_guard = miss_new & full
    popb1 = dir_guard & (n1p + n3p == cap + 1)
    popb2 = dir_guard & (n1p + n3p != cap + 1) & (n1p + n2p + n3p + n4p >= 2 * cap)
    pop = _keyed_head(
        tag, stamp, jnp.where(popb1, _TAG_B1, jnp.where(popb2, _TAG_B2, -1))
    )
    tag = jnp.where(pop, _FREE, tag)
    blocks = jnp.where(pop, -1, blocks)

    # ghost-hit adaptation (host updates p AFTER _replace, from post-sweep
    # lengths) — float32, op order identical to the host oracle
    one = jnp.float32(1.0)
    capf = cap.astype(jnp.float32)
    n3f, n4f = n3p.astype(jnp.float32), n4p.astype(jnp.float32)
    step = jnp.maximum(one, _ghost_ratio(in_b1, n3f, n4f))
    p_inc = jnp.minimum(capf, p + step)
    p_dec = jnp.maximum(jnp.float32(0.0), p - step)
    p = jnp.where(in_b1, p_inc, jnp.where(in_b2, p_dec, p))

    stamp_x = (ctr + 1)[:, None]
    # ghost hit: re-enter at T2's tail with ref bit 0
    ghost = in_b1 | in_b2
    tag = jnp.where(present & ghost[:, None], _TAG_T2, tag)
    stamp = jnp.where(present & ghost[:, None], stamp_x, stamp)
    ref = jnp.where(present & ghost[:, None], 0, ref)
    # complete miss: insert at T1's tail in the first free lane
    free = tag == _FREE
    ins = jnp.min(jnp.where(free, iota, lanes), axis=-1)
    ins_oh = (iota == ins[:, None]) & miss_new[:, None]
    tag = jnp.where(ins_oh, _TAG_T1, tag)
    blocks = jnp.where(ins_oh, xcol, blocks)
    stamp = jnp.where(ins_oh, stamp_x, stamp)
    ref = jnp.where(ins_oh, 0, ref)
    ctr = jnp.where(hit, ctr, ctr + 1)
    return blocks, tag, stamp, ref, p, ctr, hit


# ---------------------------------------------------------------------------
# stamp renormalization
# ---------------------------------------------------------------------------


def _renorm_stamps(state: AdaptiveState, renorm_at: int) -> AdaptiveState:
    """Compact stamps when ``ctr`` nears the int32 range: dense-rank each
    row-set's stamp plane (rank = #lanes with a strictly smaller stamp) and
    reset ``ctr`` to L.  Occupied lanes carry unique stamps (every grant is
    one-hot per row-set), so ranking preserves every within-list order and
    therefore every future decision bit-for-bit; free lanes' stamps are
    never compared (``_keyed_head`` masks on tag).  The O(L^2) rank compare
    runs under ``lax.cond`` — rows pay nothing until a renormalization
    actually triggers (every ~2^31/(ways+2) accesses)."""
    need = state.ctr >= renorm_at  # (B, S) bool

    def do(st: AdaptiveState) -> AdaptiveState:
        s = st.stamp  # (B, S, L)
        L = s.shape[-1]
        rank = jnp.sum(
            s[..., :, None] > s[..., None, :], axis=-1, dtype=jnp.int32
        )
        return st._replace(
            stamp=jnp.where(need[..., None], rank, s),
            ctr=jnp.where(need, jnp.int32(L), st.ctr),
        )

    return jax.lax.cond(jnp.any(need), do, lambda st: st, state)


# ---------------------------------------------------------------------------
# the PolicyState cores
# ---------------------------------------------------------------------------


class RowCounters(NamedTuple):
    """Per-row cumulative accounting — ``(rows,)`` device arrays.

    Carried OUTSIDE the policy state pytrees on purpose: `FlatState` /
    `AdaptiveState` layouts are scan carries in the sweep engine and the
    paged-KV pool, and growing them would change every consumer's pytree
    structure (and its XLA in-place-carry behaviour).  Accounting callers —
    the tenancy manager, benchmarks — thread a `RowCounters` alongside the
    state through ``on_access_counted``.

    ``pressure`` is the admission plane (DESIGN.md §9): a per-row EWMA of
    evictions-per-access, updated in the same jitted step as the access
    itself so the admission signal never lags the state it describes.  It
    is the single source of truth — host mirrors are pulled copies, never
    recomputed (XLA's FMA contraction makes a host float32 replay of the
    same recurrence diverge by ~1 ulp within a handful of steps)."""

    hits: jax.Array  # (rows,) int32
    misses: jax.Array  # (rows,) int32
    evictions: jax.Array  # (rows,) int32
    pressure: jax.Array  # (rows,) float32 EWMA of evictions/access


class _Accounting:
    """Per-row accounting shared by both core layouts (DESIGN.md §8).

    An eviction is detected structurally, not policy-by-policy: a miss
    inserts exactly one resident, so the count of residents displaced is
    ``occupancy_before + 1 - occupancy_after`` (0 when the insert landed in
    a free lane, 1 when a resident was overwritten / demoted to a ghost
    list — including ARC's discard-T1-outright and ghost-hit REPLACE
    paths).  This holds for every device policy because none of them evicts
    on a hit and every miss inserts."""

    def init_counters(self, *, mesh=None) -> RowCounters:
        """Fresh all-zero counters for this core's ``rows`` (device arrays);
        pure — allocates new arrays, mutates nothing.  ``mesh`` places the
        rows axis across a ``core.sharding`` rows mesh (rows must divide the
        device count), matching a state built with ``init(mesh=...)``."""
        z = jnp.zeros((self.rows,), dtype=jnp.int32)
        p = jnp.zeros((self.rows,), dtype=jnp.float32)
        counters = RowCounters(hits=z, misses=z, evictions=z, pressure=p)
        return sharding.shard_rows(self, counters, mesh)

    def on_access_counted(
        self,
        state: "PolicyState",
        counters: RowCounters,
        ids: jax.Array,
        *,
        active: jax.Array | None = None,
        pressure_alpha: float = 0.1,
        ring=None,
    ):
        """``on_access`` + per-row hit/miss/eviction accounting and the
        admission pressure EWMA.

        Active rows fold this access's eviction count into ``pressure`` as
        ``(1 - alpha) * p + alpha * evicted``; inactive rows keep their
        pressure (and all other counters) untouched.  Pure and jit-safe:
        returns new state/counters, mutates nothing.

        ``ring`` (an ``obs.decision_trace.DecisionRing``) opts into decision
        tracing: one KIND_ACCESS event per active row — hit flag, advisory
        victim lane, and the core's policy internals (AWRP victim weight for
        flat cores, ARC/CAR ``p`` before/after for adaptive cores) — is
        scattered into the ring and the call returns a 4-tuple
        ``(state, counters, hit, ring)``.  Tracing reads the pre/post states
        but feeds nothing back into them, so decisions are bit-identical
        with tracing on or off (tests/test_obs.py pins it)."""
        occ_b = self.occupancy(state)
        new_state, hit = self.on_access(state, ids, active=active)
        occ_a = self.occupancy(new_state)
        act = (
            jnp.ones((self.rows,), dtype=bool)
            if active is None
            else jnp.asarray(active, dtype=bool)
        )
        miss = act & ~hit
        evicted = jnp.where(miss, occ_b + 1 - occ_a, 0).astype(jnp.int32)
        a = jnp.float32(pressure_alpha)
        p_new = (1.0 - a) * counters.pressure + a * evicted.astype(jnp.float32)
        new_counters = RowCounters(
            hits=counters.hits + hit.astype(jnp.int32),
            misses=counters.misses + miss.astype(jnp.int32),
            evictions=counters.evictions + evicted,
            pressure=jnp.where(act, p_new, counters.pressure),
        )
        if ring is None:
            return new_state, new_counters, hit
        from repro.obs import decision_trace as dt

        cols = self._trace_cols(state, new_state)
        events = dt.pack_events(
            self.rows,
            kind=dt.KIND_ACCESS,
            row=jnp.arange(self.rows, dtype=jnp.int32),
            key=jnp.asarray(ids, dtype=jnp.int32),
            hit=hit.astype(jnp.int32),
            set_id=0,
            **cols,
        )
        return new_state, new_counters, hit, dt.ring_push(ring, events, act)

    def row_telemetry(
        self, state: "PolicyState", counters: RowCounters
    ) -> Dict[str, jax.Array]:
        """Per-row accounting as ``(rows,)`` device arrays — the uniform
        record the tenancy layer (and any batched consumer) reports from:
        cumulative hits/misses/evictions, current occupancy, and the static
        per-row capacity."""
        return {
            "hits": counters.hits,
            "misses": counters.misses,
            "evictions": counters.evictions,
            "accesses": counters.hits + counters.misses,
            "occupancy": self.occupancy(state),
            "capacity": jnp.asarray(self.row_capacity, dtype=jnp.int32),
            "pressure": counters.pressure,
        }


#: admission decision codes — the device encoding of the host controller's
#: ``"accept"/"defer"/"shed"`` strings.  Stable int32 values: they appear in
#: jitted programs and in the serve-loop bench's recorded decisions.
ADMIT_ACCEPT = 0
ADMIT_DEFER = 1
ADMIT_SHED = 2


def admission_decide(
    pressure: jax.Array,
    accesses: jax.Array,
    *,
    defer_at: float,
    shed_at: float,
    warmup: int,
) -> jax.Array:
    """Pure device admission decision over per-row planes (DESIGN.md §9).

    Mirrors ``AdmissionController.decide`` exactly: rows still inside the
    warmup window (``accesses < warmup``) always ACCEPT; otherwise SHED when
    ``pressure >= shed_at``, DEFER when ``pressure >= defer_at``, else
    ACCEPT.  Comparisons run on the device float32 pressure plane, so host
    and device agree bit-for-bit when the host reads a pulled mirror.

    Args:
      pressure: ``(rows,)`` float32 eviction-rate EWMA
        (``RowCounters.pressure``).
      accesses: ``(rows,)`` int32 cumulative accesses (hits + misses).
      defer_at/shed_at/warmup: static thresholds (baked into the jitted
        program).

    Returns:
      ``(rows,)`` int32 of ``ADMIT_ACCEPT`` / ``ADMIT_DEFER`` /
      ``ADMIT_SHED``.  Pure and jit-safe."""
    code = jnp.where(
        pressure >= jnp.float32(shed_at),
        jnp.int32(ADMIT_SHED),
        jnp.where(
            pressure >= jnp.float32(defer_at),
            jnp.int32(ADMIT_DEFER),
            jnp.int32(ADMIT_ACCEPT),
        ),
    )
    return jnp.where(accesses < jnp.int32(warmup), jnp.int32(ADMIT_ACCEPT), code)


def admission_decay(
    pressure: jax.Array, mask: jax.Array, alpha: float
) -> jax.Array:
    """Probation decay after a shed: rows where ``mask`` is True scale their
    pressure by ``1 - alpha`` (the same fold a zero-eviction access would
    apply), so a shed tenant re-enters service after sustained calm instead
    of being locked out at its peak EWMA.  Pure and jit-safe; rows outside
    ``mask`` are untouched."""
    a = jnp.float32(alpha)
    return jnp.where(
        jnp.asarray(mask, dtype=bool), pressure * (1.0 - a), pressure
    )


def _select_state(active, new_state, old_state):
    """Row-masked pytree select: rows where ``active`` is False keep their
    old state (used for the serving callers' masked no-op accesses)."""

    def pick(new, old):
        a = active.reshape(active.shape + (1,) * (new.ndim - active.ndim))
        return jnp.where(a, new, old)

    return jax.tree.map(pick, new_state, old_state)


@dataclasses.dataclass(frozen=True)
class FlatCore(_Accounting):
    """Static spec for a batch of flat-state policy rows (awrp/lru/fifo/lfu).

    ``pids``/``ways`` are per-row: mixed policies and mixed capacities batch
    together (smaller rows get dead padding lanes masked out of both fill
    and eviction).  ``lanes`` pads the ways axis (kernel alignment / batch
    uniformity); ``use_kernel`` routes AWRP victim selection through the
    Pallas rows kernel."""

    pids: Tuple[int, ...]  # per-row POLICY_IDS values
    ways: Tuple[int, ...]  # per-row live lanes per set
    num_sets: int = 1
    lanes: Optional[int] = None  # padded ways axis; default max(ways)
    use_kernel: bool = False

    def __post_init__(self):
        bad = [p for p in self.pids if p not in _SIMPLE_IDS]
        if bad:
            raise ValueError(
                f"FlatCore supports {JAX_POLICIES}; got policy ids {bad} "
                f"(adaptive policies run on AdaptiveCore)"
            )
        if self.lanes is not None and self.lanes < max(self.ways):
            raise ValueError(f"lanes {self.lanes} < max ways {max(self.ways)}")

    @property
    def rows(self) -> int:
        """Number of independent policy rows (the free batch axis)."""
        return len(self.pids)

    @property
    def W(self) -> int:
        """Padded lane count of the ways axis (``lanes`` or max(ways))."""
        return self.lanes if self.lanes is not None else max(self.ways)

    @property
    def row_capacity(self) -> Tuple[int, ...]:
        """Total resident capacity per row (= ways summed over sets)."""
        return tuple(w * self.num_sets for w in self.ways)

    def _masks(self) -> _GridMasks:
        return _make_masks(np.asarray(self.pids), np.asarray(self.ways), self.W)

    def occupancy(self, state: FlatState) -> jax.Array:
        """(rows,) int32 resident-block count (dead padding lanes excluded —
        they never hold blocks from `on_access`, but quota shrinks performed
        by the tenancy layer rewrite planes directly, so mask anyway)."""
        live = ~self._masks().dead  # (B, W)
        occ = state.blocks >= 0
        if self.num_sets == 1:
            return jnp.sum(occ & live, axis=-1, dtype=jnp.int32)
        return jnp.sum(occ & live[:, None, :], axis=(-2, -1), dtype=jnp.int32)

    def init(self, *, mesh=None) -> FlatState:
        """Fresh empty ``FlatState`` for this spec (pure; new arrays).
        ``mesh`` places the rows axis across a ``core.sharding`` rows mesh
        (rows must divide the device count; see ``sharding.pad_rows_to``)."""
        B, S, W = self.rows, self.num_sets, self.W
        shape = (B, W) if S == 1 else (B, S, W)
        state = FlatState(
            blocks=jnp.full(shape, -1, dtype=jnp.int32),
            f=jnp.zeros(shape, dtype=jnp.int32),
            r=jnp.zeros(shape, dtype=jnp.int32),
            clock=jnp.zeros(shape[:-1], dtype=jnp.int32),
        )
        return sharding.shard_rows(self, state, mesh)

    def on_access(
        self,
        state: FlatState,
        ids: jax.Array,
        *,
        active: jax.Array | None = None,
        masks: _GridMasks | None = None,
    ) -> Tuple[FlatState, jax.Array]:
        """One access per row.  ``ids`` (rows,) int32 block ids; ``active``
        optionally masks rows to no-ops.  Decisions are bit-identical to the
        host oracles (the parity suites are the contract).

        ``masks`` overrides the spec-derived per-row constants; sharded
        callers (``jax_policies`` under a rows mesh) pass each device's
        slice of the grid masks so the step stays shard-local — the spec's
        own ``pids``/``ways`` then only fix the shard's row count/layout."""
        ids = jnp.asarray(ids, dtype=jnp.int32)
        if masks is None:
            masks = self._masks()
        bidx = jnp.arange(self.rows)
        if self.num_sets == 1:
            # single-set layout: (B, W) planes, no sets axis (see FlatState)
            clk = state.clock + 1
            slot, is_hit, new_f, new_r = _row_step(
                state.blocks, state.f, state.r, clk, ids, masks,
                self.use_kernel,
            )
            new_state = FlatState(
                blocks=state.blocks.at[bidx, slot].set(ids),
                f=state.f.at[bidx, slot].set(new_f),
                r=state.r.at[bidx, slot].set(new_r),
                clock=clk,
            )
        else:
            sid = ids % self.num_sets
            clk = state.clock[bidx, sid] + 1
            slot, is_hit, new_f, new_r = _row_step(
                state.blocks[bidx, sid],
                state.f[bidx, sid],
                state.r[bidx, sid],
                clk,
                ids,
                masks,
                self.use_kernel,
            )
            new_state = FlatState(
                blocks=state.blocks.at[bidx, sid, slot].set(ids),
                f=state.f.at[bidx, sid, slot].set(new_f),
                r=state.r.at[bidx, sid, slot].set(new_r),
                clock=state.clock.at[bidx, sid].set(clk),
            )
        if active is not None:
            new_state = _select_state(active, new_state, state)
            is_hit = is_hit & active
        return new_state, is_hit

    def victim(self, state: FlatState) -> jax.Array:
        """Advisory victim lanes — ``(rows,)`` for single-set cores,
        ``(rows, num_sets)`` otherwise: the lane each set would evict (or
        fill) if the next access — at clock N+1, as the decision is always
        made — were a miss."""
        if self.num_sets == 1:
            masks = self._masks()
            return _flat_victim(
                state.f, state.r, state.clock + 1, masks, self.use_kernel
            )
        B, S, W = state.blocks.shape
        rep = np.repeat(np.arange(B), S)
        masks = _make_masks(
            np.asarray(self.pids)[rep], np.asarray(self.ways)[rep], W
        )
        v = _flat_victim(
            state.f.reshape(B * S, W),
            state.r.reshape(B * S, W),
            (state.clock + 1).reshape(B * S),
            masks,
            self.use_kernel,
        )
        return v.reshape(B, S)

    def _trace_cols(
        self, state: FlatState, new_state: FlatState
    ) -> Dict[str, jax.Array]:
        """Decision-trace fields for flat cores (single-set layout): the
        pre-access advisory victim lane and its AWRP weight at the decision
        clock N+1 (meaningful for awrp rows; informational for the rest)."""
        if self.num_sets != 1:
            raise NotImplementedError(
                "decision tracing covers the single-set serving layout"
            )
        victim = self.victim(state)
        bidx = jnp.arange(self.rows)
        w = awrp_weights(
            state.f[bidx, victim], state.r[bidx, victim], state.clock + 1
        )
        return {"victim": victim, "weight": w}


@dataclasses.dataclass(frozen=True)
class AdaptiveCore(_Accounting):
    """Static spec for a batch of adaptive (arc/car) policy rows.

    ``caps`` is the per-row per-set capacity c; the directory spans
    ``lanes = 2*max(caps)`` lanes (cache + ghosts).  ``renorm_at`` is the
    stamp-counter ceiling that triggers in-place stamp renormalization
    (None disables the check entirely — a static guarantee the caller makes
    when the access count is bounded, e.g. a known-length sweep trace)."""

    kind: str  # "arc" | "car"
    caps: Tuple[int, ...]  # per-row per-set capacity
    num_sets: int = 1
    lanes: Optional[int] = None  # padded directory lanes; default 2*max(caps)
    renorm_at: Optional[int] = "auto"  # type: ignore[assignment]

    def __post_init__(self):
        if self.kind not in ADAPTIVE_POLICIES:
            raise ValueError(
                f"AdaptiveCore supports {ADAPTIVE_POLICIES}, got {self.kind!r}"
            )
        if self.renorm_at == "auto":
            object.__setattr__(self, "renorm_at", self.default_renorm_at())
        if self.lanes is not None and self.lanes < 2 * max(self.caps):
            raise ValueError(f"lanes {self.lanes} < 2*max caps {2 * max(self.caps)}")

    def default_renorm_at(self) -> int:
        """Ceiling with headroom for several accesses' worth of stamp grants
        (at most ``max_ways + 2`` per access) between checks."""
        return INT_MAX - 8 * (max(self.caps) + 4)

    @property
    def rows(self) -> int:
        """Number of independent policy rows (the free batch axis)."""
        return len(self.caps)

    @property
    def L(self) -> int:
        """Lane count of the tag/stamp/ref planes: 2*max(caps) — residents
        plus ghosts."""
        return self.lanes if self.lanes is not None else 2 * max(self.caps)

    def init(self, *, mesh=None) -> AdaptiveState:
        """Fresh empty ``AdaptiveState`` for this spec (pure; new arrays).
        ``mesh`` places the rows axis across a ``core.sharding`` rows mesh
        (rows must divide the device count; see ``sharding.pad_rows_to``)."""
        state = init_adaptive_state(self.rows, self.num_sets, self.L)
        return sharding.shard_rows(self, state, mesh)

    def on_access(
        self,
        state: AdaptiveState,
        ids: jax.Array,
        *,
        active: jax.Array | None = None,
        caps: jax.Array | None = None,
    ) -> Tuple[AdaptiveState, jax.Array]:
        """One ARC/CAR access per row; mirrors the host oracles decision-for-
        decision (float32 p, int truncation, LRU/clock-hand by min-stamp).
        Stamps renormalize automatically when ``ctr`` nears int32 range.

        ``caps`` overrides the spec's per-row capacities with a ``(rows,)``
        runtime array; sharded callers pass each device's slice so the step
        stays shard-local (the spec's static ``caps`` then only fix the
        shard's row count and lane padding)."""
        ids = jnp.asarray(ids, dtype=jnp.int32)
        if self.renorm_at is not None:
            state = _renorm_stamps(state, self.renorm_at)
        L = self.L
        iota_l = jnp.arange(L, dtype=jnp.int32)[None, :]
        cap = (
            jnp.asarray(self.caps, dtype=jnp.int32)
            if caps is None
            else jnp.asarray(caps, dtype=jnp.int32)
        )
        if self.num_sets == 1:
            # single-set fast path: cheap squeeze/expand instead of the
            # gather/scatter (the scan body is dispatch-bound on CPU)
            get = lambda a: a[:, 0]  # noqa: E731
            put = lambda a, new: new[:, None]  # noqa: E731
        else:
            rows = jnp.arange(self.rows)
            sid = ids % self.num_sets
            get = lambda a: a[rows, sid]  # noqa: E731
            put = lambda a, new: a.at[rows, sid].set(new)  # noqa: E731
        blocks, tag, stamp = get(state.blocks), get(state.tag), get(state.stamp)
        p, ctr = get(state.p), get(state.ctr)
        if self.kind == "arc":
            blocks, tag, stamp, p, ctr, hit = _arc_step(
                blocks, tag, stamp, p, ctr, cap, ids, iota_l, L
            )
            ref = state.ref
        else:
            max_iters = max(self.caps) + 1
            blocks, tag, stamp, new_ref, p, ctr, hit = _car_step(
                blocks, tag, stamp, get(state.ref), p, ctr, cap, ids,
                iota_l, L, max_iters,
            )
            ref = put(state.ref, new_ref)
        new_state = AdaptiveState(
            blocks=put(state.blocks, blocks),
            tag=put(state.tag, tag),
            stamp=put(state.stamp, stamp),
            ref=ref,
            p=put(state.p, p),
            ctr=put(state.ctr, ctr),
        )
        if active is not None:
            new_state = _select_state(active, new_state, state)
            hit = hit & active
        return new_state, hit

    def victim(self, state: AdaptiveState) -> jax.Array:
        """Advisory ``(rows, 1)`` victim lanes: the lane whose page the
        policy would move out of the cache (into its ghost list) if the next
        access were a complete miss; -1 where no eviction would occur (cache
        not yet full).  Computed by probing ``on_access`` with a never-seen
        block id and diffing residency — the probe state is discarded."""
        if self.num_sets != 1:
            raise NotImplementedError(
                "AdaptiveCore.victim probes one access; with num_sets > 1 "
                "issue the probe per set via on_access instead"
            )
        probe = jnp.full((self.rows,), INT_MAX, dtype=jnp.int32)
        probed, _ = self.on_access(state, probe)
        res_b = (state.tag == _TAG_T1) | (state.tag == _TAG_T2)  # (B, 1, L)
        res_a = (probed.tag == _TAG_T1) | (probed.tag == _TAG_T2)
        # the probe's own insertion lane is new, never previously resident
        ev = res_b & ~res_a
        L = self.L
        iota = jnp.arange(L, dtype=jnp.int32)
        lane = jnp.min(jnp.where(ev, iota, L), axis=-1)
        return jnp.where(lane < L, lane, -1).astype(jnp.int32)

    def _trace_cols(
        self, state: AdaptiveState, new_state: AdaptiveState
    ) -> Dict[str, jax.Array]:
        """Decision-trace fields for adaptive cores: the pre-access advisory
        victim lane (-1 while the cache is filling) and the adaptation
        target ``p`` before/after the access — the live view of ARC/CAR's
        learning signal."""
        victim = self.victim(state)
        return {
            "victim": victim[:, 0] if victim.ndim == 2 else victim,
            "p_before": state.p[:, 0],
            "p_after": new_state.p[:, 0],
        }

    def resident_mask(self, state: AdaptiveState) -> jax.Array:
        """(rows, num_sets, L) bool — lanes whose block is cache-resident
        (T1 or T2; ghost-directory entries are NOT resident)."""
        return (state.tag == _TAG_T1) | (state.tag == _TAG_T2)

    @property
    def row_capacity(self) -> Tuple[int, ...]:
        """Total resident capacity per row (= caps summed over sets)."""
        return tuple(c * self.num_sets for c in self.caps)

    def occupancy(self, state: AdaptiveState) -> jax.Array:
        """(rows,) int32 resident-page count (ghost entries excluded)."""
        return jnp.sum(self.resident_mask(state), axis=(-2, -1), dtype=jnp.int32)


PolicyCore = Union[FlatCore, AdaptiveCore]


# ---------------------------------------------------------------------------
# factories
# ---------------------------------------------------------------------------


def make_core(
    policy: str,
    rows: int = 1,
    num_sets: int = 1,
    ways: int = 1,
    *,
    use_kernel: bool = False,
    renorm_at: Optional[int] = "auto",  # type: ignore[assignment]
) -> PolicyCore:
    """Uniform-policy core factory: ``rows`` independent instances of one
    device policy, each ``num_sets`` sets of ``ways`` lanes.  Mixed-policy /
    mixed-capacity batches (the sweep engine's grid) construct ``FlatCore``
    / ``AdaptiveCore`` directly with per-row tuples."""
    if policy in JAX_POLICIES:
        return FlatCore(
            pids=(POLICY_IDS[policy],) * rows,
            ways=(int(ways),) * rows,
            num_sets=int(num_sets),
            use_kernel=use_kernel,
        )
    if policy in ADAPTIVE_POLICIES:
        return AdaptiveCore(
            kind=policy,
            caps=(int(ways),) * rows,
            num_sets=int(num_sets),
            renorm_at=renorm_at,
        )
    raise ValueError(f"not a device policy: {policy!r}; have {DEVICE_POLICIES}")


def init(
    policy: str, rows: int = 1, num_sets: int = 1, ways: int = 1,
    *, mesh=None, **kw
) -> Tuple[PolicyCore, PolicyState]:
    """Protocol entry point: build the core for ``policy`` and its initial
    state in one call — ``core, state = init(policy, rows, sets, ways)``.
    ``mesh`` (a ``core.sharding`` rows mesh) places the state's rows axis
    across devices; rows must divide the device count."""
    core = make_core(policy, rows, num_sets, ways, **kw)
    return core, core.init(mesh=mesh)


@functools.lru_cache(maxsize=None)
def _host_policy_registry():
    from repro.core.policies import POLICIES

    return POLICIES


def make_cache_policy(policy, capacity: int, **kw):
    """The serving-side factory: resolve ``policy`` — a name from
    ``repro.core.policies.POLICIES`` or an already-built instance — into a
    host ``ReplacementPolicy``.  Every host-side serving cache
    (``PrefixCache``, ``ExpertCacheRuntime``'s oracle path) routes through
    here so telemetry reports per-policy hit ratios from one code path."""
    from repro.core.policies import ReplacementPolicy, make_policy

    if isinstance(policy, ReplacementPolicy):
        if policy.capacity != int(capacity):
            raise ValueError(
                f"prebuilt policy has capacity {policy.capacity} but the "
                f"cache requested {capacity}"
            )
        return policy
    return make_policy(policy, capacity, **kw)
