"""Paged KV cache with AWRP eviction — the paper's technique as a
first-class, fully vectorized serving feature (DESIGN.md §2).

A bounded pool of P pages (page_size tokens each) per (layer, sequence).
Page metadata mirrors the paper exactly: frequency F_p, recency clock R_p,
global clock N; a page is *referenced* at a decode step when its attention
mass exceeds tau = 1/num_resident_pages; eviction on pool-full allocation is
``argmin W_p = F_p / (N - R_p)`` — eq. (1) verbatim, computed lazily at miss
(allocation) time only, exactly like the paper's lazy weight update.

All arrays carry leading (B,) — one policy instance per sequence — and the
model stacks a further (n_repeats,) layer dim scanned by lax.scan (one policy
instance per layer, since attention mass differs per layer).  The row planes
(``ROW_PLANES``) ride that scan's carry whole: the functions that write them
take ``layer``, the index of the layer's pool in the stack (``()`` for a pool
of one layer), and write only the new token's row, in place.

Two eviction modes (both policy-pluggable through the unified core,
DESIGN.md §7):

* **classic** (``insert_token``/``score_update``): stateless decisions over
  the (F, R, page_start) metadata via ``repro.core.kv_policy.page_victim``
  — awrp/lru/fifo/lfu exactly, arc/car as two-segment approximations.
* **true-adaptive** (``adaptive_insert_token``/``adaptive_score_update``):
  the pool carries ``policy_core.AdaptiveState`` planes per (B,) sequence —
  ghost directory, stamps and the self-tuning ``p`` — so eviction runs the
  REAL ARC/CAR, bit-identical to the host oracles and the sweep engine on
  the pool's access stream (page allocations are complete misses, per-step
  references are hits issued in slot order; parity-tested in
  tests/test_adaptive_kv.py).  Note the stream's structure: page ids only
  grow, so ghost *hits* cannot occur during decode — ``p`` stays put but
  the T1/T2 once-vs-multiply-referenced segmentation, LRU/clock-hand order
  and reference-bit promotion are live and exact.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

import numpy as np

from repro.core import sharding
from repro.core.policy_core import (
    _TAG_B1,
    _TAG_B2,
    _TAG_T1,
    _TAG_T2,
    AdaptiveCore,
    AdaptiveState,
    awrp_victim_rows,
    div_rn,
    first_min,
)

INT_MAX = 2**31 - 1

#: kv_policy names served by the true-adaptive pool mode -> core policy
TRUE_ADAPTIVE_KV = {"arc_adaptive": "arc", "car_adaptive": "car"}

#: the planes of a decode cache that hold one row per cached token: a pool's
#: or a contiguous cache's ``k``/``v``, a latent cache's ``c``.  A decode
#: step writes them by row, in place; every other plane is small and is
#: written whole.
ROW_PLANES = ("k", "v", "c")


def split_rows(tree):
    """``(rows, rest, how)``: the leaves of a cache tree that are row planes
    (``ROW_PLANES``), the other leaves, both in traversal order, and what
    ``merge_rows`` needs to put the tree back together."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(tree)
    # a NamedTuple field's key has a ``name``, a dict entry's a ``key``
    is_row = tuple(getattr(path[-1], "name", getattr(path[-1], "key", None))
                   in ROW_PLANES for path, _ in paths)
    rows = [x for (_, x), r in zip(paths, is_row) if r]
    rest = [x for (_, x), r in zip(paths, is_row) if not r]
    return rows, rest, (treedef, is_row)


def merge_rows(rows, rest, how):
    """The cache tree ``split_rows`` took apart, from its two lists."""
    treedef, is_row = how
    rows, rest = iter(rows), iter(rest)
    return treedef.unflatten([next(rows) if r else next(rest) for r in is_row])


def layer_rows(plane: jax.Array, layer: Tuple = ()) -> jax.Array:
    """(B, T, width): one layer's rows of a row plane, a pool's pages laid
    end to end (T = pages * page) or a contiguous cache's T rows."""
    rows = plane[layer]
    return rows.reshape(rows.shape[0], -1, rows.shape[-1])


def write_token(planes, rows, layer, need_alloc, slot, within):
    """Write each sequence's new token row into a pool's row planes, in
    place: ``rows[i]`` (B, width) goes to ``planes[i][*layer, b, slot[b],
    within]``.  On a page boundary (``need_alloc``) the allocated pages are
    zeroed first; between boundaries that write points past the pool and is
    dropped, so a step writes nothing but its rows."""
    bidx = jnp.arange(slot.shape[0])
    pages = planes[0].shape[len(layer) + 1]
    reset = (*layer, bidx, jnp.where(need_alloc, slot, pages))
    return tuple(p.at[reset].set(0, mode="drop")
                 .at[(*layer, bidx, slot, within)].set(row)
                 for p, row in zip(planes, rows))


class PagedPool(NamedTuple):
    """Per-layer-per-sequence bounded KV pool.  Inside ``decode_step``'s
    layer scan ``k`` and ``v`` are the whole layer stack (module
    docstring)."""

    k: jax.Array  # (B, P, page, kvd)
    v: jax.Array  # (B, P, page, kvd)
    f: jax.Array  # (B, P) int32 — paper's F_i
    r: jax.Array  # (B, P) int32 — paper's R_i
    page_start: jax.Array  # (B, P) int32 token index of page start; -1 free
    clock: jax.Array  # (B,) int32 — paper's N (one policy clock per sequence)
    open_slot: jax.Array  # (B,) int32 slot currently being written


def init_pool(
    batch: int, pages: int, page_size: int, kvd: int, dtype, *, mesh=None
) -> PagedPool:
    """Concrete all-zeros pool (all pages free: ``page_start == -1``).

    Pure constructor — allocates device arrays, mutates nothing.  The pool
    itself is an immutable NamedTuple pytree: every update function below
    returns a new pool, so it is safe to carry through jit/scan/donation.
    ``mesh`` (a ``core.sharding`` rows mesh) places the per-sequence batch
    axis across devices — every pool update is sequence-local, so a sharded
    pool decides identically to an unsharded one; ``batch`` must divide the
    device count."""
    pool = PagedPool(
        k=jnp.zeros((batch, pages, page_size, kvd), dtype),
        v=jnp.zeros((batch, pages, page_size, kvd), dtype),
        f=jnp.zeros((batch, pages), jnp.int32),
        r=jnp.zeros((batch, pages), jnp.int32),
        page_start=jnp.full((batch, pages), -1, jnp.int32),
        clock=jnp.zeros((batch,), jnp.int32),
        open_slot=jnp.zeros((batch,), jnp.int32),
    )
    return sharding.shard_rows(None, pool, mesh)


def abstract_pool(batch: int, pages: int, page_size: int, kvd: int, dtype):
    """``init_pool``'s shape/dtype skeleton (``jax.ShapeDtypeStruct`` leaves)
    for ``jax.eval_shape`` / AOT tracing — allocates no device memory."""
    sds = jax.ShapeDtypeStruct
    return PagedPool(
        k=sds((batch, pages, page_size, kvd), dtype),
        v=sds((batch, pages, page_size, kvd), dtype),
        f=sds((batch, pages), jnp.int32),
        r=sds((batch, pages), jnp.int32),
        page_start=sds((batch, pages), jnp.int32),
        clock=sds((batch,), jnp.int32),
        open_slot=sds((batch,), jnp.int32),
    )


def awrp_victim(
    f: jax.Array,  # (B, P) int32
    r: jax.Array,  # (B, P) int32
    clock: jax.Array,  # (B,) int32
    valid: jax.Array,  # (B, P) bool — resident pages
    pinned: jax.Array,  # (B, P) bool — excluded (the open page)
) -> jax.Array:
    """Vectorized eq. (1) victim select; same float32 ops / first-index
    tie-break as the host oracle (bit-exact, property-tested).  A core-level
    dispatch (``policy_core.awrp_victim_rows``): the bit-pattern
    min-reduction (w >= 0, so IEEE order == int32 bit order), not argmin."""
    return awrp_victim_rows(f, r, clock, valid & ~pinned)  # (B,)


def _allocate(pool, pos: jax.Array, page_size: int, policy: str):
    """The page-boundary step of a token write, shared by every classic
    pool: ``(within, need_alloc, slot, f, r, page_start)`` — the row in
    the page, whether ``pos`` opens a page, the slot written (on a
    boundary the first free slot, else ``policy``'s victim, never the open
    page), and the metadata with the allocated page reset (paper insert
    rule: F=1, R=N)."""
    from repro.core.kv_policy import page_victim

    B, P = pool.f.shape
    within = (pos % page_size).astype(jnp.int32)
    need_alloc = within == 0

    # --- allocation path (computed always, selected by need_alloc) ---------
    free = pool.page_start < 0  # (B, P)
    has_free = jnp.any(free, axis=-1)
    first_free = jnp.argmax(free, axis=-1).astype(jnp.int32)
    pinned = jax.nn.one_hot(pool.open_slot, P, dtype=bool)
    victim = page_victim(policy, pool.f, pool.r, pool.page_start, pool.clock,
                         pinned)
    alloc_slot = jnp.where(has_free, first_free, victim)  # (B,)
    slot = jnp.where(need_alloc, alloc_slot, pool.open_slot)  # (B,)

    bidx = jnp.arange(B)
    f = pool.f.at[bidx, slot].set(
        jnp.where(need_alloc, 1, pool.f[bidx, slot])
    )
    r = pool.r.at[bidx, slot].set(
        jnp.where(need_alloc, pool.clock, pool.r[bidx, slot])
    )
    page_start = pool.page_start.at[bidx, slot].set(
        jnp.where(need_alloc, pos, pool.page_start[bidx, slot])
    )
    return within, need_alloc, slot, f, r, page_start


def insert_token(
    pool: PagedPool,
    new_k: jax.Array,  # (B, kvd)
    new_v: jax.Array,  # (B, kvd)
    pos: jax.Array,  # scalar int32 — token index being written
    page_size: int,
    policy: str = "awrp",
    layer: Tuple = (),
) -> PagedPool:
    """Write one token row; on page-boundary allocate (evicting by ``policy``
    when the pool is full).  ``layer`` picks the pool's layer when its K/V
    planes are a layer stack (module docstring).  Runs under jit/scan."""
    within, need_alloc, slot, f, r, page_start = _allocate(
        pool, pos, page_size, policy)
    k, v = write_token((pool.k, pool.v), (new_k, new_v), layer, need_alloc,
                       slot, within)
    open_slot = jnp.where(need_alloc, slot, pool.open_slot).astype(jnp.int32)
    return PagedPool(k, v, f, r, page_start, pool.clock, open_slot)


class LatentPagedPool(NamedTuple):
    """A bounded pool whose page holds one latent plane: the row multi-head
    latent attention caches per token (``[c | k_pe]``, shared by every
    head) in place of per-head K and V.  The AWRP metadata and every
    function on it (``kv_positions``, ``score_update``, ``step_counts``)
    are ``PagedPool``'s."""

    c: jax.Array  # (B, P, page, width)
    f: jax.Array  # (B, P) int32
    r: jax.Array  # (B, P) int32
    page_start: jax.Array  # (B, P) int32; -1 free
    clock: jax.Array  # (B,) int32
    open_slot: jax.Array  # (B,) int32


def latent_pool(batch: int, pages: int, page_size: int, width: int, dtype,
                *, abstract: bool = False) -> LatentPagedPool:
    """An empty latent pool (every page free), or its ``ShapeDtypeStruct``
    skeleton with ``abstract``."""
    make = ((lambda s, dt, fill: jax.ShapeDtypeStruct(s, dt)) if abstract
            else (lambda s, dt, fill: jnp.full(s, fill, dt)))
    return LatentPagedPool(
        c=make((batch, pages, page_size, width), dtype, 0),
        f=make((batch, pages), jnp.int32, 0),
        r=make((batch, pages), jnp.int32, 0),
        page_start=make((batch, pages), jnp.int32, -1),
        clock=make((batch,), jnp.int32, 0),
        open_slot=make((batch,), jnp.int32, 0),
    )


def insert_latent(
    pool: LatentPagedPool,
    row: jax.Array,  # (B, width) the new token's latent row
    pos: jax.Array,  # scalar int32
    page_size: int,
    policy: str = "awrp",
    layer: Tuple = (),
) -> LatentPagedPool:
    """``insert_token`` for a latent pool: the same allocation and
    eviction (``_allocate``), one plane written."""
    within, need_alloc, slot, f, r, page_start = _allocate(
        pool, pos, page_size, policy)
    (c,) = write_token((pool.c,), (row,), layer, need_alloc, slot, within)
    open_slot = jnp.where(need_alloc, slot, pool.open_slot).astype(jnp.int32)
    return LatentPagedPool(c, f, r, page_start, pool.clock, open_slot)


def kv_positions(pool: PagedPool, pos: jax.Array, page_size: int) -> jax.Array:
    """(B, P*page) token index per cache row; -1 for invalid rows."""
    B, P = pool.f.shape
    row = jnp.arange(page_size, dtype=jnp.int32)
    tok = pool.page_start[..., None] + row[None, None]  # (B, P, page)
    valid = (pool.page_start[..., None] >= 0) & (tok <= pos)
    return jnp.where(valid, tok, -1).reshape(B, P * page_size)


def referenced_pages(
    pool: PagedPool,
    attn_mass: jax.Array,  # (B, P*page) softmax mass per cache row
    page_size: int,
) -> jax.Array:
    """Paper hit rule on pages: a resident page is *referenced* this decode
    step iff its attention mass >= tau = 1/resident_count.  The single
    definition both pool modes (classic F/R metadata and the true-adaptive
    policy stream) consume — returns a (B, P) bool mask."""
    B, P = pool.f.shape
    mass = attn_mass.reshape(B, P, page_size).sum(-1)  # (B, P)
    resident = (pool.page_start >= 0).sum(-1, keepdims=True)  # (B, 1)
    tau = div_rn(1.0, jnp.maximum(resident.astype(jnp.float32), 1.0))
    return (mass >= tau) & (pool.page_start >= 0)


def score_update(
    pool: PagedPool,
    attn_mass: jax.Array,  # (B, P*page) softmax mass per cache row
    page_size: int,
) -> PagedPool:
    """Apply the paper hit rule (``referenced_pages``): F += 1 and R = N on
    reference.  One clock tick per decode step."""
    referenced = referenced_pages(pool, attn_mass, page_size)
    clock = pool.clock + 1
    f = jnp.where(referenced, pool.f + 1, pool.f)
    r = jnp.where(referenced, clock[:, None], pool.r)
    return pool._replace(f=f, r=r, clock=clock)


def _pools(caches):
    """Every ``PagedPool`` in a cache tree (the ``pool`` of an adaptive one),
    in traversal order."""
    is_pool = lambda x: isinstance(  # noqa: E731
        x, (PagedPool, LatentPagedPool, AdaptivePagedPool))
    return [x.pool if isinstance(x, AdaptivePagedPool) else x
            for x in jax.tree.leaves(caches, is_leaf=is_pool) if is_pool(x)]


def step_counts(old_caches, new_caches) -> Tuple[jax.Array, jax.Array]:
    """``(hits, evictions)`` one decode step made, summed over every paged
    pool of a cache tree (layers and sequences): a page was referenced (the
    paper's hit rule) iff its R became the new clock, and evicted iff its
    slot was re-allocated while it was resident.  Exact int32 counts,
    ``(0, 0)`` for a tree with no paged pool."""
    hits = evictions = jnp.int32(0)
    for a, b in zip(_pools(old_caches), _pools(new_caches)):
        hits += jnp.sum((b.r == b.clock[..., None]) & (b.page_start >= 0),
                        dtype=jnp.int32)
        evictions += jnp.sum((b.page_start != a.page_start)
                             & (a.page_start >= 0), dtype=jnp.int32)
    return hits, evictions


# ---------------------------------------------------------------------------
# true-adaptive (ARC/CAR) pool mode — AdaptiveState planes per sequence
# ---------------------------------------------------------------------------


class AdaptivePagedPool(NamedTuple):
    """Paged pool + the unified core's adaptive policy planes: the ghost
    directory (2P lanes), within-list stamps and the self-tuning ``p`` that
    the classic pool's (F, R) metadata cannot carry.  The ``pool`` member's
    F/R/clock keep ticking for telemetry; eviction decisions come from
    ``policy`` via the REAL ARC/CAR step functions."""

    pool: PagedPool
    policy: AdaptiveState  # (B, 1, 2P) planes + (B, 1) scalars


def adaptive_core(kv_policy: str, batch: int, pages: int) -> AdaptiveCore:
    """The pool's policy core: one ARC/CAR instance per sequence, capacity =
    the page pool size.  ``kv_policy`` accepts the serving names
    (``arc_adaptive``/``car_adaptive``) or the core names (``arc``/``car``)."""
    kind = TRUE_ADAPTIVE_KV.get(kv_policy, kv_policy)
    return AdaptiveCore(kind=kind, caps=(pages,) * batch)


def init_adaptive_pool(
    batch: int, pages: int, page_size: int, kvd: int, dtype, kv_policy: str,
    *, mesh=None,
) -> AdaptivePagedPool:
    """Concrete empty pool + freshly initialised ARC/CAR planes.  Pure
    constructor; the result is an immutable pytree (see ``init_pool``).
    ``mesh`` batches the per-sequence adaptive pools across its devices —
    pool and policy planes shard on the same rows axis, so each device
    carries whole sequences (``batch`` must divide the device count) and
    decisions stay bit-identical to the unsharded pool."""
    return AdaptivePagedPool(
        pool=init_pool(batch, pages, page_size, kvd, dtype, mesh=mesh),
        policy=adaptive_core(kv_policy, batch, pages).init(mesh=mesh),
    )


def abstract_adaptive_pool(
    batch: int, pages: int, page_size: int, kvd: int, dtype, kv_policy: str
) -> AdaptivePagedPool:
    """``init_adaptive_pool``'s shape/dtype skeleton for ``jax.eval_shape``
    — no device allocation (see ``abstract_pool``)."""
    sds = jax.ShapeDtypeStruct
    L = 2 * pages
    return AdaptivePagedPool(
        pool=abstract_pool(batch, pages, page_size, kvd, dtype),
        policy=AdaptiveState(
            blocks=sds((batch, 1, L), jnp.int32),
            tag=sds((batch, 1, L), jnp.int32),
            stamp=sds((batch, 1, L), jnp.int32),
            ref=sds((batch, 1, L), jnp.int32),
            p=sds((batch, 1), jnp.float32),
            ctr=sds((batch, 1), jnp.int32),
        ),
    )


def seed_adaptive_state(
    batch: int, pages: int, first_page: int, n_res: int
) -> AdaptiveState:
    """Adaptive-policy counterpart of ``pool_from_prefill``'s seeding: the
    ``n_res`` resident pages (ids ``first_page..first_page+n_res-1``) enter
    as complete-miss insertions in order — all in T1, stamps in insertion
    order, ``p = 0``, empty ghost lists.  This is exactly the state the host
    ARC/CAR oracles reach on that access stream (the ctr value itself never
    affects decisions, only the stamp order does)."""
    L = 2 * pages
    lane = jnp.arange(L, dtype=jnp.int32)
    res = lane < n_res
    one_seq = lambda a: jnp.broadcast_to(a, (batch, 1, L))  # noqa: E731
    return AdaptiveState(
        blocks=one_seq(jnp.where(res, first_page + lane, -1)),
        tag=one_seq(jnp.where(res, _TAG_T1, 0)),
        stamp=one_seq(jnp.where(res, lane + 1, 0)),
        ref=jnp.zeros((batch, 1, L), jnp.int32),
        p=jnp.zeros((batch, 1), jnp.float32),
        ctr=jnp.full((batch, 1), n_res, jnp.int32),
    )


def pool_telemetry(state: AdaptiveState) -> Dict[str, jax.Array]:
    """Registry provider planes for a persisted true-adaptive KV policy
    state: the self-tuning ``p`` (mean/max over rows) and mean resident
    pages, as UN-pulled 0-d device arrays — the obs registry batches them
    into its single snapshot ``device_get`` (DESIGN.md §11).  Accepts
    tail-layer ``(B, 1, L)`` and stacked ``(n_rep, B, 1, L)`` planes
    alike."""
    resident = (state.tag == _TAG_T1) | (state.tag == _TAG_T2)
    return {
        "p_mean": jnp.mean(state.p),
        "p_max": jnp.max(state.p),
        "resident_mean": jnp.mean(jnp.sum(resident, axis=-1).astype(jnp.float32)),
    }


# ---------------------------------------------------------------------------
# ghost-hit feed: cross-request re-references for the true-adaptive pool
# ---------------------------------------------------------------------------
#
# Within one decode, page ids only grow, so ghost hits can never occur and
# ``p`` never moves (DESIGN.md §2 caveat).  The re-references that drive
# ARC/CAR's adaptation come from *across* requests: a prefix-cache miss that
# re-prefills a page position the previous request's pool had evicted is
# exactly a ghost hit.  ``replay_page_ids`` feeds such a re-prefill stream
# through a persisted ``AdaptiveState``; ``reseed_from_ghosts`` then rebuilds
# a pool-coherent seeded state that carries the adapted ``p`` and the
# surviving ghost directory into the new request (DESIGN.md §8).


def _flatten_adaptive(state: AdaptiveState):
    """Collapse a (possibly layer-stacked) state's leading dims to one rows
    axis: planes ``(..., S, L) -> (R, 1, L)``.  Only ``S == 1`` layouts (the
    serving pools') are supported."""
    lead = state.p.shape[:-1]
    if state.p.shape[-1] != 1:
        raise ValueError(f"expected single-set planes, got p shape {state.p.shape}")
    L = state.blocks.shape[-1]
    R = int(np.prod(lead)) if lead else 1
    flat = AdaptiveState(
        blocks=state.blocks.reshape(R, 1, L),
        tag=state.tag.reshape(R, 1, L),
        stamp=state.stamp.reshape(R, 1, L),
        ref=state.ref.reshape(R, 1, L),
        p=state.p.reshape(R, 1),
        ctr=state.ctr.reshape(R, 1),
    )
    return flat, lead, L


def _unflatten_adaptive(flat: AdaptiveState, lead, L: int) -> AdaptiveState:
    return AdaptiveState(
        blocks=flat.blocks.reshape(lead + (1, L)),
        tag=flat.tag.reshape(lead + (1, L)),
        stamp=flat.stamp.reshape(lead + (1, L)),
        ref=flat.ref.reshape(lead + (1, L)),
        p=flat.p.reshape(lead + (1,)),
        ctr=flat.ctr.reshape(lead + (1,)),
    )


def replay_page_ids(
    state: AdaptiveState, kind: str, pages: int, page_ids
) -> Tuple[AdaptiveState, jax.Array]:
    """Replay ``page_ids`` (in order) through a persisted adaptive state —
    one real ``on_access`` each, so ghost hits adapt ``p`` with the exact
    host-oracle arithmetic.  Works on tail-layer ``(B, 1, L)`` and stacked
    ``(n_rep, B, 1, L)`` planes alike.  Returns ``(new_state, ghost_hits)``
    with ghost_hits counted per row (leading dims preserved)."""
    flat, lead, L = _flatten_adaptive(state)
    R = flat.p.shape[0]
    core = AdaptiveCore(kind=TRUE_ADAPTIVE_KV.get(kind, kind), caps=(pages,) * R)

    def body(st, pid):
        ghost = jnp.any(
            (st.blocks[:, 0] == pid)
            & ((st.tag[:, 0] == _TAG_B1) | (st.tag[:, 0] == _TAG_B2)),
            axis=-1,
        )
        st, _ = core.on_access(st, jnp.full((R,), pid, dtype=jnp.int32))
        return st, ghost

    flat, ghosts = jax.lax.scan(
        body, flat, jnp.asarray(page_ids, dtype=jnp.int32)
    )
    gh = jnp.sum(ghosts, axis=0, dtype=jnp.int32)
    return _unflatten_adaptive(flat, lead, L), gh.reshape(lead)


def reseed_from_ghosts(
    prev: AdaptiveState, kind: str, pages: int, n_have: int, n_res: int
) -> Tuple[AdaptiveState, np.ndarray]:
    """Cross-request reseed of the true-adaptive pool policy: replay the
    re-prefill page stream (ids ``0..n_have-1``) through the previous
    request's final state — previously evicted pages ghost-hit and move
    ``p`` — then rebuild residency to match the freshly seeded pool (the
    last ``n_res`` pages, ``pool_from_prefill``'s layout):

    * target pages resident after the replay keep their T1/T2 membership,
      stamps and ref bits (a ghost hit re-entered them at T2 — preserved);
    * target pages the replay itself evicted re-enter as fresh T1 inserts;
    * non-target residents are demoted to their ghost list at the MRU end
      (the pool dropped them — record it where the policy can see it);
    * ghost lists are trimmed LRU-first to ARC/CAR's directory invariants
      (``|T1|+|B1| <= c``, total ≤ 2c).

    Runs host-side (numpy) — this is a request-boundary operation, not a
    decode-step one.  Returns ``(state, ghost_hits-per-row)``."""
    replayed, ghost_hits = replay_page_ids(prev, kind, pages, np.arange(n_have))
    flat, lead, L = _flatten_adaptive(replayed)
    blocks = np.asarray(flat.blocks[:, 0]).copy()
    tag = np.asarray(flat.tag[:, 0]).copy()
    stamp = np.asarray(flat.stamp[:, 0]).copy()
    ref = np.asarray(flat.ref[:, 0]).copy()
    p = np.asarray(flat.p[:, 0])
    R = blocks.shape[0]
    cap = pages
    first_page = n_have - n_res
    target = set(range(first_page, n_have))

    nb = np.full((R, L), -1, dtype=np.int32)
    nt = np.zeros((R, L), dtype=np.int32)
    ns = np.zeros((R, L), dtype=np.int32)
    nf = np.zeros((R, L), dtype=np.int32)
    nctr = np.zeros(R, dtype=np.int32)
    for r in range(R):
        res, ghosts, demoted = [], [], []  # (id, tag, stamp, ref) tuples
        for lane in range(L):
            t = int(tag[r, lane])
            if t == 0:
                continue
            bid, st_, rf = int(blocks[r, lane]), int(stamp[r, lane]), int(ref[r, lane])
            if t in (_TAG_T1, _TAG_T2):
                if bid in target:
                    res.append((bid, t, st_, rf))
                else:  # pool dropped it: demote to the matching ghost list
                    demoted.append((bid, _TAG_B1 if t == _TAG_T1 else _TAG_B2,
                                    st_, 0))
            elif bid not in target:  # ghost survives unless re-resident;
                ghosts.append((bid, t, st_, 0))  # re-residents re-enter below
        hi = max(
            [st_ for _, _, st_, _ in res + ghosts + demoted], default=0
        )
        # demoted residents append at their ghost lists' MRU end (fresh
        # stamps, relative order preserved); target pages the replay itself
        # evicted (or popped entirely) re-enter as fresh T1 inserts
        for bid, t, _, _ in sorted(demoted, key=lambda e: e[2]):
            hi += 1
            ghosts.append((bid, t, hi, 0))
        for pid in sorted(target - {e[0] for e in res}):
            hi += 1
            res.append((pid, _TAG_T1, hi, 0))
        # directory invariants, LRU-first trims
        def count(entries, *tags):
            return sum(1 for e in entries if e[1] in tags)

        while count(res, _TAG_T1) + count(ghosts, _TAG_B1) > cap:
            b1 = [e for e in ghosts if e[1] == _TAG_B1]
            ghosts.remove(min(b1, key=lambda e: e[2]))
        while len(res) + len(ghosts) > 2 * cap:
            b2 = [e for e in ghosts if e[1] == _TAG_B2]
            if not b2:
                b1 = [e for e in ghosts if e[1] == _TAG_B1]
                ghosts.remove(min(b1, key=lambda e: e[2]))
            else:
                ghosts.remove(min(b2, key=lambda e: e[2]))
        for lane, (bid, t, st_, rf) in enumerate(res + ghosts):
            nb[r, lane], nt[r, lane], ns[r, lane], nf[r, lane] = bid, t, st_, rf
        nctr[r] = hi

    out = AdaptiveState(
        blocks=jnp.asarray(nb)[:, None, :],
        tag=jnp.asarray(nt)[:, None, :],
        stamp=jnp.asarray(ns)[:, None, :],
        ref=jnp.asarray(nf)[:, None, :],
        p=jnp.asarray(p, dtype=jnp.float32)[:, None],
        ctr=jnp.asarray(nctr)[:, None],
    )
    return (
        _unflatten_adaptive(out, lead, L),
        np.asarray(ghost_hits).reshape(lead if lead else (1,)),
    )


def adaptive_insert_token(
    apool: AdaptivePagedPool,
    new_k: jax.Array,  # (B, kvd)
    new_v: jax.Array,  # (B, kvd)
    pos: jax.Array,  # scalar int32 — token index being written
    page_size: int,
    core: AdaptiveCore,
    layer: Tuple = (),
) -> AdaptivePagedPool:
    """``insert_token`` with TRUE arc/car eviction: a page-boundary
    allocation is one complete-miss access of the new page id; the policy's
    REPLACE step picks the page to demote out of the cache (into its ghost
    list) and the pool reuses that page's slot.  Residency stays coherent by
    construction — every allocation is an access, every policy eviction
    frees exactly one pool slot, and references never evict.  Branch-free;
    runs under jit/scan."""
    pool, pstate = apool
    B, P = pool.f.shape
    within = (pos % page_size).astype(jnp.int32)
    need_alloc = within == 0
    page_id = (pos // page_size).astype(jnp.int32)

    # policy access (masked: no-op between page boundaries)
    new_pstate, _ = core.on_access(
        pstate, jnp.broadcast_to(page_id, (B,)),
        active=jnp.broadcast_to(need_alloc, (B,)),
    )
    # the page REPLACE demoted (if any): resident before, ghost/gone after
    res_b = core.resident_mask(pstate)[:, 0]  # (B, 2P)
    res_a = core.resident_mask(new_pstate)[:, 0]
    evicted = res_b & ~res_a
    ev_id = jnp.max(jnp.where(evicted, pstate.blocks[:, 0], -1), axis=-1)  # (B,)

    # map the evicted page id to its pool slot; no eviction -> first free
    pool_pid = jnp.where(pool.page_start >= 0, pool.page_start // page_size, -2)
    victim = first_min(jnp.where(pool_pid == ev_id[:, None], 0, 1))
    free = pool.page_start < 0
    first_free = first_min(jnp.where(free, 0, 1))
    alloc_slot = jnp.where(ev_id >= 0, victim, first_free)  # (B,)
    slot = jnp.where(need_alloc, alloc_slot, pool.open_slot)

    bidx = jnp.arange(B)
    # metadata upkeep mirrors the classic pool (paper insert rule: F=1, R=N)
    # so telemetry and kv_positions stay uniform across modes
    f = pool.f.at[bidx, slot].set(jnp.where(need_alloc, 1, pool.f[bidx, slot]))
    r = pool.r.at[bidx, slot].set(
        jnp.where(need_alloc, pool.clock, pool.r[bidx, slot])
    )
    page_start = pool.page_start.at[bidx, slot].set(
        jnp.where(need_alloc, pos, pool.page_start[bidx, slot])
    )
    k, v = write_token((pool.k, pool.v), (new_k, new_v), layer, need_alloc,
                       slot, within)
    open_slot = jnp.where(need_alloc, slot, pool.open_slot).astype(jnp.int32)
    return AdaptivePagedPool(
        pool=PagedPool(k, v, f, r, page_start, pool.clock, open_slot),
        policy=new_pstate,
    )


def adaptive_score_update(
    apool: AdaptivePagedPool,
    attn_mass: jax.Array,  # (B, P*page) softmax mass per cache row
    page_size: int,
    core: AdaptiveCore,
) -> AdaptivePagedPool:
    """``score_update`` with TRUE arc/car bookkeeping: every referenced page
    (paper hit rule, mass >= 1/resident_count) is one policy HIT access —
    ARC promotes T1 pages to T2 / restamps T2's MRU, CAR sets reference
    bits.  Multiple references in one decode step are issued in slot order
    (the mode's documented tie order); hits never evict, so the bounded
    per-step loop is P masked accesses."""
    pool, pstate = apool
    B, P = pool.f.shape
    referenced = referenced_pages(pool, attn_mass, page_size)
    # classic metadata upkeep (F/R/clock telemetry) — same rule, same tick
    pool = score_update(pool, attn_mass, page_size)
    page_ids = jnp.where(pool.page_start >= 0, pool.page_start // page_size, 0)

    def body(s, st):
        st, _ = core.on_access(st, page_ids[:, s], active=referenced[:, s])
        return st

    pstate = jax.lax.fori_loop(0, P, body, pstate)
    return AdaptivePagedPool(pool=pool, policy=pstate)


# ---------------------------------------------------------------------------
# fused decode: policy step + paged attention in ONE Pallas launch
# ---------------------------------------------------------------------------


def _scatter_new_token(pool: PagedPool, new_k, new_v, pos, page_size, layer,
                       slot, f, r, page_start, clock, open_slot) -> PagedPool:
    """Apply the kernel's allocation decision to the K/V arrays — the same
    zero-page + row-write ``insert_token`` performs, driven by the returned
    ``slot`` (the kernel keeps the pool K/V read-only; see DESIGN.md §10)."""
    within = (pos % page_size).astype(jnp.int32)
    k, v = write_token((pool.k, pool.v), (new_k, new_v), layer, within == 0,
                       slot, within)
    return PagedPool(k=k, v=v, f=f, r=r, page_start=page_start, clock=clock,
                     open_slot=open_slot)


def _shard_wrap(fn, mesh, batch: int, example_args, n_batch_args: int):
    """Wrap a fused-kernel call in ``shard_map`` over the rows axis when a
    mesh is given and the batch divides it (PR 7 contract: decisions are
    row-local, so shard-local launches are bit-identical); identity
    otherwise."""
    if mesh is None or batch % mesh.devices.size:
        return fn
    from jax.sharding import PartitionSpec

    rows = PartitionSpec(sharding.ROWS_AXIS)
    in_specs = tuple(
        PartitionSpec(sharding.ROWS_AXIS, *(None,) * (x.ndim - 1))
        for x in example_args[:n_batch_args]
    ) + (PartitionSpec(None),)  # pos is replicated
    outs = jax.eval_shape(fn, *example_args)
    out_specs = jax.tree.map(
        lambda s: rows if s.ndim == 1
        else PartitionSpec(sharding.ROWS_AXIS, *(None,) * (s.ndim - 1)), outs)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def fused_decode_step(
    pool: PagedPool,
    q: jax.Array,  # (B, KVH, G, hd) decode-step queries
    new_k: jax.Array,  # (B, kvd) new token K row
    new_v: jax.Array,  # (B, kvd)
    pos: jax.Array,  # scalar int32 token index
    page_size: int,
    policy: str = "awrp",
    *,
    layer: Tuple = (),
    mesh=None,
    interpret: bool | None = None,
) -> Tuple[jax.Array, jax.Array, PagedPool]:
    """One flat-policy decode step as a single fused launch: equivalent to
    ``insert_token`` + ``kernels.ops.paged_attention`` + ``score_update``
    but with the policy arithmetic inside the attention kernel.  Returns
    ``(out (B, KVH, G, hd), page_mass (B, P), new_pool)`` with decisions
    bit-identical to the unfused chain.  Under ``mesh`` the kernel is
    launched shard-locally via ``shard_map`` (PR 7 path preserved)."""
    from repro.kernels import ops

    B, P = pool.f.shape
    KVH, G, hd = q.shape[1:]
    kp = layer_rows(pool.k, layer).reshape(B, P, page_size, KVH, hd)
    vp = layer_rows(pool.v, layer).reshape(B, P, page_size, KVH, hd)
    nk = new_k.reshape(B, KVH, hd)
    nv = new_v.reshape(B, KVH, hd)
    pos = jnp.asarray(pos, jnp.int32)

    def call(q, kp, vp, nk, nv, f, r, ps, clock, open_slot, pos1):
        return ops.policy_paged_attention(
            q, kp, vp, nk, nv, pos1, f, r, ps, clock, open_slot,
            policy=policy, interpret=interpret)

    args = (q, kp, vp, nk, nv, pool.f, pool.r, pool.page_start, pool.clock,
            pool.open_slot, pos.reshape(1))
    call = _shard_wrap(call, mesh, B, args, 10)
    out, mass, slot, f2, r2, ps2, clock2, open2 = call(*args)
    new_pool = _scatter_new_token(pool, new_k, new_v, pos, page_size, layer,
                                  slot, f2, r2, ps2, clock2, open2)
    return out, mass, new_pool


def fused_adaptive_decode_step(
    apool: AdaptivePagedPool,
    q: jax.Array,  # (B, KVH, G, hd)
    new_k: jax.Array,  # (B, kvd)
    new_v: jax.Array,  # (B, kvd)
    pos: jax.Array,  # scalar int32
    page_size: int,
    core: AdaptiveCore,
    *,
    layer: Tuple = (),
    mesh=None,
    interpret: bool | None = None,
) -> Tuple[jax.Array, jax.Array, AdaptivePagedPool]:
    """One TRUE-adaptive (arc/car) decode step as a single fused launch:
    equivalent to ``adaptive_insert_token`` + paged attention +
    ``adaptive_score_update`` — the rows=1 ``AdaptiveCore.on_access``
    miss/hit passes run inside the kernel.  Returns ``(out, page_mass,
    new_apool)`` with decisions AND adaptive planes bit-identical to the
    unfused chain (hard-gated in tests + bench)."""
    from repro.kernels import ops

    pool, pstate = apool
    B, P = pool.f.shape
    KVH, G, hd = q.shape[1:]
    L = pstate.blocks.shape[-1]
    kp = layer_rows(pool.k, layer).reshape(B, P, page_size, KVH, hd)
    vp = layer_rows(pool.v, layer).reshape(B, P, page_size, KVH, hd)
    nk = new_k.reshape(B, KVH, hd)
    nv = new_v.reshape(B, KVH, hd)
    pos = jnp.asarray(pos, jnp.int32)

    def call(q, kp, vp, nk, nv, f, r, ps, clock, open_slot,
             blocks, tag, stamp, refbits, p_plane, ctr, pos1):
        return ops.adaptive_policy_paged_attention(
            q, kp, vp, nk, nv, pos1, f, r, ps, clock, open_slot,
            blocks, tag, stamp, refbits, p_plane, ctr,
            kind=core.kind, renorm_at=core.renorm_at, interpret=interpret)

    args = (q, kp, vp, nk, nv, pool.f, pool.r, pool.page_start, pool.clock,
            pool.open_slot, pstate.blocks[:, 0], pstate.tag[:, 0],
            pstate.stamp[:, 0], pstate.ref[:, 0], pstate.p[:, 0],
            pstate.ctr[:, 0], pos.reshape(1))
    call = _shard_wrap(call, mesh, B, args, 16)
    (out, mass, slot, f2, r2, ps2, clock2, open2,
     blk2, tag2, stp2, ref2, pp2, ctr2) = call(*args)
    new_pool = _scatter_new_token(pool, new_k, new_v, pos, page_size, layer,
                                  slot, f2, r2, ps2, clock2, open2)
    new_state = AdaptiveState(
        blocks=blk2[:, None], tag=tag2[:, None], stamp=stp2[:, None],
        ref=ref2[:, None], p=pp2[:, None], ctr=ctr2[:, None])
    return out, mass, AdaptivePagedPool(pool=new_pool, policy=new_state)


# ---------------------------------------------------------------------------
# simple full / ring-window caches (decode baselines)
# ---------------------------------------------------------------------------


def put_row(
    plane: jax.Array,  # (..., B, T, width)
    row: jax.Array,  # (B, width)
    t: jax.Array,  # scalar int32 — the time index written
    layer: Tuple = (),
) -> jax.Array:
    """Write every sequence's row at index ``t`` of a contiguous cache, in
    place; the unbounded cache writes at the token's position.  ``layer``
    as for ``insert_token``."""
    upd = row.reshape((1,) * len(layer) + (row.shape[0], 1, row.shape[-1]))
    return jax.lax.dynamic_update_slice(plane, upd, (*layer, 0, t, 0))


def ring_put_row(plane: jax.Array, row: jax.Array, pos: jax.Array,
                 layer: Tuple = ()) -> jax.Array:
    """``put_row`` into a sliding-window ring: slot ``pos % W``, W the
    ring's width (evicting the token W steps back)."""
    return put_row(plane, row, pos % plane.shape[-2], layer)


def ring_positions(pos: jax.Array, window: int) -> jax.Array:
    """(W,) token index held by each ring slot after inserting ``pos``."""
    slots = jnp.arange(window, dtype=jnp.int32)
    # latest token with index % W == slot and index <= pos
    cand = pos - ((pos - slots) % window)
    return jnp.where(cand >= 0, cand, -1)
