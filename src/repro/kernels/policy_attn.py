"""Pallas TPU kernel family: policy-aware paged-attention decode — victim
selection + KV gather + policy-plane update in ONE launch (DESIGN.md §10).

The unfused decode path pays AWRP's "low overhead" claim as a per-step XLA
dispatch chain: ``insert_token``/``adaptive_insert_token`` (victim select +
metadata scatters), then the ``paged_attn`` kernel, then ``score_update``/
``adaptive_score_update`` (reference detection + more scatters, and for
ARC/CAR a ``fori_loop`` of ``AdaptiveCore.on_access`` hit accesses).  These
kernels run the whole step per sequence inside the attention launch itself:

* grid ``(B, P)`` with the page axis innermost (sequential on TPU), exactly
  like ``kernels/paged_attn.py``'s split-KV layout;
* at ``p == 0`` the program computes the page-boundary allocation decision
  from the policy planes it already holds in VMEM — the SAME traced code the
  unfused path runs (``kv_policy.page_victim``'s bit-pattern min-reductions
  for the flat quartet; a rows=1 ``AdaptiveCore.on_access`` for arc/car) —
  and stashes the post-allocation planes in scratch;
* every page iteration gathers its KV tile flash-style (running (m, l, acc)
  in VMEM scratch), injecting the new token's K/V row in-tile at the open
  page so the pool arrays are read-only inputs;
* at ``p == P-1`` it finalizes the attention output AND the per-page mass,
  applies the paper's reference rule (mass >= 1/residents) and the policy
  score update, and writes attention + every updated policy plane.

Decisions are bit-identical to the unfused core path by construction: the
policy arithmetic is literally the shared step functions traced at rows=1
(all their reductions are row-local — the batched call computes the same
per-row result), and the attention mass recurrence is
``paged_attn.flash_page_step``, shared with the unfused kernel, so the
reference threshold sees bitwise-equal inputs.  Hard-gated in tests/test_policy_attn.py and
benchmarks/policy_attn_bench.py.

The pool K/V arrays stay read-only here (writing them through the kernel
would force a full copy-through of the pool every step); the caller applies
the one-row scatter with the returned slot — see
``paged_kv.fused_decode_step``.  Block layout follows the TPU compiler's
tiling rule (``_fused_call``); ``ops.py`` compiles the kernels on the TPU
backend and interprets them on the CPU one, as for every kernel here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.policy_core import div_rn
from repro.kernels.paged_attn import (
    flash_finalize,
    flash_page_step,
    flash_scratch,
    init_flash,
)


def _finalize_attention(o_ref, mass_ref, *flash):
    """Write the normalized output + per-page mass (paged_attn's epilogue);
    returns the (1, P) float32 mass for the in-kernel score update."""
    out, mass = flash_finalize(*flash)
    o_ref[...] = out.astype(o_ref.dtype)
    mass_ref[...] = mass.astype(mass_ref.dtype)
    return mass


def _lane(plane, idx):
    """(1, 1) value of lane ``idx`` of a (1, N) plane: a masked lane
    reduction, because a TPU kernel cannot load one lane at a dynamic
    index (exact for the int32 planes it reads)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, plane.shape, 1)
    return jnp.sum(jnp.where(lane == idx, plane, 0), axis=-1, keepdims=True)


def _classic_score_update(mass, fa, ra, psa, clock):
    """The paper's reference rule + F/R/clock tick on the post-allocation
    planes — same arithmetic as ``paged_kv.referenced_pages``/
    ``score_update`` at rows=1.  ``clock`` is (1, 1).  Returns
    (referenced, f', r', clock')."""
    resident = jnp.sum((psa >= 0).astype(jnp.int32), axis=-1,
                       keepdims=True)  # (1, 1)
    tau = div_rn(1.0, jnp.maximum(resident.astype(jnp.float32), 1.0))
    referenced = (mass >= tau) & (psa >= 0)  # (1, P)
    clock_new = clock + 1  # (1, 1)
    f_new = jnp.where(referenced, fa + 1, fa)
    r_new = jnp.where(referenced, clock_new, ra)
    return referenced, f_new, r_new, clock_new


def _attend(q_ref, k_ref, v_ref, nk_ref, nv_ref, pos, slot, within, p_idx,
            psa, flash, *, page):
    """Every grid step: one page's flash step (``paged_attn.flash_page_step``,
    shared with the unfused kernel so the reference threshold sees
    bitwise-equal mass), with the new token's K/V row injected in-tile at
    (slot, within) so the pool arrays can stay read-only inputs.  ``slot``
    is a (1, 1) vector, ``pos``/``within`` scalars, ``psa`` the (1, P)
    post-allocation page starts."""
    q = q_ref[...].astype(jnp.float32)  # (KVH, G, hd)
    k = k_ref[...].astype(jnp.float32)  # (page, KVH, hd)
    v = v_ref[...].astype(jnp.float32)
    row_k = jax.lax.broadcasted_iota(jnp.int32, (page, 1, 1), 0)
    inject = (p_idx == slot[None]) & (row_k == within)  # (page, 1, 1)
    k = jnp.where(inject, nk_ref[...].astype(jnp.float32)[None], k)
    v = jnp.where(inject, nv_ref[...].astype(jnp.float32)[None], v)
    start = _lane(psa, p_idx)[None]  # (1, 1, 1)
    row_s = jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
    valid = (start >= 0) & (start + row_s <= pos)  # (1, 1, page)
    flash_page_step(q, k, v, valid, p_idx, *flash)


def _flat_kernel(pos_ref, q_ref, k_ref, v_ref, nk_ref, nv_ref,
                 f_ref, r_ref, ps_ref, clock_ref, open_ref,
                 o_ref, mass_ref, slot_ref, fo_ref, ro_ref, pso_ref,
                 clocko_ref, openo_ref,
                 m_scr, l_scr, acc_scr, psum_scr, pmax_scr,
                 fa_scr, ra_scr, psa_scr, slot_scr,
                 *, page: int, n_pages: int, policy: str):
    """Fused flat-policy (awrp/lru/fifo/lfu) decode step for one sequence.
    Per-sequence planes arrive as (1, P) blocks and per-sequence scalars as
    (1, 1) vector blocks; only the shared ``pos`` is a scalar (SMEM)."""
    from repro.core.kv_policy import page_victim
    from repro.core.policy_core import first_min

    flash = (m_scr, l_scr, acc_scr, psum_scr, pmax_scr)
    p_idx = pl.program_id(1)
    pos = pos_ref[0]
    within = pos % page
    need_alloc = within == 0

    @pl.when(p_idx == 0)
    def _policy_alloc():
        init_flash(*flash)
        f = f_ref[...]  # (1, P)
        r = r_ref[...]
        ps = ps_ref[...]
        clock = clock_ref[...]  # (1, 1)
        open_slot = open_ref[...]  # (1, 1)
        iota = jax.lax.broadcasted_iota(jnp.int32, (1, n_pages), 1)
        # the exact insert_token allocation chain at rows=1
        free = ps < 0
        has_free = jnp.any(free, axis=-1, keepdims=True)
        first_free = first_min(jnp.where(free, 0, 1))[:, None]
        pinned = iota == open_slot
        victim = page_victim(policy, f, r, ps, clock[:, 0], pinned)[:, None]
        alloc_slot = jnp.where(has_free, first_free, victim)
        slot = jnp.where(need_alloc, alloc_slot, open_slot).astype(jnp.int32)
        # post-allocation planes (paper insert rule: F=1, R=N)
        sel = (iota == slot) & need_alloc
        fa_scr[...] = jnp.where(sel, 1, f)
        ra_scr[...] = jnp.where(sel, clock, r)
        psa_scr[...] = jnp.where(sel, pos, ps)
        slot_scr[...] = slot

    _attend(q_ref, k_ref, v_ref, nk_ref, nv_ref, pos, slot_scr[...], within,
            p_idx, psa_scr[...], flash, page=page)

    @pl.when(p_idx == n_pages - 1)
    def _finalize():
        mass = _finalize_attention(o_ref, mass_ref, *flash)
        _, f_new, r_new, clock_new = _classic_score_update(
            mass, fa_scr[...], ra_scr[...], psa_scr[...], clock_ref[...])
        fo_ref[...] = f_new
        ro_ref[...] = r_new
        pso_ref[...] = psa_scr[...]
        clocko_ref[...] = clock_new
        slot = slot_scr[...]
        slot_ref[...] = slot
        openo_ref[...] = jnp.where(need_alloc, slot, open_ref[...])


def _row_block(x):
    """Block of a per-sequence operand laid out ``(B, 1, N)``: one ``(1, N)``
    block per sequence, the batch dim squeezed — last two dims equal to the
    array's, as the TPU compiler's tiling rule asks."""
    return pl.BlockSpec((None,) + tuple(x.shape[1:]), lambda b, p: (b, 0, 0))


def _fused_call(name, kern, q, k_pages, v_pages, new_k, new_v, pos,
                rows_in, rows_out, scratch, interpret):
    """``pallas_call``, named ``name``, of a fused kernel over grid
    ``(B, P)``.  ``rows_in`` are the per-sequence policy operands, ``(B, N)``
    planes or ``(B,)`` scalars, passed as ``(B, 1, N)`` / ``(B, 1, 1)``;
    ``rows_out`` lists each per-sequence output as ``(N or None for a
    scalar, dtype)``.
    Returns the attention output followed by the policy outputs in their
    ``(B, N)`` / ``(B,)`` shapes."""
    B, P, page, KVH, hd = k_pages.shape
    G = q.shape[2]
    ins = [x.reshape(B, 1, -1) for x in rows_in]
    outs = [jax.ShapeDtypeStruct((B, 1, n or 1), dt) for n, dt in rows_out]
    q_spec = pl.BlockSpec((None, KVH, G, hd), lambda b, p: (b, 0, 0, 0))
    page_spec = pl.BlockSpec((None, None, page, KVH, hd),
                             lambda b, p: (b, p, 0, 0, 0))
    row_spec = pl.BlockSpec((None, KVH, hd), lambda b, p: (b, 0, 0))
    res = pl.pallas_call(
        kern,
        grid=(B, P),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), q_spec, page_spec,
                  page_spec, row_spec, row_spec] + [_row_block(x) for x in ins],
        out_specs=[q_spec] + [_row_block(x) for x in outs],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)] + outs,
        scratch_shapes=flash_scratch(KVH, G, hd, P) + scratch,
        interpret=interpret,
        name=name,
    )(pos, q, k_pages, v_pages, new_k, new_v, *ins)
    return (res[0],) + tuple(x.reshape(B, n) if n else x.reshape(B)
                             for x, (n, _) in zip(res[1:], rows_out))


def policy_paged_attention_kernel(
    q: jax.Array,  # (B, KVH, G, hd)
    k_pages: jax.Array,  # (B, P, page, KVH, hd) — WITHOUT the new token
    v_pages: jax.Array,  # (B, P, page, KVH, hd)
    new_k: jax.Array,  # (B, KVH, hd) new token K row (injected in-tile)
    new_v: jax.Array,  # (B, KVH, hd)
    pos: jax.Array,  # (1,) int32 current token index (shared by the batch)
    f: jax.Array,  # (B, P) int32 — paper's F_i
    r: jax.Array,  # (B, P) int32 — paper's R_i
    page_start: jax.Array,  # (B, P) int32, -1 = free page
    clock: jax.Array,  # (B,) int32 — paper's N
    open_slot: jax.Array,  # (B,) int32
    *,
    policy: str,
    interpret: bool = False,
):
    """One fused flat-policy decode step.  Returns ``(out (B,KVH,G,hd),
    page_mass (B,P) f32, slot (B,), f', r', page_start', clock',
    open_slot')`` — the attention output plus every policy plane
    ``insert_token`` + ``score_update`` would have produced, decided
    bit-identically, in a single launch."""
    P = k_pages.shape[1]
    kern = functools.partial(_flat_kernel, page=k_pages.shape[2],
                             n_pages=P, policy=policy)
    i32 = jnp.int32
    return _fused_call(
        "policy_paged_attention",
        kern, q, k_pages, v_pages, new_k, new_v, pos,
        [f, r, page_start, clock, open_slot],
        [(P, jnp.float32), (None, i32), (P, i32), (P, i32), (P, i32),
         (None, i32), (None, i32)],
        [pltpu.VMEM((1, P), i32)] * 3 + [pltpu.VMEM((1, 1), i32)],
        interpret)


def _adaptive_kernel(pos_ref, q_ref, k_ref, v_ref, nk_ref, nv_ref,
                     f_ref, r_ref, ps_ref, clock_ref, open_ref,
                     blk_ref, tag_ref, stp_ref, refb_ref, pp_ref, ctr_ref,
                     o_ref, mass_ref, slot_ref, fo_ref, ro_ref, pso_ref,
                     clocko_ref, openo_ref,
                     blko_ref, tago_ref, stpo_ref, refbo_ref, ppo_ref,
                     ctro_ref,
                     m_scr, l_scr, acc_scr, psum_scr, pmax_scr,
                     fa_scr, ra_scr, psa_scr, slot_scr,
                     blk_scr, tag_scr, stp_scr, refb_scr, pp_scr, ctr_scr,
                     *, page: int, n_pages: int, kind: str, lanes: int,
                     renorm_at):
    """Fused true-adaptive (arc/car) decode step for one sequence: a rows=1
    ``AdaptiveCore.on_access`` runs IN-KERNEL for the allocation miss and
    for every referenced page's hit — the literal ``_arc_step``/``_car_step``
    traced code, so decisions match the unfused pool bit-for-bit."""
    from repro.core.policy_core import AdaptiveCore, AdaptiveState, first_min

    core = AdaptiveCore(kind=kind, caps=(n_pages,), lanes=lanes,
                        renorm_at=renorm_at)
    flash = (m_scr, l_scr, acc_scr, psum_scr, pmax_scr)
    p_idx = pl.program_id(1)
    pos = pos_ref[0]
    within = pos % page
    need_alloc = within == 0
    # caps as a traced array (scalar broadcast): pallas_call rejects the
    # captured array constant jnp.asarray(self.caps) would become
    caps_arr = jnp.full((1,), n_pages, jnp.int32)

    @pl.when(p_idx == 0)
    def _policy_alloc():
        init_flash(*flash)
        state = AdaptiveState(
            blocks=blk_ref[...][:, None, :], tag=tag_ref[...][:, None, :],
            stamp=stp_ref[...][:, None, :], ref=refb_ref[...][:, None, :],
            p=pp_ref[...], ctr=ctr_ref[...])
        page_id = pos // page
        # the exact adaptive_insert_token chain at rows=1: one masked
        # complete-miss access, then map the demoted page id to its slot
        new_state, _ = core.on_access(
            state, jnp.full((1,), page_id, jnp.int32),
            active=jnp.full((1,), need_alloc), caps=caps_arr)
        res_b = core.resident_mask(state)[:, 0]  # (1, L)
        res_a = core.resident_mask(new_state)[:, 0]
        evicted = res_b & ~res_a
        ev_id = jnp.max(jnp.where(evicted, state.blocks[:, 0], -1), axis=-1,
                        keepdims=True)  # (1, 1)
        ps = ps_ref[...]
        pool_pid = jnp.where(ps >= 0, ps // page, -2)
        victim = first_min(jnp.where(pool_pid == ev_id, 0, 1))[:, None]
        free = ps < 0
        first_free = first_min(jnp.where(free, 0, 1))[:, None]
        alloc_slot = jnp.where(ev_id >= 0, victim, first_free)
        slot = jnp.where(need_alloc, alloc_slot, open_ref[...]).astype(
            jnp.int32)  # (1, 1)

        iota = jax.lax.broadcasted_iota(jnp.int32, (1, n_pages), 1)
        sel = (iota == slot) & need_alloc
        fa_scr[...] = jnp.where(sel, 1, f_ref[...])
        ra_scr[...] = jnp.where(sel, clock_ref[...], r_ref[...])
        psa_scr[...] = jnp.where(sel, pos, ps)
        slot_scr[...] = slot
        blk_scr[...] = new_state.blocks[:, 0]
        tag_scr[...] = new_state.tag[:, 0]
        stp_scr[...] = new_state.stamp[:, 0]
        refb_scr[...] = new_state.ref[:, 0]
        pp_scr[...] = new_state.p
        ctr_scr[...] = new_state.ctr

    _attend(q_ref, k_ref, v_ref, nk_ref, nv_ref, pos, slot_scr[...], within,
            p_idx, psa_scr[...], flash, page=page)

    @pl.when(p_idx == n_pages - 1)
    def _finalize():
        mass = _finalize_attention(o_ref, mass_ref, *flash)
        psa = psa_scr[...]
        referenced, f_new, r_new, clock_new = _classic_score_update(
            mass, fa_scr[...], ra_scr[...], psa, clock_ref[...])
        fo_ref[...] = f_new
        ro_ref[...] = r_new
        pso_ref[...] = psa
        clocko_ref[...] = clock_new
        slot = slot_scr[...]
        slot_ref[...] = slot
        openo_ref[...] = jnp.where(need_alloc, slot, open_ref[...])

        # adaptive_score_update's hit pass: P masked accesses in slot order
        page_ids = jnp.where(psa >= 0, psa // page, 0)  # (1, P)
        refd = referenced.astype(jnp.int32)
        state = AdaptiveState(
            blocks=blk_scr[...][:, None, :], tag=tag_scr[...][:, None, :],
            stamp=stp_scr[...][:, None, :], ref=refb_scr[...][:, None, :],
            p=pp_scr[...], ctr=ctr_scr[...])

        def body(si, st):
            st, _ = core.on_access(st, _lane(page_ids, si)[:, 0],
                                   active=_lane(refd, si)[:, 0] != 0,
                                   caps=caps_arr)
            return st

        state = jax.lax.fori_loop(0, n_pages, body, state)
        blko_ref[...] = state.blocks[:, 0]
        tago_ref[...] = state.tag[:, 0]
        stpo_ref[...] = state.stamp[:, 0]
        refbo_ref[...] = state.ref[:, 0]
        ppo_ref[...] = state.p
        ctro_ref[...] = state.ctr


def adaptive_policy_paged_attention_kernel(
    q: jax.Array,  # (B, KVH, G, hd)
    k_pages: jax.Array,  # (B, P, page, KVH, hd) — WITHOUT the new token
    v_pages: jax.Array,  # (B, P, page, KVH, hd)
    new_k: jax.Array,  # (B, KVH, hd)
    new_v: jax.Array,  # (B, KVH, hd)
    pos: jax.Array,  # (1,) int32
    f: jax.Array,  # (B, P) int32
    r: jax.Array,  # (B, P) int32
    page_start: jax.Array,  # (B, P) int32
    clock: jax.Array,  # (B,) int32
    open_slot: jax.Array,  # (B,) int32
    blocks: jax.Array,  # (B, L) int32 adaptive directory (L = 2P lanes)
    tag: jax.Array,  # (B, L) int32 list membership
    stamp: jax.Array,  # (B, L) int32 within-list order
    refbits: jax.Array,  # (B, L) int32 CAR reference bits
    p_plane: jax.Array,  # (B,) float32 adaptation target
    ctr: jax.Array,  # (B,) int32 stamp counter
    *,
    kind: str,
    renorm_at,
    interpret: bool = False,
):
    """One fused true-adaptive (arc/car) decode step.  Returns the flat
    kernel's eight outputs followed by the six updated ``AdaptiveState``
    planes (squeezed to ``(B, L)`` / ``(B,)``) — everything
    ``adaptive_insert_token`` + ``adaptive_score_update`` would have
    produced, bit-identically, in a single launch."""
    P, L = k_pages.shape[1], blocks.shape[1]
    kern = functools.partial(_adaptive_kernel, page=k_pages.shape[2],
                             n_pages=P, kind=kind, lanes=L,
                             renorm_at=renorm_at)
    i32, f32 = jnp.int32, jnp.float32
    return _fused_call(
        "adaptive_policy_paged_attention",
        kern, q, k_pages, v_pages, new_k, new_v, pos,
        [f, r, page_start, clock, open_slot, blocks, tag, stamp, refbits,
         p_plane, ctr],
        [(P, f32), (None, i32), (P, i32), (P, i32), (P, i32), (None, i32),
         (None, i32), (L, i32), (L, i32), (L, i32), (L, i32), (None, f32),
         (None, i32)],
        [pltpu.VMEM((1, P), i32)] * 3 + [pltpu.VMEM((1, 1), i32)]
        + [pltpu.VMEM((1, L), i32)] * 4
        + [pltpu.VMEM((1, 1), f32), pltpu.VMEM((1, 1), i32)],
        interpret)
