"""Pallas TPU kernel: decode attention over an AWRP paged KV pool.

One new query token per sequence attends to P resident pages (page_size
tokens each).  Flash-style one-pass accumulation: the grid is (B, P) with the
page axis innermost (sequential on TPU), carrying running (m, l, acc) in VMEM
scratch; the last page iteration writes the normalized output.

The kernel additionally produces the *per-page attention mass* the AWRP
scorer consumes (paper "reference" events): per-page partial sums are kept in
scratch as (sum_exp_local, max_local) per head and normalized against the
final (m, l) on the last iteration — so policy scoring costs no second pass
over HBM.

VMEM budget per program: one (page, KVH, hd) K/V tile (page=64, kvd<=3584:
~0.9MB for both) + (KVH, G, P) page partials (P<=256: <=1MB) — comfortably
inside the ~16MB/core budget with double buffering.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def init_flash(m_scr, l_scr, acc_scr, psum_scr, pmax_scr):
    """Reset the running (m, l, acc) and the per-page partial planes."""
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    psum_scr[...] = jnp.zeros_like(psum_scr)
    pmax_scr[...] = jnp.full_like(pmax_scr, NEG_INF)


def flash_page_step(q, k, v, valid, p_idx,
                    m_scr, l_scr, acc_scr, psum_scr, pmax_scr):
    """One page's flash-accumulation step, shared by this kernel and the
    fused policy-attention kernels (so their per-page mass is bitwise
    equal).  ``q`` (KVH, G, hd), ``k``/``v`` (page, KVH, hd) float32,
    ``valid`` (1, 1, page).  The page's local (sum, max) land in lane
    ``p_idx`` of the (KVH, G, P) partial planes: page on lanes, so the
    final mass is a lane vector without a relayout."""
    hd = q.shape[-1]
    s = jnp.einsum("kgh,pkh->kgp", q, k) * (1.0 / math.sqrt(hd))
    s = jnp.where(valid, s, NEG_INF)  # (KVH, G, page)

    m_loc = s.max(axis=-1)  # (KVH, G)
    p_exp = jnp.exp(s - m_loc[..., None])
    p_exp = jnp.where(valid, p_exp, 0.0)
    ssum = p_exp.sum(axis=-1)  # (KVH, G)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, m_loc)
    corr = jnp.exp(m_prev - m_new)
    scale = jnp.exp(m_loc - m_new)
    l_scr[...] = l_scr[...] * corr + ssum * scale
    pv = jnp.einsum("kgp,pkh->kgh", p_exp, v)  # (KVH, G, hd)
    acc_scr[...] = acc_scr[...] * corr[..., None] + pv * scale[..., None]
    m_scr[...] = m_new

    # stash this page's local partials for the mass output
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, psum_scr.shape[-1]), 2)
    here = lane == p_idx
    psum_scr[...] = jnp.where(here, ssum[..., None], psum_scr[...])
    pmax_scr[...] = jnp.where(here, m_loc[..., None], pmax_scr[...])


def flash_finalize(m_scr, l_scr, acc_scr, psum_scr, pmax_scr):
    """Normalized output (KVH, G, hd) and per-page mass (1, P) float32:
    mass_p = sum over heads of psum_p * exp(pmax_p - m_final) / l."""
    l = jnp.maximum(l_scr[...], 1e-30)  # (KVH, G)
    out = acc_scr[...] / l[..., None]
    w = jnp.exp(pmax_scr[...] - m_scr[...][..., None]) / l[..., None]
    mass = (psum_scr[...] * w).sum(axis=0).sum(axis=0, keepdims=True)
    return out, mass


def flash_scratch(KVH, G, hd, P):
    """VMEM scratch for ``init_flash``/``flash_page_step``."""
    return [
        pltpu.VMEM((KVH, G), jnp.float32),
        pltpu.VMEM((KVH, G), jnp.float32),
        pltpu.VMEM((KVH, G, hd), jnp.float32),
        pltpu.VMEM((KVH, G, P), jnp.float32),
        pltpu.VMEM((KVH, G, P), jnp.float32),
    ]


def _kernel(q_ref, k_ref, v_ref, startpos_ref, curpos_ref,
            o_ref, mass_ref, *flash, page: int, n_pages: int):
    p_idx = pl.program_id(1)

    @pl.when(p_idx == 0)
    def _init():
        init_flash(*flash)

    q = q_ref[0].astype(jnp.float32)  # (KVH, G, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (page, KVH, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    start = startpos_ref[0]
    cur = curpos_ref[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
    valid = (start >= 0) & (start + row <= cur)  # (1, 1, page)
    flash_page_step(q, k, v, valid, p_idx, *flash)

    @pl.when(p_idx == n_pages - 1)
    def _finalize():
        out, mass = flash_finalize(*flash)
        o_ref[0] = out.astype(o_ref.dtype)
        mass_ref[...] = mass.astype(mass_ref.dtype)


def paged_attention_kernel(
    q: jax.Array,  # (B, KVH, G, hd)
    k_pages: jax.Array,  # (B, P, page, KVH, hd)
    v_pages: jax.Array,  # (B, P, page, KVH, hd)
    page_start: jax.Array,  # (B, P) int32, -1 = free page
    cur_pos: jax.Array,  # (B,) int32 current token position
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (out (B, KVH, G, hd), page_mass (B, P))."""
    B, P, page, KVH, hd = k_pages.shape
    G = q.shape[2]
    kern = functools.partial(_kernel, page=page, n_pages=P)
    return pl.pallas_call(
        kern,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, KVH, G, hd), lambda b, p: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, page, KVH, hd), lambda b, p: (b, p, 0, 0, 0)),
            pl.BlockSpec((1, 1, page, KVH, hd), lambda b, p: (b, p, 0, 0, 0)),
            pl.BlockSpec((1, 1), lambda b, p: (b, p)),
            pl.BlockSpec((1,), lambda b, p: (b,)),
        ],
        out_specs=[
            pl.BlockSpec((1, KVH, G, hd), lambda b, p: (b, 0, 0, 0)),
            pl.BlockSpec((1, P), lambda b, p: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KVH, G, hd), q.dtype),
            jax.ShapeDtypeStruct((B, P), jnp.float32),
        ],
        scratch_shapes=flash_scratch(KVH, G, hd, P),
        interpret=interpret,
        name="paged_attention",
    )(q, k_pages, v_pages, page_start, cur_pos)
