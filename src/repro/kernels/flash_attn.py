"""Pallas TPU kernel: tiled causal flash attention (forward).

Grid (B, KVH, nQ, nK) with the KV-tile axis innermost (sequential on TPU);
running (m, l, acc) live in VMEM scratch and the output tile is written on
the last *contributing* KV iteration.  Causal block skip: tiles entirely
above the diagonal are masked out with ``pl.when`` — on TPU the loads are
still prefetched but the MXU work is skipped, which is the standard
trade-off (cf. the splash-attention schedule).

Tiles default to 128x128 on the MXU-aligned (q, kv) axes; head_dim rides
along unsplit (<=128 for every assigned arch except zamba2's 112, which the
MXU pads internally).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, bq: int, bk: int, n_k: int, causal: bool, window: int,
            scale: float, kv_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq
    k_start = ki * bk
    # block-level causal/window skip (traced predicate)
    relevant = ki >= 0
    if causal:
        relevant &= k_start <= q_start + bq - 1
    if window:
        relevant &= q_start - (k_start + bk - 1) < window

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, G, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jnp.einsum("qgh,kh->gqk", q, k) * scale  # (G, bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < kv_len  # padded keys beyond the true length
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask[None], s, NEG_INF)
        m_prev = m_scr[...]  # (G, bq)
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask[None], p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
        acc_scr[...] = acc_scr[...] * corr[..., None] + jnp.einsum(
            "gqk,kh->gqh", p, v)
        m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[..., None]).transpose(1, 0, 2).astype(
            o_ref.dtype)


def flash_attention_kernel(
    q: jax.Array,  # (B, Sq, KVH, G, hd)
    k: jax.Array,  # (B, Skv, KVH, hd)
    v: jax.Array,  # (B, Skv, KVH, hd)
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    kv_len: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """One-pass flash attention over ``(block_q, block_k)`` tiles: grid
    ``(B, KVH, Sq/block_q, Skv/block_k)`` with the key axis innermost and
    sequential, carrying the running (m, l, acc) online-softmax state in
    VMEM scratch.  Sequence lengths must already be padded to the block
    sizes — call via ``ops.flash_attention``, which pads, masks with
    ``kv_len``, and resolves the interpret fallback off-TPU."""
    B, Sq, KVH, G, hd = q.shape
    Skv = k.shape[1]
    kv_len = Skv if kv_len is None else kv_len
    assert Sq % block_q == 0 and Skv % block_k == 0, "pad in ops.py"
    n_q, n_k = Sq // block_q, Skv // block_k
    kern = functools.partial(
        _kernel, bq=block_q, bk=block_k, n_k=n_k, causal=causal,
        window=window, scale=1.0 / math.sqrt(hd), kv_len=kv_len,
    )
    # layout: move KVH before seq so blocks are (1, 1, block, ...)
    qt = q.transpose(0, 2, 1, 3, 4)  # (B, KVH, Sq, G, hd)
    kt = k.transpose(0, 2, 1, 3)  # (B, KVH, Skv, hd)
    vt = v.transpose(0, 2, 1, 3)
    out = pl.pallas_call(
        kern,
        grid=(B, KVH, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, G, hd), lambda b, h, qi, ki: (b, h, qi, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, qi, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, qi, ki: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, G, hd), lambda b, h, qi, ki: (b, h, qi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KVH, Sq, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, block_q), jnp.float32),
            pltpu.VMEM((G, block_q), jnp.float32),
            pltpu.VMEM((G, block_q, hd), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3, 4)  # (B, Sq, KVH, G, hd)
