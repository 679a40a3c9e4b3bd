"""Pallas TPU kernel: fused AWRP weight + masked argmin victim selection.

The eviction decision is the paper's hot loop: every pool-full page
allocation scans all P pages' metadata, computes W = F/(N-R) (eq. 1) and
takes the first-index minimum.  Fused in one VPU pass over VMEM-resident
metadata — no HBM round-trip for the weight vector, no separate
mask/argmin kernels, and no argmin at all: both variants select victims
with the bit-pattern min-reduction (argmin lowers to a ~30x slower scalar
reduce on XLA CPU).

Layout: metadata vectors are (B, P) int32 with P padded to the 128-lane
boundary by the ops.py wrapper.  ``awrp_select_kernel`` runs one program
per sequence; ``awrp_select_rows_kernel`` one program per tile of rows
(policy instances are independent, so either grid parallelizes freely).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.policy_core import awrp_weights


def _masked_weight_first_min(f, r, clock_col, mask):
    """Shared victim-select body for both kernel variants: paper eq. (1)
    rounded exactly as the host oracle rounds it (``awrp_weights``, so
    decisions are bit-exact on the TPU too), then the first-index minimum
    over masked lanes as two vectorizable integer min-reductions.  w >= 0 always (F >= 0, dt >= 1), and non-negative IEEE
    floats order identically to their int32 bit patterns — so no argmin
    (XLA CPU lowers a float argmin to a ~30x slower scalar reduce; TPU
    dislikes 1D iota)."""
    P = f.shape[-1]
    w = awrp_weights(f, r, clock_col)
    bits = jax.lax.bitcast_convert_type(w, jnp.int32)
    bits = jnp.where(mask, bits, jnp.iinfo(jnp.int32).max)
    lane = jax.lax.broadcasted_iota(jnp.int32, bits.shape, 1)
    m = jnp.min(bits, axis=-1, keepdims=True)
    return jnp.min(jnp.where(bits == m, lane, P), axis=-1).astype(jnp.int32)


def _kernel(f_ref, r_ref, clock_ref, valid_ref, pinned_ref, out_ref):
    f = f_ref[...]  # (1, P) int32
    r = r_ref[...]
    clock = clock_ref[0]
    valid = valid_ref[...] != 0
    pinned = pinned_ref[...] != 0
    out_ref[0] = _masked_weight_first_min(f, r, clock, valid & ~pinned)[0]


def awrp_select_kernel(
    f: jax.Array,  # (B, P) int32, P % 128 == 0
    r: jax.Array,  # (B, P) int32
    clock: jax.Array,  # (B,) int32
    valid: jax.Array,  # (B, P) int32 (0/1)
    pinned: jax.Array,  # (B, P) int32 (0/1)
    *,
    interpret: bool = False,
) -> jax.Array:
    """Per-row AWRP victim index: ``(B,)`` int32 first-index argmin of the
    eq. (1) weight W = F/(N-R) over ``valid & ~pinned`` lanes, computed with
    the bit-pattern min-reduction (no argmin).  Grid is ``(B,)``; call via
    ``ops.awrp_select`` which pads P to the lane boundary and resolves the
    interpret fallback off-TPU."""
    B, P = f.shape
    return pl.pallas_call(
        _kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, P), lambda b: (b, 0)),
            pl.BlockSpec((1, P), lambda b: (b, 0)),
            pl.BlockSpec((1,), lambda b: (b,)),
            pl.BlockSpec((1, P), lambda b: (b, 0)),
            pl.BlockSpec((1, P), lambda b: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1,), lambda b: (b,)),
        out_shape=jax.ShapeDtypeStruct((B,), jnp.int32),
        interpret=interpret,
        name="awrp_select",
    )(f, r, clock, valid, pinned)


def _rows_kernel(f_ref, r_ref, clock_ref, valid_ref, out_ref):
    valid = valid_ref[...] != 0  # (TB, P)
    out_ref[...] = _masked_weight_first_min(
        f_ref[...], r_ref[...], clock_ref[...], valid)[:, None]


#: bytes of one (row tile, P) int32 metadata block: three inputs, double
#: buffered, plus the kernel's temporaries stay a few MiB under the 16 MiB
#: scoped-VMEM default at any B
_ROW_TILE_BYTES = 512 * 1024


def rows_tile(B: int, P: int) -> int:
    """Rows per grid step of ``awrp_select_rows_kernel``: a multiple of 8
    (the sublane tile) holding at most ``_ROW_TILE_BYTES`` of one plane, and
    no more rows than ``B`` rounded up to 8."""
    cap = max(8, (_ROW_TILE_BYTES // (4 * P)) // 8 * 8)
    return min(cap, -(-B // 8) * 8)


def awrp_select_rows_kernel(
    f: jax.Array,  # (B, P) int32, P % 128 == 0, B % rows_tile(B, P) == 0
    r: jax.Array,  # (B, P) int32
    clock: jax.Array,  # (B, 1) int32
    valid: jax.Array,  # (B, P) int32 (0/1)
    *,
    tile: int,
    interpret: bool = False,
) -> jax.Array:
    """Rows variant for the batched sweep engine, called once per trace step
    with B = the whole (trace, policy, capacity) grid: a grid over row
    tiles of ``tile`` rows, so VMEM use is bounded at any B and each step
    computes a whole tile's victims in one VPU pass.  Returns ``(B, 1)``;
    ``ops.awrp_select_rows`` pads B and P and squeezes the result."""
    B, P = f.shape
    plane = pl.BlockSpec((tile, P), lambda i: (i, 0))
    col = pl.BlockSpec((tile, 1), lambda i: (i, 0))
    return pl.pallas_call(
        _rows_kernel,
        grid=(B // tile,),
        in_specs=[plane, plane, col, plane],
        out_specs=col,
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.int32),
        interpret=interpret,
        name="awrp_select_rows",
    )(f, r, clock, valid)
