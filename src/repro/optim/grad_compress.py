"""Error-feedback-style int8 gradient compression.

Two pieces:

  * ``maybe_compress_grads`` — quant->dequant inside the GSPMD train step.
    This models the numerics of an int8 wire format while letting the XLA
    partitioner keep inserting the actual reductions (you cannot hand-roll a
    ring all-reduce inside a GSPMD-partitioned jit without fighting the
    partitioner).
  * ``compressed_allreduce_int8`` — the real wire win, for ``shard_map``
    contexts: each shard quantizes to int8, the ALL-GATHER moves int8 bytes
    (4x fewer collective bytes, visible in the HLO and counted by the
    roofline's collective term), and the sum happens locally in fp32.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def maybe_compress_grads(grads):
    """Per-tensor symmetric int8 quant->dequant on matrix grads (vectors stay
    fp32 — they are tiny and precision-critical)."""

    def qd(g):
        if g.ndim < 2:
            return g
        q, s = quantize_int8(g)
        return dequantize(q, s).astype(g.dtype)

    return jax.tree.map(qd, grads)


def compressed_allreduce_int8(x: jax.Array, axis_name: str) -> jax.Array:
    """shard_map collective: int8-on-the-wire all-reduce (gather + local sum).

    Wire bytes: N * size(int8) versus N * size(fp32) for a plain psum-based
    all-gather — a 4x reduction of the collective roofline term for the
    gradient exchange."""
    q, s = quantize_int8(x)
    qs = jax.lax.all_gather(q, axis_name)  # int8 payload on the wire
    ss = jax.lax.all_gather(s, axis_name)
    return jnp.tensordot(ss, qs.astype(jnp.float32), axes=((0,), (0,)))
