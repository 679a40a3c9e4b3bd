"""Public jit'd wrappers around the Pallas kernels.

Handles TPU-friendly padding (lane-aligned page counts, MXU-aligned seq
tiles) and the execution mode: ``interpret=None`` compiles the kernel for
the TPU when JAX's backend is ``tpu``, runs the Pallas interpreter when it
is ``cpu`` (the test suite's ``JAX_PLATFORMS=cpu``), and raises on any
other backend rather than silently interpreting there.

Policy callers never import these directly: victim selection routes through
the unified core's dispatch (``repro.core.policy_core.awrp_victim_rows``,
DESIGN.md §7), which picks the kernel or the decision-identical inline
bit-pattern min-reduction per backend."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.awrp_select import (
    awrp_select_kernel,
    awrp_select_rows_kernel,
    rows_tile,
)
from repro.kernels.flash_attn import flash_attention_kernel
from repro.kernels.paged_attn import paged_attention_kernel
from repro.kernels.policy_attn import (
    adaptive_policy_paged_attention_kernel,
    policy_paged_attention_kernel,
)
from repro.obs import profiling


def _default_interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels here compile for TPU or interpret on CPU; the "
            f"JAX backend is {backend!r} (pass interpret= explicitly)")
    return backend == "cpu"


@functools.partial(jax.jit, static_argnames=("interpret",))
def awrp_select(f, r, clock, valid, pinned, *, interpret: bool | None = None):
    """(B, P) int32 metadata -> (B,) int32 victim slots (paper eq. 1)."""
    if interpret is None:
        interpret = _default_interpret()
    B, P = f.shape
    pad = (-P) % 128  # lane alignment
    if pad:
        f = jnp.pad(f, ((0, 0), (0, pad)))
        r = jnp.pad(r, ((0, 0), (0, pad)))
        valid = jnp.pad(valid, ((0, 0), (0, pad)))  # padded slots invalid
        pinned = jnp.pad(pinned, ((0, 0), (0, pad)))
    return awrp_select_kernel(
        f.astype(jnp.int32), r.astype(jnp.int32), clock.astype(jnp.int32),
        valid.astype(jnp.int32), pinned.astype(jnp.int32),
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def awrp_select_rows(f, r, clock, valid, *, interpret: bool | None = None):
    """(B, P) int32 metadata -> (B,) int32 victims, one grid step per tile
    of rows (``awrp_select.rows_tile``).

    The batched sweep engine's victim-selection hot path: called once per
    trace step with B = the flattened (trace, policy, capacity) grid."""
    if interpret is None:
        interpret = _default_interpret()
    B, P = f.shape
    pad = (-P) % 128  # lane alignment
    tile = rows_tile(B, P + pad)
    rpad = (-B) % tile  # whole row tiles; padded rows are sliced off
    pads = ((0, rpad), (0, pad))
    return awrp_select_rows_kernel(
        jnp.pad(f.astype(jnp.int32), pads), jnp.pad(r.astype(jnp.int32), pads),
        jnp.pad(clock.astype(jnp.int32), (0, rpad))[:, None],
        jnp.pad(valid.astype(jnp.int32), pads),  # padded slots invalid
        tile=tile, interpret=interpret,
    )[:B, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, page_start, cur_pos,
                    *, interpret: bool | None = None):
    """Decode attention over an AWRP pool; returns (out, page_mass)."""
    if interpret is None:
        interpret = _default_interpret()
    return paged_attention_kernel(
        q, k_pages, v_pages, page_start.astype(jnp.int32),
        cur_pos.astype(jnp.int32), interpret=interpret,
    )


# sentinel-wrapped jits (obs.profiling): the two fused policy_attn entry
# points report compile/policy_attn_step/... — when called inside an outer
# jit (the decode loop) their python wrappers run only at the OUTER trace,
# so the counters track genuine recompiles, not per-token calls
@functools.partial(
    profiling.instrument, "policy_attn_step",
    static_argnames=("policy", "interpret"))
def policy_paged_attention(q, k_pages, v_pages, new_k, new_v, pos,
                           f, r, page_start, clock, open_slot,
                           *, policy: str,
                           interpret: bool | None = None):
    """One fused flat-policy (awrp/lru/fifo/lfu) decode step: victim
    selection + in-tile KV insert + paged attention + F/R/clock score update
    in a single Pallas launch.  Returns ``(out, page_mass, slot, f', r',
    page_start', clock', open_slot')`` — see
    ``kernels/policy_attn.py`` (DESIGN.md §10).  The caller scatters the new
    token's K/V row into the pool at ``slot`` (the pool arrays stay
    read-only kernel inputs); ``repro.cache.paged_kv.fused_decode_step``
    wraps both halves."""
    if interpret is None:
        interpret = _default_interpret()
    return policy_paged_attention_kernel(
        q, k_pages, v_pages, new_k, new_v,
        pos.astype(jnp.int32).reshape(1),
        f.astype(jnp.int32), r.astype(jnp.int32),
        page_start.astype(jnp.int32), clock.astype(jnp.int32),
        open_slot.astype(jnp.int32), policy=policy, interpret=interpret,
    )


@functools.partial(
    profiling.instrument, "policy_attn_adaptive_step",
    static_argnames=("kind", "renorm_at", "interpret"))
def adaptive_policy_paged_attention(q, k_pages, v_pages, new_k, new_v, pos,
                                    f, r, page_start, clock, open_slot,
                                    blocks, tag, stamp, refbits, p_plane,
                                    ctr, *, kind: str, renorm_at,
                                    interpret: bool | None = None):
    """One fused true-adaptive (arc/car) decode step: a rows=1
    ``AdaptiveCore.on_access`` miss/hit pass runs inside the attention
    launch.  Returns the flat outputs plus the six updated ``AdaptiveState``
    planes; bit-identical to ``adaptive_insert_token`` +
    ``adaptive_score_update`` (hard-gated in tests/test_policy_attn.py)."""
    if interpret is None:
        interpret = _default_interpret()
    return adaptive_policy_paged_attention_kernel(
        q, k_pages, v_pages, new_k, new_v,
        pos.astype(jnp.int32).reshape(1),
        f.astype(jnp.int32), r.astype(jnp.int32),
        page_start.astype(jnp.int32), clock.astype(jnp.int32),
        open_slot.astype(jnp.int32), blocks.astype(jnp.int32),
        tag.astype(jnp.int32), stamp.astype(jnp.int32),
        refbits.astype(jnp.int32), p_plane.astype(jnp.float32),
        ctr.astype(jnp.int32), kind=kind, renorm_at=renorm_at,
        interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                              "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=128,
                    block_k=128, interpret: bool | None = None):
    """Tiled causal flash attention (fwd). Pads seq dims to tile multiples."""
    if interpret is None:
        interpret = _default_interpret()
    B, Sq, KVH, G, hd = q.shape
    Skv = k.shape[1]
    block_q = min(block_q, max(Sq, 16))
    block_k = min(block_k, max(Skv, 16))
    pq, pk = (-Sq) % block_q, (-Skv) % block_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    out = flash_attention_kernel(
        q, k, v, causal=causal, window=window, block_q=block_q,
        block_k=block_k, kv_len=Skv, interpret=interpret,
    )
    return out[:, :Sq]
