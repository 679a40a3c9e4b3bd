"""Ahead-of-time TPU compiles of the main-path Pallas kernels.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: block shapes off the (8, 128) tiling, scalar stores to vector
memory, more scoped VMEM than a kernel may use.  These cases compile the
kernels for a described v5e chip — no chip attached — at the shapes the
chip smoke test runs, through the ``ops`` wrappers with ``interpret=False``,
and check that the Pallas kernel is in the compiled program.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every pytest worker imports this
file.  The file skips where no v5e topology can be described.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# the chip smoke's serving shapes: SmolLM-360M heads, a 64 x 64 page pool
B, KVH, G, HD, PAGE, P = 8, 5, 3, 64, 64, 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU compile cannot be read back from the persistent cache without a
    # chip: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernels(text):
    """Base names of the Pallas kernels (``tpu_custom_call`` ops) in a
    compiled program: the ``name=`` of each ``pallas_call``, which a
    device trace shows as the op's name."""
    return {re.sub(r"\.\d+$", "", n) for n in re.findall(
        r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)}


I32, BF16, F32 = jnp.int32, jnp.bfloat16, jnp.float32
_ATTN = [((B, KVH, G, HD), BF16), ((B, P, PAGE, KVH, HD), BF16),
         ((B, P, PAGE, KVH, HD), BF16), ((B, KVH, HD), BF16),
         ((B, KVH, HD), BF16), ((), I32),
         ((B, P), I32), ((B, P), I32), ((B, P), I32), ((B,), I32), ((B,), I32)]


def test_awrp_select_rows_compiles_at_sweep_grid_size(one_chip):
    """4096 rows x 256 lanes — a Table-1 grid over 128 traces — once needed
    more scoped VMEM than the whole-array kernel may use."""
    text = _compile(
        one_chip,
        lambda f, r, c, v: ops.awrp_select_rows(f, r, c, v, interpret=False),
        ((4096, 256), I32), ((4096, 256), I32), ((4096,), I32),
        ((4096, 256), I32))
    assert _kernels(text) == {"awrp_select_rows"}


def test_fused_flat_policy_attention_compiles(one_chip):
    text = _compile(
        one_chip,
        lambda *a: ops.policy_paged_attention(*a, policy="awrp",
                                              interpret=False),
        *_ATTN)
    assert _kernels(text) == {"policy_paged_attention"}


def test_fused_adaptive_policy_attention_compiles(one_chip):
    L = 2 * P
    text = _compile(
        one_chip,
        lambda *a: ops.adaptive_policy_paged_attention(
            *a, kind="arc", renorm_at=2**31 - 1024, interpret=False),
        *_ATTN, ((B, L), I32), ((B, L), I32), ((B, L), I32), ((B, L), I32),
        ((B,), F32), ((B,), I32))
    assert _kernels(text) == {"adaptive_policy_paged_attention"}
