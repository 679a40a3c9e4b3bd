"""Ahead-of-time TPU compiles of the main-path Pallas kernels.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: block shapes off the (8, 128) tiling, scalar stores to vector
memory, more scoped VMEM than a kernel may use.  These cases compile the
kernels for a described v5e chip — no chip attached — at the shapes the
chip smoke test runs, through the ``ops`` wrappers with ``interpret=False``,
and check that the Pallas kernel is in the compiled program.  The serving
engine's decode loop is compiled the same way at published widths, to see
what the TPU compiler makes of the stacked KV pool that the loop carries.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every pytest worker imports this
file.  The file skips where no v5e topology can be described.
"""

import dataclasses
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


# the decode loop at published widths: SmolLM2-360M (32 layers, 5 KV heads
# of 64) over 8 sequences, and Moonlight-16B-A3B (1 dense + 26 MLA-MoE
# layers, latent 512 + rope 64, 8 of 64 experts held) over 16; a pool of
# 64 pages x 64 tokens a sequence and layer, prompts of 4096 and 256 steps
def _smollm():
    from repro.configs.smollm_360m import CONFIG

    return dataclasses.replace(CONFIG, rope_theta=100000.0, page_size=64,
                               bounded_kv_pages=64, dtype="bfloat16",
                               param_dtype="bfloat16"), 8


def _moonlight():
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name="moonlight-16b-a3b", family="moe", n_layers=27, d_model=2048,
        n_heads=16, n_kv_heads=16, d_ff=11264, vocab=163840,
        rope_theta=50000.0, norm_eps=1e-5, tie_embeddings=False,
        lead=("mla",), pattern=("mla_moe",), n_repeats=26, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        n_experts=64, top_k=6, router_scoring="sigmoid", routed_scaling=2.446,
        expert_ff=1408, shared_ff=2816, experts_held=8, expert_offset=0,
        page_size=64, bounded_kv_pages=64, dtype="bfloat16",
        param_dtype="bfloat16"), 16


@pytest.mark.parametrize("model", [_smollm, _moonlight],
                         ids=["smollm-360m", "moonlight-16b-a3b"])
def test_decode_loop_carries_the_stacked_pool_without_a_copy(one_chip, model):
    """The engine's decode loop, compiled for the chip, holds each stacked
    row plane of the pool (K and V, or the latent rows) in place: no
    ``copy`` of a whole stack, and less scratch memory than one stack."""
    from repro.models import model as M
    from repro.obs.metrics import loop_planes
    from repro.serve.engine import ServeEngine

    cfg, batch = model()
    prompt, steps = 4096, 256
    params = M.abstract_params(cfg)
    eng = ServeEngine(cfg, params, max_len=prompt + steps, kv_mode="paged")
    eng._build_loop(steps)
    caches = jax.eval_shape(lambda: M.decode_caches(
        cfg, batch, prompt + steps, kv_mode="paged"))

    def spec(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    args = spec((params, jax.ShapeDtypeStruct((batch, 1, cfg.vocab), BF16),
                 caches, jax.ShapeDtypeStruct((2,), jnp.uint32),
                 jax.ShapeDtypeStruct((), F32),
                 jax.eval_shape(loop_planes)))
    # the precision the program runs at (another test file may set float32)
    with jax.default_matmul_precision(None):
        compiled = eng._loop_sentinel._jits[-1].lower(*args).compile()
    stacks = {x.shape: x.size * x.dtype.itemsize
              for x in jax.tree.leaves(caches["blocks"]["u0"]) if x.ndim == 5}
    assert stacks  # the layer stack of a paged pool's row planes
    text = compiled.as_text()
    for shape in stacks:
        name = "bf16[" + ",".join(map(str, shape)) + "]"
        assert f"= {name}" in text
        copies = [ln for ln in text.splitlines()
                  if f"= {name}" in ln and " copy(" in ln]
        assert not copies, copies
    assert compiled.memory_analysis().temp_size_in_bytes < min(stacks.values())
