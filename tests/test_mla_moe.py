"""MLA and the expert-parallel expert layer (the DeepSeek-V3 block of
Moonlight-16B-A3B) against the plain float32 reference
``tests/_ref_mla_moe.py``, at a small size of the same shape: one leading
dense layer and two MLA-MoE layers, hidden 64, 4 heads, latent 32, rope 8,
16 routed experts of which 4 are held, top-3, 2 shared experts.

Tolerances: the program runs float32 here and the reference float32 at
``highest``; what differs is the order of summation (flash attention's
running softmax, absorbed vs expanded products, grouped matmuls), a few
ulps of values of order 1 compounded over three layers, so 1e-4 absolute
and relative on logits of order 1 leaves a wide margin while a missing
term (a shared expert, a rope, a held expert at the wrong offset) moves
them by 1e-2 or more.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _ref_mla_moe as ref
from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import model as M

TOL = dict(rtol=1e-4, atol=1e-4)

CFG = ModelConfig(
    name="mla-moe-test", family="moe", n_layers=3, d_model=64, n_heads=4,
    n_kv_heads=4, d_ff=96, vocab=256, rope_theta=50_000.0, norm_eps=1e-5,
    lead=("mla",), pattern=("mla_moe",), n_repeats=2,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_experts=16, top_k=3, expert_ff=24, shared_ff=48, experts_held=4,
    expert_offset=0, router_scoring="sigmoid",
    routed_scaling=2.446, page_size=4, bounded_kv_pages=64,
    dtype="float32", param_dtype="float32", remat="none")


def _params(cfg=CFG, seed=0):
    """Seeded weights, norms included (random gains, not all 1)."""
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    out = []
    for k, (path, a) in zip(keys, leaves):
        name = str(getattr(path[-1], "key", ""))
        if name.startswith("ln") or name.endswith("norm"):
            a = 0.1 * jax.random.normal(k, a.shape, a.dtype)
        elif name == "router_bias":
            a = 0.05 * jax.random.normal(k, a.shape, a.dtype)
        elif name == "embed":
            a = a * 0.5
        out.append(a * 3 if name.startswith(("w_", "shared_", "wq", "wo"))
                   else a)
    return jax.tree.unflatten(tree, out)


def _tokens(B, S, seed=0):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (B, S), 0,
                                         CFG.vocab))


def _serve_logits(params, cfg, tokens, S0, kv_mode):
    """Prefill tokens[:, :S0], then decode the rest teacher-forced; the
    logits of positions S0-1 .. S-1."""
    B, S = tokens.shape
    logits, caches = M.prefill(params, cfg, {"tokens": jnp.asarray(tokens[:, :S0])},
                               max_len=S + 4, kv_mode=kv_mode)
    out = [np.asarray(logits[:, 0])]
    step = jax.jit(lambda p, t, c: M.decode_step(p, cfg, t, c, kv_mode=kv_mode))
    for t in range(S0, S):
        lg, caches = step(params, jnp.asarray(tokens[:, t:t + 1]), caches)
        out.append(np.asarray(lg[:, 0]))
    return np.stack(out, 1)[..., : cfg.vocab], caches


def test_mla_naive_equals_absorbed_on_the_same_full_cache():
    """(a) The absorbed one-token form over latent rows equals the naive
    expanded form at the last position of the same sequence."""
    p = jax.tree.map(lambda a: a[0], _params()["u0"])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, CFG.d_model))
    pos = jnp.arange(12, dtype=jnp.int32)
    naive, lat = L.mla_attention(p, x, CFG, positions=pos)
    absorbed, mass = L.mla_decode_attend(
        p, x[:, -1:], CFG, position=jnp.int32(11), cache=lat,
        kv_positions=jnp.broadcast_to(pos, (2, 12)))
    np.testing.assert_allclose(np.asarray(absorbed[:, 0]),
                               np.asarray(naive[:, -1]), **TOL)
    np.testing.assert_allclose(np.asarray(mass.sum(-1)), CFG.n_heads, rtol=1e-5)


def test_prefill_and_full_decode_match_the_reference_forward():
    """(b) Prefill then decode in full mode, logits against the
    reference's whole forward pass at every served position."""
    params = _params()
    tokens = _tokens(2, 20)
    got, caches = _serve_logits(params, CFG, tokens, 12, "full")
    want = ref.forward(params, CFG, tokens)[:, 11:]
    np.testing.assert_allclose(got, want, **TOL)
    assert caches["moe"].shape == (2,) and int(caches["pos"]) == 20


def test_a_pool_that_never_evicts_serves_as_the_full_cache():
    """(c) Paged with room for every page: equal to full mode, and no page
    was evicted."""
    params = _params()
    tokens = _tokens(2, 22, seed=4)
    full, _ = _serve_logits(params, CFG, tokens, 12, "full")
    paged, caches = _serve_logits(params, CFG, tokens, 12, "paged")
    np.testing.assert_allclose(paged, full, **TOL)
    pool = caches["blocks"]["u0"]
    assert isinstance(pool, type(caches["blocks"]["l0"]))
    assert pool.c.shape[-1] == CFG.kv_lora_rank + CFG.qk_rope_head_dim
    assert int((pool.page_start >= 0).sum()) == 2 * 2 * 6  # 22 tokens: 6 pages


def test_a_small_pool_evicts_and_serves_finite_logits():
    cfg = dataclasses.replace(CFG, bounded_kv_pages=3)
    params = _params()
    tokens = _tokens(2, 24, seed=5)
    got, caches = _serve_logits(params, cfg, tokens, 8, "paged")
    assert np.isfinite(got).all()
    pool = caches["blocks"]["u0"]
    assert int((pool.page_start >= 0).sum(-1).max()) == 3


def _layer_params(seed=7, experts=16):
    full = dataclasses.replace(CFG, experts_held=experts)
    return jax.tree.map(lambda a: a[0], _params(full, seed)["u0"])


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """(d) The routed parts of the 4 shares of a 16-expert layer, plus the
    shared experts counted once, equal the uncut reference layer."""
    lp = _layer_params()
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 10, CFG.d_model))
    uncut = dataclasses.replace(CFG, experts_held=16)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(lp, x, uncut)
    total = 0
    for share in range(4):
        cfg = dataclasses.replace(CFG, expert_offset=4 * share)
        held = {k: (v[4 * share:4 * share + 4] if k in ("w_gate", "w_up", "w_down")
                    else v) for k, v in lp.items()}
        out, counts = L.moe(held, x, cfg)
        total = total + out - L.mlp({"w_gate": lp["shared_gate"],
                                     "w_up": lp["shared_up"],
                                     "w_down": lp["shared_down"]}, x, "swiglu")
        assert 0 < int(counts[1]) <= 4
    shared = ref.shared(lp, x)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               **TOL)


def test_no_token_is_dropped_when_every_token_picks_one_held_expert():
    """(e) A bias that sends every token's top choice to held expert 2:
    the program still matches the reference (no capacity, no drop)."""
    lp = _layer_params(experts=4)
    lp["router_bias"] = jnp.zeros_like(lp["router_bias"]).at[2].set(100.0)
    x = jax.random.normal(jax.random.PRNGKey(9), (3, 40, CFG.d_model))
    out, counts = L.moe(lp, x, CFG)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(lp, x, CFG)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)
    assert int(counts[0]) >= 3 * 40  # every token reached expert 2


def test_held_counts_count_pairs_and_experts():
    cfg = dataclasses.replace(CFG, expert_offset=4)
    idx = jnp.array([[[4, 5, 0], [4, 9, 1]]])
    assert np.asarray(L.held_counts(idx, cfg)).tolist() == [3, 2]


def test_prefill_returns_the_last_positions_logits_only():
    """(f) ``prefill`` runs the head on the last position alone, equal to
    the forward pass's last position, for a dense (SmolLM-shaped) model."""
    from repro.configs.smollm_360m import SMOKE_CONFIG

    cfg = dataclasses.replace(SMOKE_CONFIG, dtype="float32",
                              param_dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(_tokens(2, 16) % cfg.vocab)
    logits, _ = M.prefill(params, cfg, {"tokens": tokens}, max_len=24)
    assert logits.shape == (2, 1, M.pad_vocab(cfg))
    full = M.forward(params, cfg, {"tokens": tokens})
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(full[:, -1]), **TOL)


def test_served_dense_tokens_are_greedy_over_the_forward_pass():
    """(f) SmolLM-shaped serving in paged mode: each served token is the
    argmax of the whole forward pass over the prompt and the tokens
    served before it (the pool holds every page, so nothing is evicted)."""
    from repro.configs.smollm_360m import SMOKE_CONFIG
    from repro.serve.engine import Request, ServeEngine

    cfg = dataclasses.replace(SMOKE_CONFIG, dtype="float32",
                              param_dtype="float32", page_size=8,
                              bounded_kv_pages=8)
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    prompts = _tokens(2, 16, seed=2) % cfg.vocab
    eng = ServeEngine(cfg, params, max_len=32, kv_mode="paged")
    res = eng.generate([Request(i, p.tolist(), max_new_tokens=6)
                        for i, p in enumerate(prompts)])
    served = np.array([res[i].tokens for i in range(2)])
    seq = np.concatenate([prompts, served], axis=1)
    full = np.asarray(M.forward(params, cfg, {"tokens": jnp.asarray(seq)}))
    want = full[:, 15:15 + 6, : cfg.vocab].argmax(-1)
    np.testing.assert_array_equal(served, want)


def test_the_engine_serves_mla_moe_paged_and_counts_the_expert_work():
    from repro.serve.engine import Request, ServeEngine

    params = _params()
    prompts = _tokens(2, 16, seed=6)
    eng = ServeEngine(CFG, params, max_len=32, kv_mode="paged")
    res = eng.generate([Request(i, p.tolist(), max_new_tokens=5)
                        for i, p in enumerate(prompts)])
    served = np.array([res[i].tokens for i in range(2)])
    seq = np.concatenate([prompts, served], axis=1)
    want = ref.forward(params, CFG, seq)[:, 15:15 + 5].argmax(-1)
    np.testing.assert_array_equal(served, want)
    tel = eng.telemetry()
    # prefill: 2 x 16 tokens x top-3 over 16 experts, 4 held, 3 layers
    assert 0 < tel["moe/prefill/held_pairs"] <= 2 * 16 * 3 * 2
    assert 0 < tel["moe/prefill/experts_hit"] <= 4 * 2
    assert 0 < tel["moe/decode/held_pairs"] <= 4 * 2 * 3 * 2
    assert tel["moe/decode/experts_hit"] <= 4 * 4 * 2


def test_fused_serving_of_a_latent_pool_is_refused():
    from repro.serve.engine import Request, ServeEngine

    eng = ServeEngine(CFG, _params(), max_len=32, kv_mode="paged", fused=True)
    with pytest.raises(ValueError, match="latent"):
        eng.generate([Request(0, _tokens(1, 16)[0].tolist(),
                              max_new_tokens=2)])
