"""Per-arch smoke tests (reduced configs, CPU) + prefill/decode parity."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache import paged_kv
from repro.configs.base import ARCH_IDS, SHAPES, ModelConfig, load_smoke_config
from repro.models import model as M
from repro.serve.engine import Request, ServeEngine

jax.config.update("jax_default_matmul_precision", "float32")


def f32(cfg):
    return dataclasses.replace(cfg, dtype="float32", param_dtype="float32")


def make_batch(cfg, B, S, key):
    kt, kf = jax.random.split(key)
    batch = {
        "tokens": jax.random.randint(kt, (B, S), 0, cfg.vocab),
        "labels": jax.random.randint(kf, (B, S), 0, cfg.vocab),
    }
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            kf, (B, S // cfg.enc_seq_divisor, cfg.d_model), jnp.float32
        ).astype(jnp.dtype(cfg.dtype)) * 0.02
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            kf, (B, cfg.n_patch_tokens, cfg.d_model), jnp.float32
        ).astype(jnp.dtype(cfg.dtype)) * 0.02
    return batch


# Per-arch smoke runs cost 3-15s each on CPU; the default CI run keeps one
# representative per family wiring (dense: smollm, SSM: mamba2, MoE: grok1)
# and nightly (-m slow) covers the rest.  The tier-1 local run includes all.
_FAST_SMOKE = {"smollm_360m", "mamba2_370m", "grok1_314b"}
_smoke_params = [
    a if a in _FAST_SMOKE else pytest.param(a, marks=pytest.mark.slow)
    for a in ARCH_IDS
]


@pytest.mark.parametrize("arch", _smoke_params)
def test_smoke_forward_and_grad(arch):
    """One forward + one grad step on the reduced config: shapes + finite."""
    cfg = load_smoke_config(arch)
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    B, S = 2, 64
    batch = make_batch(cfg, B, S, key)
    logits = M.forward(params, cfg, batch)
    assert logits.shape == (B, S, M.pad_vocab(cfg))
    assert bool(jnp.isfinite(logits).all())
    loss, grads = jax.value_and_grad(M.loss_fn)(params, cfg, batch)
    assert bool(jnp.isfinite(loss))
    gnorm = jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
    )
    assert bool(jnp.isfinite(gnorm)) and float(gnorm) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_param_count_positive(arch):
    cfg = load_smoke_config(arch)
    n = cfg.n_params()
    na = cfg.n_active_params()
    assert n > 0 and 0 < na <= n


slow = pytest.mark.slow

PARITY_ARCHS = [
    "qwen25_14b",                            # dense GQA + qkv bias
    pytest.param("gemma3_27b", marks=slow),  # local ring + global full cache
    pytest.param("zamba2_7b", marks=slow),   # mamba + shared attention
    "mamba2_370m",                           # pure SSD recurrence
    pytest.param("whisper_large_v3", marks=slow),  # enc-dec, cross attention
    "grok1_314b",                            # MoE
]


@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_prefill_decode_matches_forward(arch):
    """logits[S-1] from full forward == prefill(S-1) + one decode step."""
    cfg = f32(load_smoke_config(arch))
    if cfg.n_experts:
        # token dropping differs between T=B*S and T=B*1 dispatch; use a
        # no-drop capacity so parity is exact (drop behaviour is tested in
        # test_smoke_forward_and_grad via the default capacity)
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.n_experts))
    key = jax.random.PRNGKey(1)
    params = M.init_params(cfg, key)
    B, S = 2, 32
    batch = make_batch(cfg, B, S, key)
    full = M.forward(params, cfg, batch)  # (B, S, V)

    pre_batch = dict(batch)
    pre_batch["tokens"] = batch["tokens"][:, : S - 1]
    if cfg.family == "encdec":
        pass  # frames unchanged: encoder context identical
    if cfg.family == "vlm":
        pre_batch["patches"] = batch["patches"]
    _, caches = M.prefill(params, cfg, pre_batch, max_len=S + 8)
    logits1, caches = M.decode_step(
        params, cfg, batch["tokens"][:, S - 1 : S], caches
    )
    np.testing.assert_allclose(
        np.asarray(logits1[:, 0]), np.asarray(full[:, S - 1]), rtol=2e-4, atol=2e-4
    )
    assert int(caches["pos"]) == S


@pytest.mark.slow
def test_paged_decode_matches_full_when_no_eviction():
    """AWRP bounded pool with capacity >= all pages must equal full-cache
    decode exactly (the technique is lossless until eviction kicks in).
    Nightly: the fast eviction test below exercises the same paged path."""
    cfg = f32(load_smoke_config("gemma3_27b"))
    cfg = dataclasses.replace(cfg, bounded_kv_pages=16, page_size=8)
    key = jax.random.PRNGKey(2)
    params = M.init_params(cfg, key)
    B = 2
    S = 24  # page-aligned (3 pages)
    batch = make_batch(cfg, B, S, key)
    _, caches_full = M.prefill(params, cfg, {"tokens": batch["tokens"]}, max_len=S + 8,
                               kv_mode="full")
    _, caches_paged = M.prefill(params, cfg, {"tokens": batch["tokens"]}, max_len=S + 8,
                                kv_mode="paged")
    tok = jax.random.randint(key, (B, 1), 0, cfg.vocab)
    lf, _ = M.decode_step(params, cfg, tok, caches_full, kv_mode="full")
    lp, _ = M.decode_step(params, cfg, tok, caches_paged, kv_mode="paged")
    np.testing.assert_allclose(np.asarray(lf), np.asarray(lp), rtol=2e-4, atol=2e-4)


def test_paged_decode_evicts_and_stays_finite():
    """Long decode with a tiny pool: AWRP evicts, logits stay finite, and the
    resident set is bounded."""
    cfg = f32(load_smoke_config("gemma3_27b"))
    cfg = dataclasses.replace(cfg, bounded_kv_pages=3, page_size=4)
    key = jax.random.PRNGKey(3)
    params = M.init_params(cfg, key)
    B, S = 1, 8  # 2 pages resident after prefill
    batch = make_batch(cfg, B, S, key)
    _, caches = M.prefill(params, cfg, {"tokens": batch["tokens"]}, max_len=64,
                          kv_mode="paged")
    tok = batch["tokens"][:, :1]
    step = jax.jit(lambda t, c: M.decode_step(params, cfg, t, c, kv_mode="paged"))
    for _ in range(24):  # crosses several page boundaries -> evictions
        logits, caches = step(tok, caches)
        assert bool(jnp.isfinite(logits).all())
        tok = jnp.argmax(logits[:, :, : cfg.vocab], axis=-1).astype(jnp.int32)
    pool = caches["blocks"]["t0"]  # a "local"? t0 is local; use global u-block
    # find a paged pool in the tree (global layer position u2 in smoke pattern)
    pool = caches["blocks"]["u2"]
    resident = np.asarray(pool.page_start >= 0).sum(axis=-1)
    assert (resident <= cfg.bounded_kv_pages).all()
    # clock advanced once per decode step
    assert int(pool.clock.reshape(-1)[0]) == 24 + 2  # prefill seeded 2 pages


def test_awrp_victim_matches_host_oracle():
    """Vectorized pool eviction == the numpy AWRP victim rule, bit-exact."""
    from repro.cache.paged_kv import awrp_victim
    from repro.core.policies import AWRP

    rng = np.random.RandomState(0)
    for _ in range(50):
        P = rng.randint(2, 12)
        clock = rng.randint(P + 1, 100)
        f = rng.randint(1, 20, size=P).astype(np.int32)
        r = rng.randint(0, clock, size=P).astype(np.int32)
        # host oracle: same slot-array layout
        host = AWRP(P)
        host.blocks = np.arange(P, dtype=np.int64)
        host.F = f.astype(np.int64)
        host.R = r.astype(np.int64)
        host.clock = clock
        expect = host.victim_slot()
        got = awrp_victim(
            jnp.asarray(f)[None], jnp.asarray(r)[None],
            jnp.asarray([clock], jnp.int32),
            jnp.ones((1, P), bool), jnp.zeros((1, P), bool),
        )
        assert int(got[0]) == expect


# ---------------------------------------------------------------------------
# the decode step's layer scan carries the row planes and writes by row
# ---------------------------------------------------------------------------

_MLA_CFG = ModelConfig(
    name="mla-rows-test", family="moe", n_layers=3, d_model=64, n_heads=4,
    n_kv_heads=4, d_ff=96, vocab=256, rope_theta=50_000.0, norm_eps=1e-5,
    lead=("mla",), pattern=("mla_moe",), n_repeats=2, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, n_experts=16,
    top_k=3, expert_ff=24, shared_ff=48, experts_held=4, expert_offset=0,
    router_scoring="sigmoid", routed_scaling=2.446, page_size=4,
    bounded_kv_pages=3, remat="none")


def _row_case(name):
    if name == "mla":
        return _MLA_CFG
    cfg = dataclasses.replace(load_smoke_config("smollm_360m"), n_layers=3,
                              bounded_kv_pages=3, page_size=4)
    return dataclasses.replace(cfg, kv_policy=name)


def _layer_by_layer_step(params, cfg, token, caches):
    """The reference: ``decode_step`` with each layer's whole cache sliced
    out of the stack as a scan input and written back whole as a scan
    output, every block run on its own layer's pool (``insert_*`` and
    ``score_update`` on that slice)."""
    pos = caches["pos"]
    lead, unit, _, _ = M.scan_plan(cfg)
    blocks, counts = dict(caches["blocks"]), None
    x = jnp.take(params["embed"], token, axis=0).astype(jnp.dtype(cfg.dtype))

    def block(kind, p, h, cache):
        return M._decode_block(kind, p, h, cfg, cache, pos, None, "paged")

    for name, kind in lead:
        x, blocks[name], c = block(kind, params[name], x, blocks[name])
        counts = M._add_counts(counts, c)

    def body(h, xs):
        p, cs = xs
        new, unit_counts = {}, None
        for name, kind in unit:
            h, new[name], c = block(kind, p[name], h, cs[name])
            unit_counts = M._add_counts(unit_counts, c)
        return h, (new, unit_counts)

    x, (new, unit_counts) = jax.lax.scan(
        body, x, ({n: params[n] for n, _ in unit},
                  {n: blocks[n] for n, _ in unit}))
    blocks.update(new)
    out = {"pos": pos + 1, "blocks": blocks}
    if unit_counts is not None:
        out["moe"] = M._add_counts(counts, unit_counts.sum(0))
    return M.logits_from_hidden(params, cfg, x), out


def _assert_rows_written(old, new, page):
    """The row rule in numpy, apart from the row writer: on a page boundary
    the page the pool's bookkeeping opened (its open slot, starting at this
    token) is zeroed, then the token's row, which is not all zero, goes to
    its place in that page; every other row of every layer stays as it
    was."""
    pos = int(old["pos"])
    within = pos % page
    for name, node in old["blocks"].items():
        was, now = getattr(node, "pool", node), new["blocks"][name]
        now = getattr(now, "pool", now)  # an adaptive pool wraps a classic one
        slot = np.asarray(now.open_slot)  # (layers, B) or (B,)
        start = np.asarray(now.page_start)
        for plane in ("k", "v", "c"):
            if not hasattr(was, plane):
                continue
            got = np.asarray(getattr(now, plane), np.float32)
            want = np.asarray(getattr(was, plane), np.float32).copy()
            for at in np.ndindex(*slot.shape):
                page_at = (*at, slot[at])
                assert start[page_at] == pos - within
                if within == 0:
                    want[page_at] = 0
                assert got[(*page_at, within)].any()
                want[(*page_at, within)] = got[(*page_at, within)]
            np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("case", ["awrp", "arc_adaptive", "mla"])
def test_carried_row_planes_decode_bit_identical_to_layer_by_layer(case):
    """The pool full at decode start (3 pages of 4), 10 greedy steps over
    three page boundaries, each evicting in every layer: tokens, logits and
    every plane of every layer's pool equal the layer-by-layer reference
    bit for bit, and each step's row writes follow the row rule checked
    apart from the writer both sides share (``_assert_rows_written``)."""
    cfg = _row_case(case)
    params = M.init_params(cfg, jax.random.PRNGKey(5))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 12), 0, cfg.vocab)
    logits, caches = M.prefill(params, cfg, {"tokens": tokens}, max_len=32,
                               kv_mode="paged")
    carried = jax.jit(lambda t, c: M.decode_step(params, cfg, t, c,
                                                 kv_mode="paged"))
    reference = jax.jit(lambda t, c: _layer_by_layer_step(params, cfg, t, c))
    tok = jnp.argmax(logits[:, :, : cfg.vocab], -1).astype(jnp.int32)
    got, want = (tok, caches), (tok, caches)
    evicted = 0
    for _ in range(10):
        (lg, c), (lw, w) = carried(*got), reference(*want)
        np.testing.assert_array_equal(np.asarray(lg), np.asarray(lw))
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), c, w)
        _assert_rows_written(got[1], c, cfg.page_size)
        evicted += int(paged_kv.step_counts(got[1], c)[1])
        nxt = jnp.argmax(lg[:, :, : cfg.vocab], -1).astype(jnp.int32)
        got, want = (nxt, c), (nxt, w)
    # every paged layer evicts one page a sequence at pos 12, 16 and 20
    assert evicted == 3 * 2 * cfg.n_layers


def test_decode_loop_writes_the_stacked_pool_in_place():
    """The engine's compiled decode loop makes no copy of a whole stacked
    K/V plane: each layer reads and writes its rows in the layer scan's
    carried stack, which the donated loop carry aliases."""
    cfg = dataclasses.replace(load_smoke_config("smollm_360m"), n_layers=4,
                              bounded_kv_pages=4, page_size=8)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_len=64, kv_mode="paged")
    eng.generate([Request(0, list(range(1, 33)), max_new_tokens=8)])
    k = jax.eval_shape(lambda: M.decode_caches(cfg, 1, 64, kv_mode="paged"))
    k = k["blocks"]["u0"].k
    shape = ({"bfloat16": "bf16", "float32": "f32"}[k.dtype.name]
             + "[" + ",".join(map(str, k.shape)) + "]")
    text = eng._loop_sentinel.compiled_text()
    assert f"= {shape}" in text  # the loop carries the stacked plane
    copies = [ln for ln in text.splitlines()
              if f"= {shape}" in ln and " copy(" in ln]
    assert not copies, copies
