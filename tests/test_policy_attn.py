"""Fused policy-attention kernel parity suite (kernels/policy_attn.py,
DESIGN.md §10).

The tentpole invariant: fusing victim selection + KV gather + score update
into one Pallas launch is DECISION-INVARIANT — every pool plane (F/R/
page_start/clock/open_slot), every adaptive plane (blocks/tag/stamp/ref/
p/ctr) and the K/V contents themselves bit-identical to the unfused
``insert_token``/``adaptive_insert_token`` + ``ops.paged_attention`` +
``score_update``/``adaptive_score_update`` chain, per decode step, across
flat policies (awrp/lru/fifo/lfu), true-adaptive arc/car, ghost-churn
seeded states, mixed pool capacities and the PR 3 stamp-renormalization
``lax.cond`` edge.  The oracle attention is the UNFUSED Pallas kernel
(``ops.paged_attention``) whose flash recurrence is the same op sequence —
so the attention mass feeding the reference rule is bitwise equal and the
plane gates are exact, not tolerance-based.  Attention output additionally
cross-checks against the plain-softmax ``ref_paged_attention``.

Kernels run in interpret mode on CPU (this container); the fast cases here
are the default-CI smoke, the ``slow``-marked grid is the nightly fused
parity run (PR 2 split).  Multi-device cases skip without forced XLA host
devices (run via ``tools/run_sharded_smoke.py`` or the CI multi-device
job).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache import paged_kv
from repro.core import sharding
from repro.kernels import ops, ref

KVH, G, HD = 2, 2, 8
KVD = KVH * HD


def _mesh_or_skip(n: int):
    if n > sharding.device_count():
        pytest.skip(f"needs {n} XLA host devices "
                    f"(have {sharding.device_count()}; see "
                    f"tools/run_sharded_smoke.py)")
    return sharding.rows_mesh(n)


def _rand_step(key, B):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, KVH, G, HD), jnp.float32)
    nk = jax.random.normal(k2, (B, KVD), jnp.float32) * 0.3
    nv = jax.random.normal(k3, (B, KVD), jnp.float32) * 0.3
    return q, nk, nv


def _unfused_flat_step(pool, q, nk, nv, pos, page, policy):
    """The dispatch chain the fused kernel replaces, with the UNFUSED Pallas
    attention as the mass oracle (same flash arithmetic -> bitwise mass)."""
    B, P = pool.f.shape
    pool = paged_kv.insert_token(pool, nk, nv, pos, page, policy=policy)
    out, mass = ops.paged_attention(
        q, pool.k.reshape(B, P, page, KVH, HD),
        pool.v.reshape(B, P, page, KVH, HD),
        pool.page_start, jnp.full((B,), pos, jnp.int32), interpret=True)
    attn_mass = jnp.zeros((B, P, page), jnp.float32).at[:, :, 0].set(
        mass).reshape(B, P * page)
    return out, mass, paged_kv.score_update(pool, attn_mass, page)


def _unfused_adaptive_step(apool, q, nk, nv, pos, page, core):
    B, P = apool.pool.f.shape
    apool = paged_kv.adaptive_insert_token(apool, nk, nv, pos, page, core)
    out, mass = ops.paged_attention(
        q, apool.pool.k.reshape(B, P, page, KVH, HD),
        apool.pool.v.reshape(B, P, page, KVH, HD),
        apool.pool.page_start, jnp.full((B,), pos, jnp.int32),
        interpret=True)
    attn_mass = jnp.zeros((B, P, page), jnp.float32).at[:, :, 0].set(
        mass).reshape(B, P * page)
    return out, mass, paged_kv.adaptive_score_update(apool, attn_mass, page,
                                                     core)


def _assert_bitwise(tag, fused, unfused):
    for name, a, b in zip(fused._fields, fused, unfused):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            f"{tag}: plane {name} diverged"


def _run_flat_parity(policy, B, P, page, steps, seed=0):
    key = jax.random.PRNGKey(seed)
    pool = paged_kv.init_pool(B, P, page, KVD, jnp.float32)
    for pos_i in range(steps):
        pos = jnp.int32(pos_i)
        key, sub = jax.random.split(key)
        q, nk, nv = _rand_step(sub, B)
        out_u, mass_u, pool_u = _unfused_flat_step(pool, q, nk, nv, pos,
                                                   page, policy)
        out_f, mass_f, pool_f = paged_kv.fused_decode_step(
            pool, q, nk, nv, pos, page, policy)
        _assert_bitwise(f"{policy} pos={pos_i}", pool_f, pool_u)
        assert np.array_equal(np.asarray(mass_f), np.asarray(mass_u))
        assert np.array_equal(np.asarray(out_f), np.asarray(out_u))
        pool = pool_u


def _run_adaptive_parity(kind, B, P, page, steps, seed=1, renorm_at=None,
                         apool=None, start_pos=0):
    key = jax.random.PRNGKey(seed)
    core = paged_kv.adaptive_core(f"{kind}_adaptive", B, P)
    if renorm_at is not None:
        core = dataclasses.replace(core, renorm_at=renorm_at)
    if apool is None:
        apool = paged_kv.AdaptivePagedPool(
            pool=paged_kv.init_pool(B, P, page, KVD, jnp.float32),
            policy=core.init())
    for pos_i in range(start_pos, start_pos + steps):
        pos = jnp.int32(pos_i)
        key, sub = jax.random.split(key)
        q, nk, nv = _rand_step(sub, B)
        out_u, mass_u, ap_u = _unfused_adaptive_step(apool, q, nk, nv, pos,
                                                     page, core)
        out_f, mass_f, ap_f = paged_kv.fused_adaptive_decode_step(
            apool, q, nk, nv, pos, page, core)
        _assert_bitwise(f"{kind} pos={pos_i}", ap_f.pool, ap_u.pool)
        _assert_bitwise(f"{kind} pos={pos_i}", ap_f.policy, ap_u.policy)
        assert np.array_equal(np.asarray(mass_f), np.asarray(mass_u))
        assert np.array_equal(np.asarray(out_f), np.asarray(out_u))
        apool = ap_u
    return apool


# -- fast default-CI smoke ---------------------------------------------------


@pytest.mark.parametrize("policy", ["awrp", "lru"])
def test_flat_fused_parity_smoke(policy):
    """Fused flat kernel bit-identical to insert+attend+score past pool
    capacity (evictions exercised)."""
    P, page = 4, 4
    _run_flat_parity(policy, B=2, P=P, page=page, steps=P * page + 2 * page)


@pytest.mark.parametrize("kind", ["arc"])
def test_adaptive_fused_parity_smoke(kind):
    """Fused arc kernel bit-identical through churn (more distinct pages
    than pool slots -> complete misses + in-decode hits)."""
    P, page = 3, 4
    _run_adaptive_parity(kind, B=2, P=P, page=page, steps=(P + 3) * page)


def test_fused_attention_matches_plain_softmax_reference():
    """Fused attention output/mass also agree with the non-flash
    ``ref_paged_attention`` oracle (allclose: different summation order)."""
    B, P, page = 2, 4, 4
    key = jax.random.PRNGKey(7)
    pool = paged_kv.init_pool(B, P, page, KVD, jnp.float32)
    for pos_i in range(10):
        pos = jnp.int32(pos_i)
        key, sub = jax.random.split(key)
        q, nk, nv = _rand_step(sub, B)
        out_f, mass_f, pool_f = paged_kv.fused_decode_step(
            pool, q, nk, nv, pos, page, "awrp")
        _, _, pool = _unfused_flat_step(pool, q, nk, nv, pos, page, "awrp")
        out_r, mass_r = ref.ref_paged_attention(
            q, pool.k.reshape(B, P, page, KVH, HD),
            pool.v.reshape(B, P, page, KVH, HD),
            pool.page_start, jnp.full((B,), pos, jnp.int32))
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(mass_f), np.asarray(mass_r),
                                   rtol=2e-5, atol=2e-5)


def test_renorm_edge_parity():
    """The PR 3 stamp-renormalization ``lax.cond`` fires identically inside
    the kernel (small renorm_at forces it within a short trace)."""
    _run_adaptive_parity("arc", B=2, P=3, page=4, steps=4 * 4,
                         renorm_at=40)


def test_ghost_churn_seeded_parity():
    """A ghost-churn seeded state (cross-request reseed with adapted ``p``
    and populated ghost directory) decodes identically fused vs unfused."""
    B, P, page = 2, 3, 4
    core = paged_kv.adaptive_core("arc_adaptive", B, P)
    # churn with RE-REFERENCES: hits move pages to T2, later misses then
    # demote to the ghost lists, and the reseed replay ghost-hits move p
    churned, gh = paged_kv.replay_page_ids(
        core.init(), "arc_adaptive", P, [0, 1, 2, 0, 1, 3, 2, 4, 0, 5, 1])
    assert np.all(np.asarray(gh) > 0)  # churn produced real ghost hits
    n_have, n_res = 2 * P, P
    state, _ = paged_kv.reseed_from_ghosts(churned, "arc_adaptive", P,
                                           n_have, n_res)
    assert np.any(np.asarray(state.p) != 0.0)  # p adapted
    assert np.any(np.asarray(state.tag) >= 3)  # ghost directory populated
    # pool residency matching the reseed target (pool_from_prefill layout)
    start_tok = (n_have - n_res) * page
    order = jnp.arange(P, dtype=jnp.int32)
    key = jax.random.PRNGKey(5)
    pool = paged_kv.PagedPool(
        k=jax.random.normal(key, (B, P, page, KVD), jnp.float32) * 0.3,
        v=jax.random.normal(key, (B, P, page, KVD), jnp.float32) * 0.3,
        f=jnp.broadcast_to(jnp.ones((P,), jnp.int32), (B, P)),
        r=jnp.broadcast_to(order + 1, (B, P)),
        page_start=jnp.broadcast_to(start_tok + order * page, (B, P)),
        clock=jnp.full((B,), n_res, jnp.int32),
        open_slot=jnp.full((B,), n_res - 1, jnp.int32),
    )
    apool = paged_kv.AdaptivePagedPool(pool=pool, policy=state)
    _run_adaptive_parity("arc", B=B, P=P, page=page, steps=2 * page,
                         apool=apool, start_pos=n_have * page)


def test_fused_mesh_parity_1dev():
    """mesh(1) keeps the shard_map fused path covered in tier-1."""
    mesh = _mesh_or_skip(1)
    B, P, page = 2, 3, 4
    key = jax.random.PRNGKey(3)
    pool = paged_kv.init_pool(B, P, page, KVD, jnp.float32)
    pool_m = pool
    for pos_i in range(page + 1):
        pos = jnp.int32(pos_i)
        key, sub = jax.random.split(key)
        q, nk, nv = _rand_step(sub, B)
        out_1, mass_1, pool = paged_kv.fused_decode_step(
            pool, q, nk, nv, pos, page, "awrp")
        out_m, mass_m, pool_m = paged_kv.fused_decode_step(
            pool_m, q, nk, nv, pos, page, "awrp", mesh=mesh)
        assert np.array_equal(np.asarray(out_1), np.asarray(out_m))
        _assert_bitwise(f"mesh1 pos={pos_i}", pool_m, pool)


@pytest.mark.parametrize("n_dev", [2, 8])
def test_fused_mesh_parity_multidev(n_dev):
    """Fused kernel under shard_map at 2/8 devices: flat AND adaptive
    outputs + planes bitwise equal to the unsharded fused run."""
    mesh = _mesh_or_skip(n_dev)
    B, P, page = 8, 3, 4
    key = jax.random.PRNGKey(4)
    core = paged_kv.adaptive_core("car_adaptive", B, P)
    pool = paged_kv.init_pool(B, P, page, KVD, jnp.float32)
    ap = paged_kv.AdaptivePagedPool(pool=pool, policy=core.init())
    pool_m, ap_m = pool, ap
    for pos_i in range(page + 2):
        pos = jnp.int32(pos_i)
        key, sub = jax.random.split(key)
        q, nk, nv = _rand_step(sub, B)
        _, _, pool = paged_kv.fused_decode_step(pool, q, nk, nv, pos, page,
                                                "awrp")
        _, _, pool_m = paged_kv.fused_decode_step(pool_m, q, nk, nv, pos,
                                                  page, "awrp", mesh=mesh)
        _, _, ap = paged_kv.fused_adaptive_decode_step(ap, q, nk, nv, pos,
                                                       page, core)
        _, _, ap_m = paged_kv.fused_adaptive_decode_step(
            ap_m, q, nk, nv, pos, page, core, mesh=mesh)
        _assert_bitwise(f"mesh{n_dev} flat pos={pos_i}", pool_m, pool)
        _assert_bitwise(f"mesh{n_dev} pool pos={pos_i}", ap_m.pool, ap.pool)
        _assert_bitwise(f"mesh{n_dev} state pos={pos_i}", ap_m.policy,
                        ap.policy)


def test_model_decode_step_fused_parity():
    """End-to-end ``decode_step(fused=True)``: pool planes bitwise equal and
    logits allclose to the unfused model path (decode_attend's plain softmax
    vs the kernel's flash recurrence — numerics, not decisions, differ)."""
    from repro.configs.base import load_smoke_config
    from repro.models import model as M

    cfg = load_smoke_config("gemma3_27b")
    cfg = dataclasses.replace(cfg, dtype="float32", param_dtype="float32",
                              bounded_kv_pages=3, page_size=8)
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    batch = {"tokens": jnp.asarray(np.arange(1, 17)[None], jnp.int32)}
    _, caches_u = M.prefill(params, cfg, batch, max_len=128, kv_mode="paged")
    _, caches_f = M.prefill(params, cfg, batch, max_len=128, kv_mode="paged")
    du = jax.jit(lambda p, t, c: M.decode_step(p, cfg, t, c,
                                               kv_mode="paged"))
    df = jax.jit(lambda p, t, c: M.decode_step(p, cfg, t, c, kv_mode="paged",
                                               fused=True))
    tok = jnp.asarray([[5]], jnp.int32)
    for step in range(10):
        lg_u, caches_u = du(params, tok, caches_u)
        lg_f, caches_f = df(params, tok, caches_f)
        pu = [leaf for leaf in jax.tree.leaves(caches_u["blocks"])
              if leaf.dtype == jnp.int32]
        pf = [leaf for leaf in jax.tree.leaves(caches_f["blocks"])
              if leaf.dtype == jnp.int32]
        assert pu and len(pu) == len(pf)
        for a, b in zip(pu, pf):
            assert np.array_equal(np.asarray(a), np.asarray(b)), step
        np.testing.assert_allclose(np.asarray(lg_u), np.asarray(lg_f),
                                   rtol=2e-3, atol=2e-3)
        tok = jnp.argmax(lg_u[:, -1:], -1).astype(jnp.int32)


def test_engine_fused_generates():
    """ServeEngine(fused=True) serves a paged request end to end through
    the donated jitted decode loop."""
    from repro.configs.base import load_smoke_config
    from repro.models import model as M
    from repro.serve.engine import Request, ServeEngine

    cfg = load_smoke_config("gemma3_27b")
    cfg = dataclasses.replace(cfg, dtype="float32", param_dtype="float32",
                              bounded_kv_pages=3, page_size=8)
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    eng = ServeEngine(cfg, params, max_len=128, kv_mode="paged", fused=True)
    out = eng.generate([Request(0, list(range(1, 17)), max_new_tokens=30)])
    assert len(out[0].tokens) == 30  # past 3*8=24 resident tokens


@pytest.mark.parametrize("kv_policy", ["awrp", "arc_adaptive"])
def test_engine_fused_agrees_with_unfused(kv_policy):
    """Fused and unfused engines serve identical tokens and count identical
    paged-KV hits and evictions (``kv/pool/...``) past pool capacity."""
    from repro.configs.base import load_smoke_config
    from repro.models import model as M
    from repro.serve.engine import Request, ServeEngine

    cfg = load_smoke_config("gemma3_27b")
    cfg = dataclasses.replace(cfg, dtype="float32", param_dtype="float32",
                              bounded_kv_pages=3, page_size=8,
                              kv_policy=kv_policy)
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    seen = []
    for fused in (False, True):
        eng = ServeEngine(cfg, params, max_len=128, kv_mode="paged",
                          fused=fused)
        out = eng.generate([Request(i, list(range(1 + i, 17 + i)),
                                    max_new_tokens=30) for i in range(2)])
        tel = eng.telemetry()
        seen.append(([out[i].tokens for i in range(2)],
                     int(tel["kv/pool/hits"]), int(tel["kv/pool/evictions"])))
    assert seen[0] == seen[1]
    assert seen[0][1] > 0 and seen[0][2] > 0  # references and evictions ran


@pytest.mark.parametrize("policy", ["awrp", "lru", "fifo", "lfu", "arc", "car"])
def test_fused_step_dispatches_fewer_eqns_than_unfused(policy):
    """The fused kernel folds victim selection, the metadata scatters,
    attention and the score update into one ``pallas_call`` (plus the K/V
    row scatter): its decode step's jaxpr has fewer equations than the
    unfused chain's, counted as ``count_eqns`` counts them (a kernel launch
    is one)."""
    from repro.obs.profiling import count_eqns

    B, P, page = 2, 4, 4
    q, nk, nv = _rand_step(jax.random.PRNGKey(1), B)
    carry = paged_kv.init_pool(B, P, page, KVD, jnp.float32)
    if policy in ("arc", "car"):
        core = paged_kv.adaptive_core(f"{policy}_adaptive", B, P)
        carry = paged_kv.AdaptivePagedPool(pool=carry, policy=core.init())

        def fused(c, q, nk, nv, pos):
            return paged_kv.fused_adaptive_decode_step(c, q, nk, nv, pos,
                                                       page, core)

        def unfused(c, q, nk, nv, pos):
            return _unfused_adaptive_step(c, q, nk, nv, pos, page, core)
    else:
        def fused(c, q, nk, nv, pos):
            return paged_kv.fused_decode_step(c, q, nk, nv, pos, page,
                                              policy)

        def unfused(c, q, nk, nv, pos):
            return _unfused_flat_step(c, q, nk, nv, pos, page, policy)

    args = (carry, q, nk, nv, jnp.int32(0))
    n_fused = count_eqns(jax.make_jaxpr(fused)(*args))
    n_unfused = count_eqns(jax.make_jaxpr(unfused)(*args))
    assert n_fused < n_unfused, (policy, n_fused, n_unfused)


# -- nightly full parity grid (PR 2 split) -----------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("policy", ["awrp", "lru", "fifo", "lfu"])
@pytest.mark.parametrize("B,P,page", [(1, 4, 4), (3, 4, 8), (2, 5, 4)])
def test_flat_fused_parity_grid(policy, B, P, page):
    """Nightly: every flat policy × mixed shapes/capacities, full eviction
    pressure."""
    _run_flat_parity(policy, B=B, P=P, page=page, steps=P * page + 2 * page,
                     seed=hash((policy, B, P)) % 1000)


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["arc", "car"])
@pytest.mark.parametrize("B,P", [(1, 2), (2, 3), (2, 5)])
def test_adaptive_fused_parity_grid(kind, B, P):
    """Nightly: arc AND car across mixed capacities, churn past capacity."""
    page = 4
    _run_adaptive_parity(kind, B=B, P=P, page=page, steps=(P + 3) * page,
                         seed=P)


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["arc", "car"])
def test_renorm_edge_parity_grid(kind):
    """Nightly: the renormalization cond edge for both adaptive kinds."""
    _run_adaptive_parity(kind, B=2, P=3, page=4, steps=5 * 4, renorm_at=36)
