"""Property tests for the AWRP paged-KV pool (the paper's technique applied
to serving) — invariants under arbitrary decode streams."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, st  # hypothesis, or fallback shim

from repro.cache import paged_kv
from repro.core.kv_policy import PAGE_POLICIES, page_victim


def _drive(pool, steps, page_size, kvd, policy="awrp", seed=0):
    rng = np.random.RandomState(seed)
    for pos in range(steps):
        nk = jnp.asarray(rng.randn(pool.f.shape[0], kvd), jnp.float32)
        nv = jnp.asarray(rng.randn(pool.f.shape[0], kvd), jnp.float32)
        pool = paged_kv.insert_token(pool, nk, nv, jnp.asarray(pos, jnp.int32),
                                     page_size, policy=policy)
        # synthetic attention mass: random but normalized per sequence
        mass = rng.rand(pool.f.shape[0], pool.f.shape[1] * page_size)
        mass = mass / mass.sum(-1, keepdims=True)
        pool = paged_kv.score_update(pool, jnp.asarray(mass, jnp.float32),
                                     page_size)
    return pool


def _check_pool_invariants(pages, page_size, steps, policy):
    B, kvd = 2, 8
    pool = paged_kv.init_pool(B, pages, page_size, kvd, jnp.float32)
    pool = _drive(pool, steps, page_size, kvd, policy=policy)
    ps = np.asarray(pool.page_start)
    f = np.asarray(pool.f)
    r = np.asarray(pool.r)
    clock = np.asarray(pool.clock)
    resident = ps >= 0
    # residency bounded and equals min(pages written, pool size)
    pages_written = (steps + page_size - 1) // page_size
    assert (resident.sum(-1) == min(pages_written, pages)).all()
    # page starts are page-aligned and within the stream
    assert (ps[resident] % page_size == 0).all()
    assert (ps[resident] < steps).all()
    # the OPEN page (latest) must always be resident — never evicted (pinned)
    open_start = ((steps - 1) // page_size) * page_size
    assert ((ps == open_start).sum(-1) == 1).all()
    # clock ticks once per decode step
    assert (clock == steps).all()
    # paper metadata sanity: F >= 1 on residents, R <= clock
    assert (f[resident] >= 1).all()
    assert (r[resident] <= steps).all()


@settings(max_examples=5, deadline=None)
@given(
    pages=st.integers(min_value=2, max_value=6),
    page_size=st.integers(min_value=2, max_value=8),
    steps=st.integers(min_value=1, max_value=60),
    policy=st.sampled_from(PAGE_POLICIES),
)
def test_pool_invariants_under_decode_stream(pages, page_size, steps, policy):
    """Trimmed default-run variant (each example drives a full decode
    stream, ~0.7s; the nightly variant below samples 3x more)."""
    _check_pool_invariants(pages, page_size, steps, policy)


@pytest.mark.slow
@settings(max_examples=15, deadline=None)
@given(
    pages=st.integers(min_value=2, max_value=6),
    page_size=st.integers(min_value=2, max_value=8),
    steps=st.integers(min_value=1, max_value=60),
    policy=st.sampled_from(PAGE_POLICIES),
)
def test_pool_invariants_under_decode_stream_full(pages, page_size, steps, policy):
    _check_pool_invariants(pages, page_size, steps, policy)


# ---------------------------------------------------------------------------
# page_victim: decision parity with the pre-port argmin formulation
# ---------------------------------------------------------------------------


def _page_victim_argmin_reference(policy, f, r, page_start, clock, pinned):
    """The original argmin-based page_victim (reference for the min-reduction
    port — kept verbatim so the switch is provably decision-identical)."""
    INT_MAX = 2**31 - 1
    from repro.core.jax_policies import awrp_weights

    valid = (page_start >= 0) & ~pinned
    if policy == "awrp":
        w = awrp_weights(f, r, clock[:, None])
        return jnp.argmin(jnp.where(valid, w, jnp.inf), axis=-1).astype(jnp.int32)
    if policy == "lru":
        return jnp.argmin(jnp.where(valid, r, INT_MAX), axis=-1).astype(jnp.int32)
    if policy == "fifo":
        return jnp.argmin(
            jnp.where(valid, page_start, INT_MAX), axis=-1
        ).astype(jnp.int32)
    if policy == "lfu":
        fm = jnp.where(valid, f, INT_MAX)
        minf = jnp.min(fm, axis=-1, keepdims=True)
        cand = fm == minf
        return jnp.argmin(jnp.where(cand, r, INT_MAX), axis=-1).astype(jnp.int32)
    raise ValueError(policy)


@settings(max_examples=12, deadline=None)
@given(
    P=st.sampled_from([3, 7, 8]),  # few shapes -> jit caches across examples
    seed=st.integers(min_value=0, max_value=2000),
)
def test_page_victim_matches_argmin_reference(P, seed):
    """Min-reduction chain == the old argmin formulation, including engineered
    weight/recency ties and pinned/free lanes (first-index tie-break)."""
    rng = np.random.RandomState(seed)
    B = 4
    # tiny value ranges force frequent exact ties in W = F/(N-R), r and f
    f = jnp.asarray(rng.randint(1, 4, size=(B, P)), jnp.int32)
    r = jnp.asarray(rng.randint(0, 6, size=(B, P)), jnp.int32)
    starts = jnp.asarray(rng.randint(0, 4, size=(B, P)) * 4, jnp.int32)
    clock = jnp.asarray(rng.randint(6, 10, size=(B,)), jnp.int32)
    starts = jnp.where(jnp.asarray(rng.rand(B, P) < 0.2), -1, starts)
    pinned = jnp.asarray(rng.rand(B, P) < 0.2)
    for policy in ("awrp", "lru", "fifo", "lfu"):
        got = np.asarray(page_victim(policy, f, r, starts, clock, pinned))
        want = np.asarray(
            _page_victim_argmin_reference(policy, f, r, starts, clock, pinned)
        )
        np.testing.assert_array_equal(got, want, err_msg=policy)


def test_page_victim_arc_car_segment_semantics():
    """Serving-layer arc/car: once-referenced (T1-analog) pages evict before
    multiply-referenced ones; arc orders the segment by recency, car by
    insertion (clock) order; both fall back to the hot segment when every
    page is hot."""
    f = jnp.asarray([[3, 1, 1, 2]], jnp.int32)
    r = jnp.asarray([[9, 5, 3, 2]], jnp.int32)
    starts = jnp.asarray([[0, 12, 8, 4]], jnp.int32)
    clock = jnp.asarray([10], jnp.int32)
    pinned = jnp.zeros((1, 4), bool)
    # cold segment = pages 1, 2 (f == 1)
    assert int(page_victim("arc", f, r, starts, clock, pinned)[0]) == 2  # min r
    assert int(page_victim("car", f, r, starts, clock, pinned)[0]) == 2  # min start
    starts2 = jnp.asarray([[0, 8, 12, 4]], jnp.int32)
    assert int(page_victim("car", f, r, starts2, clock, pinned)[0]) == 1
    # all pages hot -> T2-analog: arc == lru, car == fifo
    f_hot = jnp.asarray([[3, 2, 5, 2]], jnp.int32)
    assert int(page_victim("arc", f_hot, r, starts, clock, pinned)[0]) == int(
        page_victim("lru", f_hot, r, starts, clock, pinned)[0]
    )
    assert int(page_victim("car", f_hot, r, starts, clock, pinned)[0]) == int(
        page_victim("fifo", f_hot, r, starts, clock, pinned)[0]
    )


@settings(max_examples=20, deadline=None)
@given(
    P=st.integers(min_value=2, max_value=10),
    seed=st.integers(min_value=0, max_value=1000),
)
def test_page_victim_policies_differ_and_are_valid(P, seed):
    rng = np.random.RandomState(seed)
    B = 3
    f = jnp.asarray(rng.randint(1, 20, size=(B, P)), jnp.int32)
    r = jnp.asarray(rng.randint(0, 50, size=(B, P)), jnp.int32)
    starts = jnp.asarray(rng.randint(0, 1000, size=(B, P)), jnp.int32)
    clock = jnp.asarray(rng.randint(51, 99, size=(B,)), jnp.int32)
    pinned = jnp.zeros((B, P), bool)
    for policy in PAGE_POLICIES:
        v = np.asarray(page_victim(policy, f, r, starts, clock, pinned))
        assert ((0 <= v) & (v < P)).all()
    # lru victim == min r
    v = np.asarray(page_victim("lru", f, r, starts, clock, pinned))
    assert (np.asarray(r)[np.arange(B), v] == np.asarray(r).min(-1)).all()
    # fifo victim == min page_start
    v = np.asarray(page_victim("fifo", f, r, starts, clock, pinned))
    assert (np.asarray(starts)[np.arange(B), v] == np.asarray(starts).min(-1)).all()


def test_pool_eviction_matches_core_awrp_oracle():
    """Drive a pool to eviction and check each eviction picks the same slot
    the numpy AWRP weight rule would (metadata-level equivalence)."""
    from repro.core.jax_policies import awrp_weights

    B, pages, page_size, kvd = 1, 3, 4, 4
    pool = paged_kv.init_pool(B, pages, page_size, kvd, jnp.float32)
    rng = np.random.RandomState(1)
    for pos in range(40):
        prev = pool
        nk = jnp.asarray(rng.randn(B, kvd), jnp.float32)
        pool = paged_kv.insert_token(pool, nk, nk, jnp.asarray(pos, jnp.int32),
                                     page_size)
        mass = rng.rand(B, pages * page_size)
        mass /= mass.sum()
        pool = paged_kv.score_update(pool, jnp.asarray(mass, jnp.float32),
                                     page_size)
        if pos % page_size == 0 and pos >= pages * page_size:
            # an eviction happened at this allocation: the evicted slot is
            # where page_start changed; verify it was argmin W (excl. pinned)
            changed = np.asarray(prev.page_start != pool.page_start)[0]
            assert changed.sum() == 1
            w = np.array(awrp_weights(prev.f, prev.r, prev.clock[:, None]))[0].copy()
            pin = int(np.asarray(prev.open_slot)[0])
            w[pin] = np.inf
            assert int(np.argmin(w)) == int(np.flatnonzero(changed)[0])


@pytest.mark.parametrize("policy", ["awrp", "lru"])
def test_step_counts_match_reference_rule_and_evictions(policy):
    """``step_counts`` counts exactly the pages the reference rule marked
    and one eviction per page-boundary allocation into a full pool."""
    B, P, page, kvd = 2, 3, 4, 8
    pool = paged_kv.init_pool(B, P, page, kvd, jnp.float32)
    key = jax.random.PRNGKey(2)
    total_ev = 0
    for pos in range(P * page + 2 * page + 1):
        key, k1, k2 = jax.random.split(key, 3)
        row = jax.random.normal(k1, (B, kvd))
        ins = paged_kv.insert_token(pool, row, row, jnp.int32(pos), page,
                                    policy=policy)
        mass = jax.random.uniform(k2, (B, P * page)) / page
        new = paged_kv.score_update(ins, mass, page)
        hits, ev = paged_kv.step_counts(pool, new)
        want = paged_kv.referenced_pages(ins, mass, page).sum()
        assert int(hits) == int(want)
        full_alloc = pos % page == 0 and pos >= P * page
        assert int(ev) == (B if full_alloc else 0), pos
        total_ev += int(ev)
        pool = new
    assert total_ev == 3 * B  # allocations at P*page, +page, +2*page
    # a tree holding the pool twice counts it twice; no pool counts nothing
    h2, e2 = paged_kv.step_counts({"a": pool, "b": [pool]},
                                  {"a": pool, "b": [pool]})
    assert int(e2) == 0 and int(h2) == 2 * int(paged_kv.step_counts(
        pool, pool)[0])
    assert tuple(map(int, paged_kv.step_counts({}, {}))) == (0, 0)
