"""Unified policy core (repro.core.policy_core): protocol semantics, host-
oracle parity of the incremental API, masked accesses, advisory victims, and
stamp renormalization (the long-run safety mechanism that replaced the
engine's trace-length rejection guard)."""

import numpy as np
import pytest
from _propcheck import given, settings, st  # hypothesis, or fallback shim

from repro.core import make_policy
from repro.core.jax_policies import simulate_trace_batched
from repro.core.policy_core import (
    ADAPTIVE_POLICIES,
    DEVICE_POLICIES,
    INT_MAX,
    JAX_POLICIES,
    POLICY_IDS,
    AdaptiveCore,
    FlatCore,
    _round_quotient,
    div_rn,
    init,
    make_core,
)


def host_hits_rows(policy, streams, capacity, num_sets=1):
    """Per-row host-oracle hit bits: streams is (rows, T); each row is an
    independent policy instance (num_sets oracle instances per row)."""
    out = []
    for row in streams:
        insts = {s: make_policy(policy, capacity // num_sets)
                 for s in range(num_sets)}
        out.append([insts[int(b) % num_sets].access(int(b)) for b in row])
    return np.asarray(out, dtype=bool)


def drive(core, state, streams):
    """Run (rows, T) streams through the incremental protocol; returns the
    final state and the (rows, T) hit bits.  Jitted per core, as a serving
    caller would hold it (the core is static; one compile per stream shape)."""
    import jax

    step = jax.jit(core.on_access)
    hits = []
    for t in range(streams.shape[1]):
        state, h = step(state, streams[:, t])
        hits.append(np.asarray(h))
    return state, np.stack(hits, axis=1)


# ---------------------------------------------------------------------------
# exact float32 division (the TPU divide is not correctly rounded)
# ---------------------------------------------------------------------------


def _division_operands():
    """Every count pair up to 700, plus random counts up to 2**24 and large
    normal floats: the decision quotients' domain and beyond it."""
    a = np.arange(700, dtype=np.float32)
    x, y = np.meshgrid(a, a + 1)
    rng = np.random.RandomState(0)
    xr = rng.randint(0, 1 << 24, 200_000).astype(np.float32)
    yr = rng.randint(1, 1 << 24, 200_000).astype(np.float32)
    xf = (rng.rand(20_000) * 2.0**40).astype(np.float32)
    yf = (rng.rand(20_000) * 2.0**20 + 1).astype(np.float32)
    # quotients at and next to powers of two, where the float spacing
    # changes: y * 2**k and its neighbours
    ye = rng.randint(1, 1 << 24, 4_000).astype(np.float32)
    bits = (ye * np.float32(2.0**rng.randint(-8, 9, ye.size))).view(np.int32)
    xe = (bits[:, None] + np.arange(-3, 4)).astype(np.int32).view(np.float32)
    return (np.concatenate([x.ravel(), xr, xf, xe.ravel()]),
            np.concatenate([y.ravel(), yr, yf, np.repeat(ye, 7)]))


def test_div_rn_is_ieee_division():
    import jax

    x, y = _division_operands()
    got = np.asarray(jax.jit(div_rn)(x, y))
    np.testing.assert_array_equal(got.view(np.int32), (x / y).view(np.int32))


@pytest.mark.parametrize("ulps", [-2, -1, 1, 2])
def test_div_rn_corrects_an_inexact_quotient(ulps):
    """What a TPU divide returns — a quotient a few ulp off — comes out
    correctly rounded: ``_round_quotient`` is fed IEEE quotients shifted by
    ``ulps`` (where that stays positive)."""
    import jax

    x, y = _division_operands()
    want = x / y
    bits = want.view(np.int32)
    q0 = np.where(x > 0, bits + ulps, bits).astype(np.int32).view(np.float32)
    got = np.asarray(jax.jit(_round_quotient)(x, y, q0))
    np.testing.assert_array_equal(got.view(np.int32), bits)


# ---------------------------------------------------------------------------
# protocol: init / on_access / victim
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", DEVICE_POLICIES)
def test_incremental_on_access_matches_host_oracles(policy):
    """core, state = init(policy, rows, sets, ways); repeated on_access ==
    the host oracle, row by row, access for access — the serving-side use
    (paged pools, expert caches) of the exact machinery the sweep scans."""
    rng = np.random.RandomState(7)
    streams = rng.randint(0, 24, size=(3, 160)).astype(np.int32)
    core, state = init(policy, rows=3, num_sets=1, ways=6)
    _, hits = drive(core, state, streams)
    assert (hits == host_hits_rows(policy, streams, 6)).all()


@pytest.mark.parametrize("policy", JAX_POLICIES)
def test_incremental_set_associative_matches_host(policy):
    rng = np.random.RandomState(11)
    streams = rng.randint(0, 40, size=(2, 200)).astype(np.int32)
    core, state = init(policy, rows=2, num_sets=4, ways=3)  # capacity 12
    _, hits = drive(core, state, streams)
    assert (hits == host_hits_rows(policy, streams, 12, num_sets=4)).all()


def test_core_equals_batched_engine():
    """The engine IS a scan over on_access: incremental driving reproduces
    simulate_trace_batched bit-for-bit for every device policy."""
    rng = np.random.RandomState(3)
    tr = rng.randint(0, 30, size=300)
    eng = np.asarray(simulate_trace_batched(tr, DEVICE_POLICIES, [8]))
    for pi, policy in enumerate(DEVICE_POLICIES):
        core, state = init(policy, rows=1, num_sets=1, ways=8)
        _, hits = drive(core, state, tr[None, :].astype(np.int32))
        assert (hits[0] == eng[0, pi, 0]).all(), policy


@pytest.mark.parametrize("policy", DEVICE_POLICIES)
def test_victim_predicts_next_eviction(policy):
    """victim(state) names the lane the next complete miss actually evicts
    (flat cores: also the fill lane; adaptive cores: -1 until full)."""
    rng = np.random.RandomState(5)
    core, state = init(policy, rows=2, num_sets=1, ways=4)
    if policy in ADAPTIVE_POLICIES:
        v0 = np.asarray(core.victim(state))
        assert (v0[:, 0] == -1).all()  # empty cache: nothing to evict
    streams = rng.randint(0, 10, size=(2, 60)).astype(np.int32)
    state, _ = drive(core, state, streams)
    v = np.asarray(core.victim(state))
    fresh = np.asarray([1000, 2000], np.int32)  # complete misses everywhere
    new_state, _ = core.on_access(state, fresh)
    if policy in ADAPTIVE_POLICIES:
        res_b = np.asarray(core.resident_mask(state))[:, 0]
        res_a = np.asarray(core.resident_mask(new_state))[:, 0]
        for b in range(2):
            evicted = np.flatnonzero(res_b[b] & ~res_a[b])
            assert evicted.size == 1
            assert v[b, 0] == evicted[0]
    else:
        changed_blocks = np.asarray(new_state.blocks) == fresh[:, None]
        for b in range(2):
            assert changed_blocks[b, int(v[b])]


def test_active_masking_is_a_noop():
    """Rows with active=False keep their state bit-for-bit, tick no clock,
    and report no hit — the serving callers' masked-access contract."""
    rng = np.random.RandomState(2)
    streams = rng.randint(0, 12, size=(2, 50)).astype(np.int32)
    for policy in DEVICE_POLICIES:
        import jax

        core, state = init(policy, rows=2, num_sets=1, ways=4)
        state, _ = drive(core, state, streams)
        frozen = state
        mask = np.asarray([True, False])
        step = jax.jit(lambda st, ids: core.on_access(st, ids, active=mask))
        for t in range(20):
            ids = np.asarray([int(streams[0, t]), 7], np.int32)
            state, h = step(state, ids)
            assert not bool(np.asarray(h)[1])
        for a, b in zip(jax_leaves(state), jax_leaves(frozen)):
            np.testing.assert_array_equal(np.asarray(a)[1], np.asarray(b)[1])


def jax_leaves(tree):
    import jax

    return jax.tree.leaves(tree)


def test_factory_validation():
    with pytest.raises(ValueError, match="not a device policy"):
        make_core("2q", rows=1, num_sets=1, ways=4)
    with pytest.raises(ValueError, match="FlatCore supports"):
        FlatCore(pids=(POLICY_IDS["arc"],), ways=(4,))
    with pytest.raises(ValueError, match="AdaptiveCore supports"):
        AdaptiveCore(kind="lru", caps=(4,))
    with pytest.raises(NotImplementedError):
        core = AdaptiveCore(kind="arc", caps=(4,), num_sets=2)
        core.victim(core.init())


# ---------------------------------------------------------------------------
# stamp renormalization (replaces the old trace-length rejection guard)
# ---------------------------------------------------------------------------


def test_renorm_near_int32_parity_and_reset():
    """Push an adaptive state's stamps/ctr to the int32 brink mid-stream
    (order-preserving offset), keep going: decisions must keep matching the
    host oracle and the counter must come back down (proof a renormalization
    actually fired, not just survived)."""
    import jax

    rng = np.random.RandomState(13)
    streams = rng.randint(0, 14, size=(1, 400)).astype(np.int32)
    for policy in ADAPTIVE_POLICIES:
        ref = host_hits_rows(policy, streams, 5)
        core, state = init(policy, rows=1, num_sets=1, ways=5)
        step = jax.jit(core.on_access)
        hits = []
        for t in range(streams.shape[1]):
            if t == 200:  # shift to the brink; relative stamp order unchanged
                shift = np.int32(core.renorm_at - int(np.asarray(state.ctr).max()))
                state = state._replace(
                    stamp=state.stamp + shift, ctr=state.ctr + shift
                )
            state, h = step(state, streams[:, t])
            hits.append(bool(np.asarray(h)[0]))
        assert (np.asarray(hits) == ref[0]).all(), policy
        ctr = int(np.asarray(state.ctr)[0, 0])
        assert ctr < core.renorm_at  # renormalized back into safe range
        assert ctr < 10_000  # ...all the way down, not merely below the line


@settings(max_examples=8, deadline=None)
@given(
    trace=st.lists(
        st.integers(min_value=0, max_value=16), min_size=150, max_size=150
    ),
    cap=st.sampled_from([3, 5]),
)
def test_property_forced_renormalization_engine_parity(trace, cap):
    """Engine-level: a renormalization threshold low enough to fire every
    few accesses (the regime the deleted trace-length guard used to reject)
    leaves the batched engine bit-identical to the host oracles."""
    tr = np.asarray(trace, dtype=np.int64)
    hits = np.asarray(
        simulate_trace_batched(tr, ADAPTIVE_POLICIES, [cap], _renorm_at=64)
    )
    for pi, pol in enumerate(ADAPTIVE_POLICIES):
        ref = host_hits_rows(pol, tr[None, :], cap)
        divergence = np.flatnonzero(hits[0, pi, 0] != ref[0])
        assert divergence.size == 0, (
            f"{pol} cap={cap}: first divergence at access {divergence[0]}"
        )


def test_long_trace_no_rejection():
    """The engine accepts adaptive traces of any length (the old guard at
    ~int32/(ways+2) accesses raised); renormalization makes them safe."""
    tr = np.arange(500) % 9
    # would renormalize ~8 times at this threshold; must stay bit-exact
    hits = np.asarray(
        simulate_trace_batched(tr, ["arc", "car"], [4], _renorm_at=200)
    )
    for pi, pol in enumerate(["arc", "car"]):
        ref = host_hits_rows(pol, tr[None, :], 4)
        assert (hits[0, pi, 0] == ref[0]).all(), pol
