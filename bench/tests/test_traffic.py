"""The copied trace generators keep the program's parameters, and every
mix makes the same inputs from the same seed."""

import inspect

import numpy as np
import pytest

from bench import traffic
from repro.core import traces as prog


#: the program's generator of each family and the name of its length
PROGRAM = {"paper": (prog.paper_trace, "n"),
           "zipf": (prog.trace_zipf, "n_accesses"),
           "scan_mix": (prog.trace_scan_mix, "n_accesses"),
           "markov": (prog.trace_markov, "n_accesses")}


def _defaults(fn, skip):
    return {k: p.default for k, p in inspect.signature(fn).parameters.items()
            if k not in skip}


def test_every_family_has_a_program_generator():
    assert set(traffic.FAMILIES) == set(PROGRAM)


@pytest.mark.parametrize("family", sorted(traffic.FAMILIES))
def test_generator_keeps_program_parameters(family):
    fn, length = PROGRAM[family]
    ours = _defaults(traffic.FAMILIES[family], {"rng", "n_accesses"})
    theirs = _defaults(fn, {"seed", length})
    assert ours == theirs
    for name in ("paper64", "paper1024"):
        for fam in traffic.load_mix(name)["families"]:
            if fam["family"] == family:
                assert {k: v for k, v in fam.items()
                        if k != "family"} == theirs


@pytest.mark.parametrize("family", ["paper", "zipf", "scan_mix"])
@pytest.mark.parametrize("seed", [0, 7, 12345])
@pytest.mark.parametrize("n", [1000, 5000])
def test_copied_generator_is_the_program_generator(family, seed, n):
    fn, length = PROGRAM[family]
    ours = traffic.FAMILIES[family](np.random.RandomState(seed), n)
    np.testing.assert_array_equal(ours, fn(seed=seed, **{length: n}))


def test_vectorised_markov_matches_the_loop_in_distribution():
    n, p_stay, size, regions = 200_000, 0.95, 64, 8
    ours = traffic.trace_markov(np.random.RandomState(3), n)
    theirs = prog.trace_markov(n, seed=3)
    for t in (ours, theirs):
        region = t // size
        changes = np.mean(region[1:] != region[:-1])
        # a jump lands in a new region with probability 7/8
        assert changes == pytest.approx((1 - p_stay) * (regions - 1)
                                        / regions, rel=0.05)
        assert np.bincount(t % size, minlength=size).min() > n / size * 0.9
        assert t.min() >= 0 and t.max() < regions * size


def test_same_seed_same_inputs_and_large_seeds():
    mix = traffic.load_mix("paper64", rehearsal=True)
    seed = 2**31 + 12345
    a = traffic.make_batches(mix, seed)
    b = traffic.make_batches(mix, seed)
    assert len(a) == mix["batches"] + 1
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], traffic.make_batches(mix, seed + 1)[0])
    assert not np.array_equal(a[0], a[1])
    p = traffic.load_mix("longctx", rehearsal=True)
    pb = traffic.make_batches(p, seed, vocab=512)
    assert pb[0].shape == (p["batch"], p["prompt_len"])
    assert pb[0].min() >= 1 and pb[0].max() < 512
