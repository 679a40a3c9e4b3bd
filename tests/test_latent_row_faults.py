"""The ``serve.moonlight.longctx`` cell's check catches a latent row that
the decode step leaves stale or writes into the wrong page, under eviction.

The faults are planted in ``paged_kv.insert_latent`` as the decode step
calls it, with ``layer``: the latent pool's bookkeeping (F, R, page starts,
the open page) advances as it does, and only the row write into the carried
layer stack of latent rows goes wrong.  The cell runs at its CPU rehearsal
size against its own reference and limits; the same run with nothing
planted reads correct.
"""

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from repro.cache import paged_kv  # noqa: E402

CELL = "serve.moonlight.longctx"


@pytest.fixture
def jax_config():
    """The cell turns on the persistent compile cache; put it back."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)


def _plant(monkeypatch, alter):
    """``insert_latent`` as it is, then ``alter(old, new, row, pos, page,
    layer)`` gives the stack of latent rows left in the pool."""
    real = paged_kv.insert_latent

    def altered(pool, row, pos, page_size, policy="awrp", layer=()):
        new = real(pool, row, pos, page_size, policy, layer)
        return new._replace(c=alter(pool, new, row, pos, page_size, layer))

    monkeypatch.setattr(paged_kv, "insert_latent", altered)


def _row_left_stale(old, new, row, pos, page, layer):
    return old.c


def _page_opening_row_in_the_next_page(old, new, row, pos, page, layer):
    """The row that opens a page goes to the page after it; the page's
    other rows go where they belong."""
    B, P = old.f.shape
    bidx, slot = np.arange(B), new.open_slot
    moved = (new.c.at[(*layer, bidx, slot, 0)].set(0)
             .at[(*layer, bidx, (slot + 1) % P, 0)].set(row))
    return jnp.where(pos % page == 0, moved, new.c)


@pytest.mark.parametrize("fault", [None, _row_left_stale,
                                   _page_opening_row_in_the_next_page],
                         ids=lambda f: f.__name__ if f else "none")
def test_a_latent_row_fault_reads_not_correct(fault, monkeypatch, jax_config):
    if fault is not None:
        _plant(monkeypatch, fault)
    jax.clear_caches()
    out, record = harness.run_cell(CELL, 31337, 0.2, False, rehearse=True)
    jax.clear_caches()
    assert record["evictions"] > 0
    assert out["correct"] is (fault is None), out["checks"]
