"""``serve.moonlight.longctx`` on the CPU: its rehearsal reads correct,
faults planted in the expert layer or the latent pool's plane, or a
control served in the program's place, read not correct, and ``bench/moe_sizes.py`` counts what a hand
count gives at the rehearsal size."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, moe_sizes

CELL = "serve.moonlight.longctx"


def _rehearsal_cfg():
    bench = harness.load_benchmark()
    _, entry = harness.find_cell(bench, CELL)
    return harness.load_config(entry, rehearsal=True)


def _run(seed=2**31 + 99):
    jax.clear_caches()
    out, record = harness.run_cell(CELL, seed, 0.2, False, rehearse=True)
    jax.clear_caches()
    return out, record


def test_the_rehearsal_reads_correct_and_counts_the_expert_work():
    out, record = _run()
    assert out["correct"], out["checks"]
    assert record["evictions"] > 0 and record["compiles_in_window"] == 0
    # 2 x 32 prompt tokens x top-3 over 2 expert layers, 4 of 16 held
    assert 0 < record["moe_prefill_held_pairs"] <= record["prefills"] * 2 * 32 * 3 * 2
    assert 0 < record["moe_decode_experts_hit"] <= record["decode_steps"] * 4 * 2


def _patch_moe(monkeypatch, **changes):
    from repro.models import layers as L

    real = L.moe

    def altered(params, x, cfg, **k):
        return real(params, x, dataclasses.replace(cfg, **changes), **k)

    monkeypatch.setattr(L, "moe", altered)


def _no_shared_experts(monkeypatch):
    _patch_moe(monkeypatch, shared_ff=0)


def _experts_at_the_wrong_offset(monkeypatch):
    _patch_moe(monkeypatch, expert_offset=4)


def _patch_insert_latent(monkeypatch, alter):
    """``insert_latent`` allocates and evicts as it does (f, r,
    page_start and the open page advance), then ``alter(old, new, row,
    pos, page_size)`` gives the latent plane left in the pool."""
    from repro.cache import paged_kv

    real = paged_kv.insert_latent

    def altered(pool, row, pos, page_size, policy="awrp"):
        new = real(pool, row, pos, page_size, policy)
        return new._replace(c=alter(pool, new, row, pos, page_size))

    monkeypatch.setattr(paged_kv, "insert_latent", altered)


def _latent_plane_left_stale(monkeypatch):
    _patch_insert_latent(monkeypatch, lambda old, new, row, pos, page: old.c)


def _page_opening_row_in_the_wrong_page(monkeypatch):
    """The row that opens a page goes to the page after it; the page's
    other rows go where they belong."""
    def wrong(old, new, row, pos, page):
        B, P = old.f.shape
        bidx, slot = np.arange(B), new.open_slot
        moved = new.c.at[bidx, slot, 0].set(0).at[bidx, (slot + 1) % P, 0].set(row)
        return jnp.where(pos % page == 0, moved, new.c)

    _patch_insert_latent(monkeypatch, wrong)


def _float8_control_served(monkeypatch):
    """The tokens served are those the float8 reference puts first at each
    position (teacher-forced on the program's own): the control's
    reading becomes the check's."""
    from bench.drivers import serve  # the base the harness's driver extends

    real = serve.Run._generate

    def control(self, prompts):
        served = np.array(real(self, prompts))
        logits, _ = self._reference("float8", prompts, served)
        return logits.argmax(-1).tolist()

    monkeypatch.setattr(serve.Run, "_generate", control)


@pytest.mark.parametrize("plant", [_no_shared_experts,
                                   _experts_at_the_wrong_offset,
                                   _latent_plane_left_stale,
                                   _page_opening_row_in_the_wrong_page,
                                   _float8_control_served],
                         ids=lambda f: f.__name__)
def test_a_planted_fault_reads_not_correct(plant, monkeypatch):
    plant(monkeypatch)
    out, _ = _run(31337)
    assert not out["correct"], out["checks"]


def test_the_sizes_agree_with_a_hand_count_at_the_rehearsal_size():
    """Rehearsal sizes: d 64, 4 heads, latent 32, rope 8, nope 16, v 16,
    dense 96, experts 24 wide, 4 of 16 held, top-3, 2 shared, 3 layers
    (1 dense), vocab 512."""
    m = moe_sizes.sizes(_rehearsal_cfg())
    attn = 64 * 4 * 24 + 64 * 40 + 32 * 4 * 32 + 4 * 16 * 64  # 18944
    assert moe_sizes.attn_params(m) == attn
    w = moe_sizes.weight_params(m)
    assert w == {"attention": 3 * attn, "dense": 3 * 64 * 96,
                 "routed": 2 * 4 * 3 * 64 * 24, "shared": 2 * 3 * 64 * 48,
                 "router": 2 * 65 * 16, "embed": 512 * 64, "head": 512 * 64}

    from bench.drivers.serve_moe import model_config
    from repro.models import model as M

    leaves = jax.tree_util.tree_flatten_with_path(
        M.abstract_params(model_config(_rehearsal_cfg())))[0]
    norms = sum(x.size for p, x in leaves if str(p[-1].key).startswith("ln")
                or str(p[-1].key).endswith("norm"))
    assert sum(x.size for _, x in leaves) - norms == sum(w.values())

    # a decode step of 2 sequences over 32 keys, 5 held pairs, 6 of 8 held
    # experts chosen: absorbed attention a token and layer
    a_tok = (64 * 96 + 64 * 40 + 4 * 16 * 32 + 4 * 32 * (40 + 32)
             + 4 * 32 * 16 + 4 * 16 * 64)
    per_tok = 3 * a_tok + 3 * 64 * 96 + 2 * (3 * 64 * 48 + 64 * 16) + 64 * 512
    assert moe_sizes.decode_step_flops(m, 2, 32, 5) == 2 * (
        2 * per_tok + 5 * 3 * 64 * 24)
    weights = sum(w.values()) - w["embed"] - (8 - 6) * 3 * 64 * 24
    assert moe_sizes.decode_step_bytes(m, 2, 32, 6) == 2 * weights + (
        3 * 2 * 32 * 40 * 2)
    seq_tok = 3 * attn + 3 * 64 * 96 + 2 * (3 * 64 * 48 + 64 * 16)
    scores = 3 * 4 * (16 + 8 + 16) * (32 * 33 // 2)
    assert moe_sizes.prefill_flops(m, 2, 32, 7) == 2 * (
        2 * (32 * seq_tok + scores + 64 * 512) + 7 * 3 * 64 * 24)


def test_the_tail_reads_a_fault_that_the_mean_dilutes():
    """A fault in 130 of one request's 256 positions moves the mean of 8
    requests by 130/2048 of its size, and that request's tail by all of
    it; flips at a tenth of every request's positions leave the tail 0."""
    from bench.drivers.serve_moe import request_tail

    gaps = np.zeros((8, 256))
    gaps[3, :130] = 1.0
    assert gaps.mean() < 0.1 < request_tail(gaps) == 1.0
    gaps[3] = 0.0
    gaps[:, ::10] = 2.0  # isolated positions of every request, as flips
    assert request_tail(gaps) == 0.0
