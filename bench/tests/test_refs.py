"""The plain references against the program on the CPU, and their
controls, which must fail the comparison."""

import dataclasses
import json
import pathlib

import numpy as np
import pytest

from bench import traffic, weights
from bench.refs import policies as ref
from bench.refs.decoder import Decoder, widest_gap

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _trace(family, n, seed=1):
    return traffic.FAMILIES[family](traffic.stream(seed, 0, 0), n)


@pytest.mark.parametrize("family", sorted(traffic.FAMILIES))
@pytest.mark.parametrize("policy", sorted(ref.POLICIES))
def test_sweep_reference_equals_the_host_oracles(family, policy):
    from repro.core.policies import make_policy

    tr = _trace(family, 2500)
    for cap in (30, 120, 240):
        oracle = make_policy(policy, cap)
        want = np.array([oracle.access(b) for b in tr.tolist()])
        np.testing.assert_array_equal(ref.hits(policy, tr, cap), want)


@pytest.mark.parametrize("policy", ["awrp", "arc", "car"])
def test_bfloat16_control_changes_decisions(policy):
    bad = [(f, c) for f in sorted(traffic.FAMILIES) for c in (30, 120, 240)
           if not np.array_equal(
               ref.hits(policy, _trace(f, 5000), c, "bfloat16"),
               ref.hits(policy, _trace(f, 5000), c))]
    assert bad


def _tiny(dtype):
    from repro.configs.smollm_360m import CONFIG

    m = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
             d_ff=128, vocab=512, rope_theta=100000.0, norm_eps=1e-5)
    cfg = dataclasses.replace(
        CONFIG, n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, vocab=512, page_size=8, bounded_kv_pages=4,
        kv_policy="awrp", dtype=dtype, param_dtype=dtype,
        rope_theta=100000.0, norm_eps=1e-5)
    return m, cfg


def _serve(cfg, params, prompts, new):
    from repro.serve.engine import Request, ServeEngine

    eng = ServeEngine(cfg, params, max_len=prompts.shape[1] + new,
                      kv_mode="paged")
    res = eng.generate([Request(i, p.tolist(), max_new_tokens=new)
                        for i, p in enumerate(prompts)])
    return (np.array([res[i].tokens for i in range(len(prompts))]),
            eng.telemetry()["kv/pool/evictions"])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_decoder_reference_follows_the_engine(dtype):
    """At a tiny width with weights scaled so that logits spread as at the
    published width: the program's gap sits under the configuration's
    limit, and the float8 control's over it."""
    from repro.models import model as M

    m, cfg = _tiny(dtype)
    params = weights.make(M.abstract_params(cfg), 2**33 + 5, 0.12)
    prompts = traffic.stream(9, 0).randint(1, 512, size=(3, 32))
    served, evictions = _serve(cfg, params, prompts, 21)
    logits, ref_ev = Decoder(m, params).logits(prompts, served, 4, 8)
    assert ref_ev == evictions == 3 * 2 * 3  # 3 boundaries x 2 layers x 3
    gap = widest_gap(logits, served)
    if dtype == "float32":  # the same arithmetic: the same argmax
        assert gap == 0.0
    limit = json.loads((ROOT / "bench/configs/smollm-360m-pagedkv.json")
                       .read_text())["limits"]["logit_gap"]
    assert gap < limit
    low, _ = Decoder(m, params, "float8").logits(prompts, served, 4, 8)
    assert widest_gap(logits, low.argmax(-1)) > limit
