"""Distribution-layer tests.

The full 256/512-chip dry-run lives in ``repro.launch.dryrun`` (run
separately); here we prove the same machinery end-to-end at test scale in a
SUBPROCESS with 8 forced host devices (so every other test keeps the default
single-device environment — the dry-run's XLA_FLAGS rule, DESIGN.md)."""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import dataclasses
import jax
import jax.numpy as jnp

from repro.configs.base import load_smoke_config
from repro.launch import inputs as I
from repro.models import model as M
from repro.sharding.specs import activate, make_rules
from repro.optim import optimizer as O
from repro.train.train_step import make_train_step

# Auto axes: the sharding rules place activations by constraint, and
# make_mesh defaults to Explicit axes, under which gathers reject them
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
results = {}
for arch in ("qwen25_14b", "zamba2_7b", "phi35_moe"):
    cfg = load_smoke_config(arch)
    rules = make_rules(moe_sharding=cfg.moe_sharding)
    with activate(mesh, rules):
        pspecs = I.params_shardings(cfg, mesh, rules)
        params = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            M.abstract_params(cfg), pspecs)
        oc = O.OptConfig()
        step = make_train_step(cfg, oc, n_micro=2)
        opt = O.abstract_opt_state(params, oc)
        batch = {
            "tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
            "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32),
        }
        if cfg.family == "vlm":
            batch["patches"] = jax.ShapeDtypeStruct(
                (8, cfg.n_patch_tokens, cfg.d_model), jnp.dtype(cfg.dtype))
        compiled = jax.jit(step).lower(params, opt, batch).compile()
        from repro.roofline.analysis import cost_analysis_dict
        ca = cost_analysis_dict(compiled)
        results[arch] = {"flops": float(ca.get("flops", 0.0)),
                         "ok": True}
print(json.dumps(results))
"""


@pytest.mark.slow
def test_sharded_train_step_compiles_on_8_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=480,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    results = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(results) == {"qwen25_14b", "zamba2_7b", "phi35_moe"}
    for arch, r in results.items():
        assert r["ok"] and r["flops"] > 0, (arch, r)


def test_make_rules_variants_consistent():
    from repro.sharding.specs import make_rules

    base = make_rules()
    assert base["p_feat"] == ("model",)
    assert base["act_batch"] == ("data",)
    multi = make_rules(multi_pod=True)
    assert multi["act_batch"] == ("pod", "data")
    dp = make_rules(tp_feat=False)
    assert dp["p_feat"] is None and dp["act_feat"] is None
    sp = make_rules(seq_parallel=True)
    assert sp["act_res_seq"] == ("model",)
    tp2d = make_rules(param_mode="tp2d")
    assert tp2d["p_feat"] == ("data", "model")
    assert tp2d["p_embed"] is None
    long = make_rules(shard_pages=True)
    assert long["act_pages"] == ("data",)
    assert long["act_batch"] is None  # batch=1: pages take the batch axes
    ep = make_rules(moe_sharding="ep")
    assert ep["p_experts"] == ("model",) and ep["p_expert_ff"] is None
