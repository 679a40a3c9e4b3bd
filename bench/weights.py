"""Random weights for a served model, made on the device from the seed in
one jitted call, in the dtype each leaf is served in.

Every matrix is drawn N(0, ``init_std``**2), the source config's
``initializer_range``; norm scales are stored as 0, which the program's
RMSNorm reads as a gain of 1.  The reference reads the same leaves.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def make(shapes, seed: int, init_std: float):
    """A tree of arrays shaped like ``shapes`` (``ShapeDtypeStruct``
    leaves), from ``seed``."""
    paths, tree = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        keys = jax.random.split(key, len(paths))
        out = []
        for k, (path, s) in zip(keys, paths):
            name = str(getattr(path[-1], "key", path[-1]))
            if name.startswith("ln") or name.endswith("norm"):
                out.append(jnp.zeros(s.shape, s.dtype))  # norm scales
            else:
                out.append((init_std * jax.random.normal(k, s.shape, jnp.float32))
                           .astype(s.dtype))
        return jax.tree.unflatten(tree, out)

    key = jax.random.key_data(jax.random.key(seed % 2**63))
    return jax.jit(lambda kd: build(jax.random.wrap_key_data(kd)))(key)
