"""XLA host-device setup for benchmark entrypoints (must run before jax
imports)."""

from __future__ import annotations

import os
import sys


def set_host_device_count(n: int) -> None:
    """Expose ``n`` XLA host-platform devices for mesh benchmarks.

    Appends ``--xla_force_host_platform_device_count=n`` to ``XLA_FLAGS``.
    This must run before the first jax import — a :class:`RuntimeError` is
    raised if it is already too late, because silently benchmarking on the
    wrong device count would corrupt the recorded scaling numbers.  An
    operator-provided count is respected.
    """
    if int(n) < 1:
        raise ValueError(f"device count must be >= 1, got {n}")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return  # operator already pinned a count
    if "jax" in sys.modules:
        raise RuntimeError(
            "set_host_device_count must be called before jax is imported"
        )
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={int(n)}".strip()
    )
