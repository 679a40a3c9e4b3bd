"""Multi-device smoke driver: run the sharded parity suite.

Forces ``N`` XLA host devices (default 8) via
``--xla_force_host_platform_device_count`` and then runs, in a child
process so the flag is guaranteed to precede the first jax import,
``tests/test_sharding.py``, the fast fused-kernel suite
``tests/test_policy_attn.py`` and ``tests/test_obs.py`` — the
bit-identity property suites at the forced device count (the mesh parity
cases that skip in plain tier-1 actually run here, including the fused
``shard_map`` path).

Exit status is the suite's — this is the command the CI ``multi-device``
job runs, and the one to reproduce it locally::

    python tools/run_sharded_smoke.py [--devices 8]
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    """Run the sharded parity suite at the forced device count; return
    its exit status."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, default=8, metavar="N",
                    help="XLA host device count to force (default 8)")
    args = ap.parse_args(argv)

    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = (f"{flags} --xla_force_host_platform_device_count="
                 f"{args.devices}").strip()
    env["XLA_FLAGS"] = flags
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(REPO, "src"), env.get("PYTHONPATH")) if p)

    cmd = [sys.executable, "-m", "pytest", "-x", "-q",
           os.path.join(REPO, "tests", "test_sharding.py"),
           "-m", "not slow",
           os.path.join(REPO, "tests", "test_policy_attn.py"),
           os.path.join(REPO, "tests", "test_obs.py")]
    print(f"== sharded parity suite ({args.devices} devices) ==", flush=True)
    rc = subprocess.call(cmd, env=env, cwd=REPO)
    status = "OK" if rc == 0 else f"FAILED (exit {rc})"
    print(f"\nmulti-device smoke {status} at {args.devices} devices")
    return rc


if __name__ == "__main__":
    sys.exit(main())
