"""Device time of one sweep scan step in the flat core (the awrp, lru,
fifo and lfu rows, the victim kernel included): the device seconds of the
sweep program's ops that ``Sentinel.op_scopes()`` maps to ``sweep_flat``,
over the scan steps its calls ran.  None where the program has no
op-to-scope map, or where the ops the map names cover under 95 % of the
program's device time (the trace and the map disagree)."""

PROGRAM = "jit__simulate_batched_impl"  # the jit of core.jax_policies
SCOPE = "sweep_flat"  # core.jax_policies.CORE_SCOPES


def read(ctx):
    from repro.core.jax_policies import _simulate_batched_impl

    op_scopes = getattr(_simulate_batched_impl.sentinel, "op_scopes", None)
    total = ctx.trace["modules"].get(PROGRAM)
    scopes = op_scopes() if op_scopes and total else {}
    ops = {k: v for k, v in ctx.trace["ops"].items() if k in scopes}
    if not ops or sum(ops.values()) < 0.95 * total:
        return None
    t = sum(v for k, v in ops.items() if scopes[k] == SCOPE)
    return t / ctx.record["steps"] * 1e6
