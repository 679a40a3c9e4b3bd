"""Device time of one sweep scan step outside the three policy cores: the
sweep program's device seconds less those of its ops that
``Sentinel.op_scopes()`` maps to a core scope, over the scan steps its
calls ran.  That is the ops in no core scope (the row gathers of the
step's block ids, the concatenation of hits, layout before and after the
scan), the ``mixed`` fusions of two cores' work, and the program's time in
no op of its own (loop control between ops), so the four step metrics sum
to ``sweep.step_us``.  None where the program has no op-to-scope map, or
where the ops the map names cover under 95 % of the program's device time
(the trace and the map disagree)."""

PROGRAM = "jit__simulate_batched_impl"  # the jit of core.jax_policies
CORES = ("sweep_flat", "sweep_arc", "sweep_car")  # its CORE_SCOPES


def read(ctx):
    from repro.core.jax_policies import _simulate_batched_impl

    op_scopes = getattr(_simulate_batched_impl.sentinel, "op_scopes", None)
    total = ctx.trace["modules"].get(PROGRAM)
    scopes = op_scopes() if op_scopes and total else {}
    ops = {k: v for k, v in ctx.trace["ops"].items() if k in scopes}
    if not ops or sum(ops.values()) < 0.95 * total:
        return None
    cores = sum(v for k, v in ops.items() if scopes[k] in CORES)
    return (total - cores) / ctx.record["steps"] * 1e6
