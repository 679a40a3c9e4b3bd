"""Device time of one sweep scan step: the sweep program's device seconds
in the traced window over the scan steps its calls ran."""

PROGRAM = "jit__simulate_batched_impl"  # the jit of core.jax_policies


def read(ctx):
    t = ctx.trace["modules"].get(PROGRAM)
    return t / ctx.record["steps"] * 1e6 if t else None
