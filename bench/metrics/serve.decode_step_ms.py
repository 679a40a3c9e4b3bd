"""Device time of one decode step: the decode-loop program's device
seconds over the decode steps it ran."""

PROGRAM = "jit_loop"  # the engine's jitted decode loop


def read(ctx):
    t = ctx.trace["modules"].get(PROGRAM)
    return t / ctx.record["decode_steps"] * 1e3 if t else None
