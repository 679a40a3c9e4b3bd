"""Device time of the prefill program per batch (``models/model.prefill``
as the engine jits it)."""

PROGRAM = "jit__lambda"  # the engine's prefill jit has no name of its own


def read(ctx):
    t = ctx.trace["modules"].get(PROGRAM)
    return t / ctx.record["prefills"] * 1e3 if t else None
