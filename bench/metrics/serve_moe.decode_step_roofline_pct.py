"""Least time of one decode step over its device time (the decode loop
program's device seconds over its steps).  The least time is the larger
of the step's model FLOPs at peak and its bytes at peak HBM bandwidth
(``moe_sizes.decode_step_*``): every weight held here read once but the
embedding, less the held experts no token chose (the window's
``moe_decode_experts_hit`` a step), plus every resident latent row of
every layer.  At these shapes the bound is bytes."""

from bench import moe_sizes, peaks

PROGRAM = "jit_loop"


def read(ctx):
    t = ctx.trace["modules"].get(PROGRAM)
    r = ctx.record
    if not t or "moe_decode_experts_hit" not in r:
        return None
    m = moe_sizes.sizes(ctx.config)
    steps = r["decode_steps"]
    least, _ = peaks.least_seconds(
        moe_sizes.decode_step_flops(m, r["batch"], r["pool_keys"],
                                    r["moe_decode_held_pairs"] / steps),
        moe_sizes.decode_step_bytes(m, r["batch"], r["pool_keys"],
                                    r["moe_decode_experts_hit"] / steps),
        ctx.device_kind)
    return 100 * least / (t / steps)
