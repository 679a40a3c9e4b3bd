"""Least time of one decode step over its device time
(``serve.decode_step_ms``).  The least time is the larger of the step's
model FLOPs at peak and its bytes at peak HBM bandwidth: every weight
read once and each sequence's resident pool K/V read in every layer
(``peaks.decode_step_*``).  At these shapes the bound is bytes."""

from bench import peaks

PROGRAM = "jit_loop"


def read(ctx):
    t = ctx.trace["modules"].get(PROGRAM)
    if not t:
        return None
    r = ctx.record
    m = peaks.decoder_sizes(ctx.config)
    least, _ = peaks.least_seconds(
        peaks.decode_step_flops(m, r["batch"], r["pool_keys"]),
        peaks.decode_step_bytes(m, r["batch"], r["pool_keys"]),
        ctx.device_kind)
    return 100 * least / (t / r["decode_steps"])
