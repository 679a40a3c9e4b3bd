"""Model FLOPs of the traced window's prefills and decode steps, from the
configuration's shapes (``peaks.prefill_flops``, ``peaks.
decode_step_flops``), over window seconds x the chip's bf16 peak."""

from bench import peaks


def read(ctx):
    r = ctx.record
    m = peaks.decoder_sizes(ctx.config)
    flops = (r["prefills"] * peaks.prefill_flops(m, r["batch"],
                                                 r["prompt_len"])
             + r["decode_steps"] * peaks.decode_step_flops(
                 m, r["batch"], r["pool_keys"]))
    return 100 * flops / (ctx.trace["window_s"]
                          * ctx.peaks["bf16_flops_per_s"])
