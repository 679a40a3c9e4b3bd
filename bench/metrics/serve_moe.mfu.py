"""Model FLOPs of the traced window's prefills and decode steps
(``moe_sizes.prefill_flops``, ``moe_sizes.decode_step_flops``, routed work
from the window's ``moe_*_held_pairs``), over window seconds x the chip's
bf16 peak."""

from bench import moe_sizes


def read(ctx):
    r = ctx.record
    if "moe_prefill_held_pairs" not in r or not r["prefills"]:
        return None
    m = moe_sizes.sizes(ctx.config)
    steps = r["decode_steps"]
    flops = (r["prefills"] * moe_sizes.prefill_flops(
                 m, r["batch"], r["prompt_len"],
                 r["moe_prefill_held_pairs"] / r["prefills"])
             + steps * moe_sizes.decode_step_flops(
                 m, r["batch"], r["pool_keys"],
                 r["moe_decode_held_pairs"] / max(steps, 1)))
    return 100 * flops / (ctx.trace["window_s"]
                          * ctx.peaks["bf16_flops_per_s"])
