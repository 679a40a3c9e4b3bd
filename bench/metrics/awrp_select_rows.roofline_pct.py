"""The victim kernel's share of its roofline: launches x least time over
its device time in the trace.  The kernel's VPU integer work has no
published peak, so the least time is its bytes (``peaks.
awrp_select_rows_bytes``) at peak HBM bandwidth."""

from bench import peaks, trace_reduce


def read(ctx):
    t, n = trace_reduce.kernel(ctx.trace, "awrp_select_rows")
    if not n:
        return None
    nbytes = peaks.awrp_select_rows_bytes(ctx.record["flat_rows"],
                                          ctx.record["lanes"])
    return 100 * n * nbytes / ctx.peaks["hbm_bytes_per_s"] / t
