"""The ``mc_ridge`` Pallas kernel's share of its roofline: 100 x the
least time its work needs on the chips (the larger of its operations
over the peak FLOP/s and its bytes over the peak bytes/s, of one chip)
over its device time summed over the chips, inside the window.

The work is counted from the window's Monte-Carlo chunks
(``harness.mc_ridge_cost``); a program that does not count its lane-
slots, or a trace without the kernel, gives nothing."""
from harness import mc_ridge_cost
from harness.chunk_spans import chunks


def read(ctx):
    if ctx.trace is None:
        return None
    device_s = mc_ridge_cost.kernel_seconds(ctx.trace)
    flops, nbytes = mc_ridge_cost.window_work(
        ctx, chunks(ctx, ("montecarlo",)))
    if not device_s or not flops:
        return None
    peak = mc_ridge_cost.peaks(ctx.device["kind"])
    least = max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
    return 100.0 * least / device_s
