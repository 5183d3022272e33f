"""95th percentile of the time from each request's scheduled send to its
plan, over every request due in the window; a failed request counts as
the longest wait the run allows."""
from harness.stats import percentile


def read(ctx):
    if not ctx.latency_s:
        return None
    return 1e3 * percentile(ctx.latency_s, 95)
