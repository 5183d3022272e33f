"""Mean time per Monte-Carlo chunk in the ``planner.fetch`` leaf: the
solve's outputs copied back one array at a time, over the window's
chunks."""
from harness.chunk_spans import chunks, mean_ms


def read(ctx):
    return mean_ms(chunks(ctx, ("montecarlo",)), "planner_fetch_s")
