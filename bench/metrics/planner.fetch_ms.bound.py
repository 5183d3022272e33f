"""Mean time per bound chunk in the ``planner.fetch`` leaf: the results
copied back to host arrays, over the window's chunks."""
from harness.chunk_spans import BOUND, chunks, mean_ms


def read(ctx):
    return mean_ms(chunks(ctx, BOUND), "planner_fetch_s")
