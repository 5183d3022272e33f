"""Mean time per bound chunk in the ``planner.dispatch`` leaf: the
jitted calls until they return (argument conversion, host-to-device
copies, launch), over the window's chunks."""
from harness.chunk_spans import BOUND, chunks, mean_ms


def read(ctx):
    return mean_ms(chunks(ctx, BOUND), "planner_dispatch_s")
