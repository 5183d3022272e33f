"""Mean time per Monte-Carlo chunk in the ``planner.dispatch`` leaf: the
jitted calls until they return (argument conversion, the lanes laid over
the chips, launch), over the window's chunks."""
from harness.chunk_spans import chunks, mean_ms


def read(ctx):
    return mean_ms(chunks(ctx, ("montecarlo",)), "planner_dispatch_s")
