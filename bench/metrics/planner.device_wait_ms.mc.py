"""Mean time per Monte-Carlo chunk in the ``planner.device_wait`` leaf:
the host waiting on the chips after launch, over the window's chunks."""
from harness.chunk_spans import chunks, mean_ms


def read(ctx):
    return mean_ms(chunks(ctx, ("montecarlo",)), "planner_device_wait_s")
