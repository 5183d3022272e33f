"""Mean time per bound chunk in the ``planner.device_wait`` leaf: the
host waiting on the device after launch, over the window's chunks."""
from harness.chunk_spans import BOUND, chunks, mean_ms


def read(ctx):
    return mean_ms(chunks(ctx, BOUND), "planner_device_wait_s")
