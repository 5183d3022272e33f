"""Mean host-clock time of one Monte-Carlo chunk's solve in the fleet
planner (``plan_batch`` with its fence and copy-back), over the window's
chunks."""
from harness.chunk_spans import chunks, mean_ms


def read(ctx):
    return mean_ms(chunks(ctx, ("montecarlo",)), "solve_s")
