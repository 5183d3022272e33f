"""Mean host time per Monte-Carlo chunk in the planner's own work: the
``planner.build``, ``planner.refine_host`` and ``planner.records``
leaves, over the window's chunks."""
from harness.chunk_spans import chunks, mean_ms


def read(ctx):
    return mean_ms(chunks(ctx, ("montecarlo",)), "planner_build_s",
                   "planner_refine_host_s", "planner_records_s")
