"""Mean host-clock time of one chunk's solve in the fleet planner
(``plan_batch`` with its fence and copy-back), over the chunks of the
window's requests that the service's span ring still holds."""
from harness.readers import mean_chunk_solve_ms


def read(ctx):
    return mean_chunk_solve_ms(ctx)
