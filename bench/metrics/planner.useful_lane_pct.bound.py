"""Share of the solved lanes that answer a distinct request: 100 x the
unique lanes (cache misses after in-batch dedup) over the padded bucket
lanes, summed over the window's bound chunks."""
from harness.chunk_spans import BOUND, chunks


def read(ctx):
    solved = chunks(ctx, BOUND)
    lanes = sum(s.bucket for s in solved)
    if not lanes:
        return None
    return 100.0 * sum(s.lanes_unique for s in solved) / lanes
