"""Milliseconds of garbage-collection pause per second of the window:
the process's pauses that the window's chunks carry (``gc_s``, the
pauses since the worker wrote the chunk before), over the window's
seconds.  The window's first chunk is left out: its record reaches back
before the window, to the full collection the harness makes there."""
from harness.chunk_spans import chunks


def read(ctx):
    solved = chunks(ctx)[1:]
    if not solved or ctx.seconds <= 0:
        return None
    return 1e3 * sum(s.gc_s for s in solved) / ctx.seconds
