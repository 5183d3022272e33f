"""Mean time per chunk in the ``serve.resolve`` leaf: session delivery
and future resolution, with the callbacks clients attach, over the
window's chunks."""
from harness.chunk_spans import chunks, mean_ms


def read(ctx):
    return mean_ms(chunks(ctx), "serve_resolve_s")
