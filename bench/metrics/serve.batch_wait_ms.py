"""Mean time a request waited in the micro-batcher (enqueue to its
chunk's start), over the window's requests that the service's span ring
still holds after the window (its last 8,192 at the ring's default
size)."""


def read(ctx):
    if not ctx.spans:
        return None
    return 1e3 * sum(s.batch_wait_s for s in ctx.spans) / len(ctx.spans)
