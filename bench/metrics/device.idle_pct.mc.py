"""Share of the traced window in which no operation ran on a chip,
averaged over the four chips."""
from harness.readers import idle_pct


def read(ctx):
    return idle_pct(ctx)
