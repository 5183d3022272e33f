"""Share of the traced window in which no operation ran on the chip(s)."""
from harness.readers import idle_pct


def read(ctx):
    return idle_pct(ctx)
