"""Seconds from the process's first statement to the window's start:
imports, device init, warmup from the compile cache, traffic, pre-roll."""


def read(ctx):
    return ctx.setup_s
