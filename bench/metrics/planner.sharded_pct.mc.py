"""Share of the Monte-Carlo chunks' jitted calls that ran across every
local chip: 100 x ``mc_sharded_dispatches`` over ``dispatches``, summed
over the window's chunks.  Under 100 where a batch whose scenarios or
lanes do not divide by the chips fell back to one chip.  A program that
does not count sharded passes gives nothing."""
from harness.chunk_spans import chunks


def read(ctx):
    solved = chunks(ctx, ("montecarlo",))
    calls = sum(s.dispatches for s in solved)
    if not calls or not all(hasattr(s, "mc_sharded_dispatches")
                            for s in solved):
        return None
    return 100.0 * sum(s.mc_sharded_dispatches for s in solved) / calls
