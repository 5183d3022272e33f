"""Share of the simulated lane-slots that lie before their lane's
deadline: 100 x the chunks' ``mc_live_slots`` over their
``mc_lane_slots``, summed over the window's Monte-Carlo chunks.  The
rest is timeline padding (every lane runs the batch's padded horizon).
A program that does not count them gives nothing."""
from harness.chunk_spans import chunks


def read(ctx):
    solved = chunks(ctx, ("montecarlo",))
    slots = sum(getattr(s, "mc_lane_slots", 0) for s in solved)
    if not slots:
        return None
    return 100.0 * sum(s.mc_live_slots for s in solved) / slots
