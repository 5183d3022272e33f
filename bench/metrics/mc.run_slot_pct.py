"""Share of the padded lane-slots that the ``mc_ridge`` kernel steps
through: 100 x the chunks' ``mc_run_slots`` over their ``mc_lane_slots``,
summed over the window's Monte-Carlo chunks.  Each lane block stops at
its lanes' longest deadline, so the share falls as the padding is
skipped; it is never below ``mc.live_slot_pct``.  A program that does
not count run slots, or whose passes ran no kernel, gives nothing."""
from harness.chunk_spans import chunks


def read(ctx):
    solved = chunks(ctx, ("montecarlo",))
    run = sum(getattr(s, "mc_run_slots", 0) for s in solved)
    if not run:
        return None
    return 100.0 * run / sum(s.mc_lane_slots for s in solved)
