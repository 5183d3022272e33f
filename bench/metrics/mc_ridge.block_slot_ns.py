"""The ``mc_ridge`` Pallas kernel's device time per 128-lane block-slot it
stepped through: ``1e9 x kernel seconds x 128 / mc_run_slots``, the
kernel's device time inside the window summed over the chips, over the
lane-slots of the window's Monte-Carlo chunks counted in blocks of 128
lanes.  It is the cost of one slot of one lane block: the row gather and
the SGD step.  Read beside the cell's data-set rows, it shows whether
that cost grows with the rows.

It gives nothing without a trace or the kernel in it, without the
``mc_run_slots`` counter, or where no chunk of the window noted as its
``mc_rows`` the configuration's ``objective.dataset.rows``: a program that
served another data set than the configuration names shows as a missing
metric."""
from harness import mc_ridge_cost
from harness.chunk_spans import chunks

#: lanes of one kernel block (``repro.kernels.mc_ridge.BLOCK_L``)
BLOCK_L = 128


def read(ctx):
    if ctx.trace is None:
        return None
    solved = chunks(ctx, ("montecarlo",))
    rows = int(ctx.config["objective"]["dataset"]["rows"])
    if not any(getattr(s, "mc_rows", 0) == rows for s in solved):
        return None
    run = sum(getattr(s, "mc_run_slots", 0) for s in solved)
    device_s = mc_ridge_cost.kernel_seconds(ctx.trace)
    if not run or not device_s:
        return None
    return 1e9 * device_s * BLOCK_L / run
