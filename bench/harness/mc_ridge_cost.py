"""The work of the ``mc_ridge`` Pallas kernel, counted from the shapes of
its calls and the live lane-slots, and the peaks it is measured against.

Only the work the simulation needs is counted, whatever implements it:

- per live lane-slot (a slot before its lane's deadline; the slots
  before the lane's first block arrives are masked but counted), one
  single-sample ridge SGD update on the gathered row: the dot ``w.x`` (``d`` products, ``d - 1`` adds), the residual
  (1), the step (1) and ``w <- c1 w + c2 x`` (``3 d``): ``5 d + 1``
  operations;
- per call (one slab of update slots for every lane of one run), the lane
  weights read in and written out (``2 x 4 d`` bytes a lane), and the
  data set's rows and targets read once (``4 (d + 1) rows`` bytes).

The one-hot matmul that gathers the rows on the MXU, the index and mask
tables, and the timeline padding past a lane's deadline are not counted,
so the share of the roofline cannot pass 100%.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

#: update slots per kernel call: the program's slab length
#: (``repro.fleet.objective_kernels.MC_SLAB``)
SLAB = 256

#: Published per-chip peaks by ``device_kind``: the dense bf16 MXU rate
#: (the only compute peak published for the chip) and the HBM bandwidth.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
}


def peaks(kind: str) -> Dict[str, float]:
    """The chip's peaks; an unknown kind is an error."""
    if kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(PEAKS)}")
    return PEAKS[kind]


def cost(lanes: int, slots: int, runs: int, d: int, rows: int,
         live: int, slab: int = SLAB) -> Tuple[int, int]:
    """``(operations, bytes)`` of one Monte-Carlo pass: ``runs`` runs of
    ``lanes`` lanes over ``slots`` padded update slots, ``live`` of the
    ``runs x lanes x slots`` lane-slots live."""
    calls = runs * (slots // slab)
    flops = (5 * d + 1) * live
    nbytes = calls * (2 * 4 * d * lanes + 4 * (d + 1) * rows)
    return flops, nbytes


def window_work(ctx, solved: Iterable) -> Tuple[int, int]:
    """``(operations, bytes)`` summed over the window's Monte-Carlo
    chunks (one span each), each chunk one pass of ``bucket x rates x
    grid_points`` lanes; a program that does not count lane-slots gives
    ``(0, 0)``."""
    obj = ctx.config["objective"]
    d = int(obj["dataset"]["features"])
    rows = int(obj["dataset"]["rows"])
    runs = int(obj["n_runs"])
    per_scenario = len(ctx.traffic["requests"]["rates"]) * int(
        obj["grid_points"])
    flops = nbytes = 0
    for s in solved:
        lane_slots = getattr(s, "mc_lane_slots", 0)
        if not lane_slots:
            continue
        lanes = s.bucket * per_scenario
        f, b = cost(lanes, lane_slots // (runs * lanes), runs, d, rows,
                    s.mc_live_slots)
        flops += f
        nbytes += b
    return flops, nbytes


def kernel_events(trace) -> List:
    """The kernel's device ops inside the window, on every chip: the
    custom calls that name ``mc_ridge`` (a consumer of the kernel's
    output names it only among its operands)."""
    return [e for e in trace.events_named("mc_ridge")
            if " custom-call(" in e.name]


def kernel_seconds(trace) -> float:
    """The kernel's device time inside the window, summed over chips."""
    return sum(e.dur_ns for e in kernel_events(trace)) / 1e9
