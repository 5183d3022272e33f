"""Arithmetic shared by the metric readers in ``bench/metrics/``."""
from __future__ import annotations

from typing import Dict, Optional


def chunks(ctx) -> Dict[tuple, object]:
    """One span of each solved chunk in the window.  The requests of one
    chunk share its pad, cache, solve and resolve phases, so those phases
    identify the chunk."""
    out = {}
    for s in ctx.spans:
        if s.bucket > 0:
            out[(s.objective, s.grid_mode, s.bucket, s.pad_s,
                 s.cache_lookup_s, s.solve_s, s.resolve_s)] = s
    return out


def mean_chunk_solve_ms(ctx) -> Optional[float]:
    """Mean solve time per solved chunk, from the window's request spans."""
    solved = chunks(ctx)
    if not solved:
        return None
    return 1e3 * sum(s.solve_s for s in solved.values()) / len(solved)


def idle_pct(ctx) -> Optional[float]:
    """Share of the traced window in which no operation ran on a chip,
    averaged over the chips used."""
    red = ctx.trace
    if red is None or not red.busy_s_by_device or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
