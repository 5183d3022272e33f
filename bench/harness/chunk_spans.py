"""The window's chunks, one request span each, told apart by the
``chunk_id`` every request span carries.

A program whose spans name no chunk gives no chunks, so every reader
built on this module returns nothing there.
"""
from __future__ import annotations

from typing import Iterable, List, Optional

#: the objectives of the bound plans (``.bound`` metrics)
BOUND = ("corollary1", "markov_arq")


def chunks(ctx, objectives: Optional[Iterable[str]] = None) -> List:
    """One span per solved chunk of the window (bucket above 0: the
    degradation ladder's answers are left out), in recording order;
    ``objectives`` keeps only the chunks of those objectives."""
    keep = None if objectives is None else set(objectives)
    out = {}
    for s in ctx.spans:
        cid = getattr(s, "chunk_id", -1)
        if cid >= 0 and s.bucket > 0 and (keep is None
                                          or s.objective in keep):
            out.setdefault(cid, s)
    return list(out.values())


def mean_ms(solved: List, *fields: str) -> Optional[float]:
    """Mean per chunk of the summed ``fields`` (seconds), in ms."""
    if not solved:
        return None
    total = sum(getattr(s, f) for s in solved for f in fields)
    return 1e3 * total / len(solved)
