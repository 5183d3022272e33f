"""Best-effort activation sharding hints.

``maybe_constrain(x, spec_candidates_per_dim)`` applies a
``with_sharding_constraint`` built from per-dim candidate axis lists, using
the first candidate whose mesh-axis product divides the dim.  No-op outside
a ``jax.set_mesh`` context (CPU unit tests) — the model code stays mesh-agnostic.

GSPMD usually propagates shardings fine; the hints exist for the few ops
that block propagation (scatter/argsort in the MoE dispatch, kv-group
reshapes in GQA attention) where the partitioner otherwise REPLICATES the
whole computation (see EXPERIMENTS.md §Perf, optimized-sweep notes).
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

BATCH = ("pod_data",)  # sentinel: the (pod, data) batch axes


def _mesh():
    """The mesh set by ``jax.set_mesh``, or ``None`` outside one."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _resolve(dim: int, candidates, mesh) -> Optional[object]:
    """First candidate axis (or axis tuple) that divides ``dim``."""
    for cand in candidates:
        if cand == "pod_data":
            axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
            if not axes:
                continue
            size = int(np.prod([mesh.shape[a] for a in axes]))
            if dim % size == 0:
                return axes if len(axes) > 1 else axes[0]
            if "data" in axes and dim % mesh.shape["data"] == 0:
                return "data"
            continue
        if cand in mesh.axis_names and dim % mesh.shape[cand] == 0:
            return cand
    return None


def maybe_constrain(x, dim_candidates: Sequence[Optional[List[str]]]):
    """dim_candidates[i]: list of axis candidates for dim i (None = leave
    replicated/unspecified)."""
    mesh = _mesh()
    if mesh is None:
        return x
    parts = []
    for dim, cands in zip(x.shape, dim_candidates):
        parts.append(_resolve(dim, cands, mesh) if cands else None)
    if all(p is None for p in parts):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*parts)))
