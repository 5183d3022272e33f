"""Batched planning over a :class:`ScenarioBatch`, for any registered objective.

One jitted call evaluates the joint ``(rate, n_c)`` objective for EVERY
scenario in the batch — shape ``(S, R, G)`` — and reduces it with the same
rate-major argmin tie-breaking as the scalar
:class:`~repro.core.scenario.ObjectivePlanner`, so the batched and scalar
paths pick identical plans (enforced by the fleet property tests).

Both pluggable registries meet here: the channel physics comes from the
link registry (a vmapped ``jax.lax.switch`` over the
:mod:`~repro.fleet.link_kernels` branch table turns each scenario's
``(link_model_id, link_params)`` row into its loss probability) and the
quantity being minimised comes from the OBJECTIVE registry
(:mod:`repro.core.objectives` + :mod:`~repro.fleet.objective_kernels`):
the closed-form Corollary-1 bound, the exact burst-aware Markov-ARQ
variant, the empirical Monte-Carlo ridge objective, or any plugin.  A
single compilation per objective plans a fleet mixing every registered
channel family; jitted solves are cached per kernel-table version, so
registering a new model after import just triggers one retrace.

The whole computation runs under ``jax.enable_x64(True)`` to match the
numpy reference bit-for-bit where the backend's libm allows, and the
grid objectives are sharded across local devices via
``jax.sharding.NamedSharding`` over the scenario axis whenever more than
one device is visible and ``S`` divides evenly.
"""
from __future__ import annotations

import time
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import numpy as np

from repro.core.bounds import BoundConstants
from repro.core.objectives import BoundObjective, refine_hints_for
from repro.core.planner import (Plan, coarse_indices, fleet_grid,
                                refine_grid, refine_window_bounds)
from repro.core.protocol import BlockSchedule
from repro.core.scenario import Scenario

from repro.fleet.batch import ScenarioBatch
from repro.fleet.cache import PlanCache
from repro.fleet.objective_kernels import fleet_solve, pow2ceil
from repro.fleet.tracing import trace_delta
from repro.obs.runtime import count, span

#: Valid ``FleetPlanner.grid_mode`` values: ``"dense"`` (single-pass, the
#: reference semantics and the documented escape hatch) and ``"refine"``
#: (two-pass coarse -> fine; see ``FleetPlanner``).
GRID_MODES = ("dense", "refine")

#: Valid ``FleetPlanner.mc_impl`` values: ``"auto"`` resolves by backend
#: (the pallas slab kernel on TPU, the ``lax.scan`` engines elsewhere);
#: ``"scan"`` / ``"pallas"`` pin the Monte-Carlo simulation engine.
MC_IMPLS = ("auto", "scan", "pallas")


@dataclass(frozen=True)
class PlanRecord:
    """Lightweight per-scenario plan — what the cache stores and the
    server streams back.  ``FleetPlan.record(i)`` extracts one."""

    n_c: int
    rate: float
    bound_value: float
    p_err: float
    n_o_eff: float
    full_transfer: bool
    boundary: float
    n_c_per_device: int
    objective: str = "corollary1"
    #: degradation-ladder level that produced this record ("full" =
    #: the real solve; see repro.serve.resilience.FALLBACK_LEVELS).
    #: Defaults keep full-fidelity records bitwise comparable across
    #: the service and direct plan_many paths.
    fallback: str = "full"


@dataclass(frozen=True)
class FleetPlan:
    """Struct-of-arrays planner output; all arrays share leading dim S."""

    n_c: np.ndarray             # (S,) int64   chosen union block size
    rate: np.ndarray            # (S,) float64 chosen transmission rate
    bound_value: np.ndarray     # (S,) float64 objective at the optimum
    p_err: np.ndarray           # (S,) float64 loss probability at that rate
    n_o_eff: np.ndarray         # (S,) float64 effective overhead at optimum
    full_transfer: np.ndarray   # (S,) bool    regime flag (delivered >= N)
    boundary: np.ndarray        # (S,) float64 regime-boundary block size
    n_c_per_device: np.ndarray  # (S,) int64   per-device block size
    grid: np.ndarray            # (S, G) evaluated n_c grid
    bound_grid: np.ndarray      # (S, G) objective at the chosen rate
    objective: str = "corollary1"

    def __len__(self) -> int:
        return int(self.n_c.shape[0])

    def record(self, i: int) -> PlanRecord:
        return PlanRecord(
            n_c=int(self.n_c[i]), rate=float(self.rate[i]),
            bound_value=float(self.bound_value[i]),
            p_err=float(self.p_err[i]), n_o_eff=float(self.n_o_eff[i]),
            full_transfer=bool(self.full_transfer[i]),
            boundary=float(self.boundary[i]),
            n_c_per_device=int(self.n_c_per_device[i]),
            objective=self.objective)

    def to_plan(self, batch: ScenarioBatch, i: int) -> Plan:
        """Materialise the i-th result as a full PR-1 :class:`Plan`."""
        sched = BlockSchedule(N=int(batch.N[i]), n_c=int(self.n_c[i]),
                              n_o=float(self.n_o_eff[i]),
                              T=float(batch.T[i]),
                              tau_p=float(batch.tau_p[i]))
        return Plan(
            n_c=int(self.n_c[i]), bound_value=float(self.bound_value[i]),
            full_transfer=sched.full_transfer,
            boundary=float(self.boundary[i]),
            grid=np.asarray(self.grid[i]),
            bound_grid=np.asarray(self.bound_grid[i]),
            schedule=sched, rate=float(self.rate[i]),
            p_err=float(self.p_err[i]),
            n_c_per_device=int(self.n_c_per_device[i]),
            objective=self.objective)


def _pad_batch(scenarios: List[Scenario],
               pad_to: Optional[int] = None) -> List[Scenario]:
    """Pad to a fixed length ``pad_to``, or to the next power of two —
    shape invariance bounds how many kernel shapes a request stream can
    ever compile (one per pad length).  Pad lanes repeat the batch's
    smallest-``N`` scenario: their results are discarded either way, and
    for simulated objectives (Monte Carlo scales with the update count,
    which grows with ``N``) repeating an arbitrary scenario could fill
    the padding with copies of the batch's most expensive simulation."""
    n = len(scenarios)
    if pad_to is None:
        pad_to = pow2ceil(n)
    elif pad_to < n:
        raise ValueError(f"pad_to={pad_to} < batch of {n}")
    pad = min(scenarios, key=lambda sc: sc.N)
    return scenarios + [pad] * (pad_to - n)


@dataclass(frozen=True)
class FleetPlanner:
    """Batched planner: thousands of scenarios per call, any objective.

    ``grid_size`` is the per-scenario grid width G (every scenario gets its
    own log-spaced 1..N grid of that width via
    :func:`repro.core.planner.fleet_grid`); ``shard`` toggles the
    NamedSharding layout across local devices; ``objective`` is the
    default registered objective instance solved by ``plan_batch`` /
    ``plan_many`` (``None`` means the Corollary-1
    :class:`~repro.core.objectives.BoundObjective`), overridable per call.

    ``grid_mode`` selects the solve strategy over the grid:

      * ``"dense"`` (default, and the documented escape hatch): one pass
        over the full grid — the reference semantics every equivalence
        test is stated against.
      * ``"refine"``: hierarchical coarse -> fine.  Pass 1 solves on the
        coarse subsample ``grid[::k]`` + the anchored last point
        (``k ~ sqrt(G/2)``); pass 2 re-solves per-rate bracket windows
        around each rate's coarse argmin, extended by the objective's
        guarded sawtooth tail (see
        :class:`~repro.core.objectives.RefineHints`), cutting the
        evaluated lanes roughly 2-4x.  Both passes run through the same
        jitted ``fleet_solve`` kernels, so every registered objective —
        including plugins built on ``grid_objective_builder`` — gets the
        cut for free.  The refined argmin equals the dense argmin
        (rate-major tie-breaking included) whenever the dense argmin lies
        in the evaluated windows — guaranteed by the bracket for
        coarse-resolved basins and by the dense tail guard for the
        small-block-count sawtooth, and enforced by the refinement
        parity tests; when a grid is too narrow to subsample
        (``G < hints.min_grid``), windows would cover the grid anyway, or
        a kernel does not expose per-rate argmins, the solve silently
        falls back to the dense pass.
    """

    grid_size: int = 128
    shard: bool = True
    objective: Any = None
    grid_mode: str = "dense"
    #: Round refined fine-pass widths up to the next POWER OF TWO instead
    #: of the default data-tight rule (multiples of 8 with a tail guard,
    #: exact otherwise).  Padding only repeats already-evaluated window
    #: points, so plans are unchanged — but the set of fine-pass widths a
    #: request stream can compile becomes enumerable from ``(G, hints)``
    #: alone, which is what lets :meth:`warm` precompile EVERY shape a
    #: serving configuration admits (the "zero traces after warmup" SLO).
    pow2_refine_widths: bool = False
    #: Monte-Carlo simulation engine: ``"auto"`` (default) picks the
    #: pallas slab kernel (:mod:`repro.kernels.mc_ridge`) on TPU and the
    #: ``lax.scan`` engines elsewhere; ``"scan"`` / ``"pallas"`` pin it.
    #: The choice never changes WHICH plan is selected — the engines are
    #: bitwise-matched per :class:`~repro.core.objectives.MonteCarloObjective`
    #: configuration — so only non-default engines are tagged into
    #: :meth:`cache_context`.  Ignored by non-Monte-Carlo objectives.
    mc_impl: str = "auto"

    def __post_init__(self):
        if self.grid_mode not in GRID_MODES:
            raise ValueError(
                f"unknown grid_mode {self.grid_mode!r}; valid: {GRID_MODES}")
        if self.mc_impl not in MC_IMPLS:
            raise ValueError(
                f"unknown mc_impl {self.mc_impl!r}; valid: {MC_IMPLS}")

    def _resolve_mc_impl(self) -> str:
        if self.mc_impl == "auto":
            return "pallas" if jax.default_backend() == "tpu" else "scan"
        return self.mc_impl

    def _resolve_objective(self, override):
        obj = override if override is not None else self.objective
        return obj if obj is not None else BoundObjective()

    def _resolve_grid_mode(self, override: Optional[str]) -> str:
        mode = override if override is not None else self.grid_mode
        if mode not in GRID_MODES:
            raise ValueError(
                f"unknown grid_mode {mode!r}; valid: {GRID_MODES}")
        return mode

    def _default_grid(self, batch: ScenarioBatch, objective) -> np.ndarray:
        """The per-scenario default grid for this objective: ``grid_size``
        wide, capped by the objective's own ``default_grid_size``."""
        size = self.grid_size
        own = getattr(objective, "default_grid_size", None)
        if own is not None:
            size = min(size, int(own))
        return fleet_grid(batch.N, size)

    @staticmethod
    def _solve_arrays(batch: ScenarioBatch, grid: np.ndarray) -> dict:
        """The kernel input dict (np.asarray: no copy when dtypes match)."""
        return {
            "N": np.asarray(batch.N, np.int64),
            "T": np.asarray(batch.T, np.float64),
            "union_no": batch.union_overhead,
            "tau_p": np.asarray(batch.tau_p, np.float64),
            "rates": np.asarray(batch.rates, np.float64),
            "rate_mask": batch.rate_mask,
            "grid": np.ascontiguousarray(grid),
            "link_model_id": np.asarray(batch.link_model_id, np.int32),
            "link_params": np.asarray(batch.link_params, np.float64),
        }

    def _pad_width(self, x: int, pad_multiple: int) -> int:
        """Fine-pass width padding: next power of two under
        ``pow2_refine_widths`` (enumerable shapes, for serving warmup),
        else the data-tight multiple-of-``pad_multiple`` rule."""
        if self.pow2_refine_widths:
            return pow2ceil(int(x))
        return -(-int(x) // pad_multiple) * pad_multiple

    def plan_batch(self,
                   batch: Union[ScenarioBatch, Sequence[Scenario]],
                   consts: BoundConstants,
                   grid: Optional[np.ndarray] = None,
                   objective: Any = None,
                   grid_mode: Optional[str] = None) -> FleetPlan:
        """Solve every scenario in the batch against the objective.

        ``grid`` may be ``None`` (per-scenario default grids), a shared
        ``(G,)`` vector, or a per-scenario ``(S, G)`` matrix;
        ``objective`` and ``grid_mode`` override the planner's defaults
        per call.  With ``grid=None``, an objective declaring
        ``default_grid_size`` (the Monte-Carlo objective: simulating
        training per grid point is expensive) caps the default grid width
        below ``grid_size``.  In ``"refine"`` mode the returned
        ``grid`` / ``bound_grid`` hold the evaluated fine window at each
        scenario's chosen rate (ascending in ``n_c``) rather than the
        full dense grid.
        """
        with span("planner.build"):
            consts.validate()
            objective = self._resolve_objective(objective)
            mode = self._resolve_grid_mode(grid_mode)
            if not isinstance(batch, ScenarioBatch):
                batch = ScenarioBatch.from_scenarios(list(batch))
            S = len(batch)
            if grid is None:
                grid = self._default_grid(batch, objective)
            else:
                grid = np.asarray(grid, np.int64)
                if grid.ndim == 1:
                    grid = np.broadcast_to(grid, (S, grid.shape[0]))
                if grid.shape[0] != S:
                    raise ValueError(
                        f"grid has leading dim {grid.shape[0]}, want {S}")
            solve = fleet_solve(objective)
            arrays = self._kernel_arrays(solve, batch, grid)
        out = None
        if mode == "refine":
            out, fine_grid = self._refine_solve(solve, arrays, consts,
                                                batch, objective, grid)
        if out is None:  # dense mode, or refinement fell back
            out = solve(arrays, consts, self.shard, batch)
            fine_grid = grid
        with span("planner.records"):
            D = batch.n_devices
            num = np.maximum(batch.N * out["n_o_eff"], 0.0)
            den = batch.T - batch.N
            # regime boundary N * n_o_eff / (T - N); T <= N means the full
            # set can never arrive — clamp to +inf explicitly (matching the
            # scalar boundary_n_c) so no inf/NaN arithmetic can leak into
            # records
            ratio = num / np.where(den > 0.0, den, 1.0)
            boundary = np.where(den > 0.0, ratio, np.inf)
            return FleetPlan(
                n_c=out["n_c"], rate=out["rate"],
                bound_value=out["bound_value"], p_err=out["p_err"],
                n_o_eff=out["n_o_eff"], full_transfer=out["full_transfer"],
                boundary=boundary,
                n_c_per_device=np.maximum(1, out["n_c"] // D),
                grid=np.asarray(fine_grid), bound_grid=out["bound_grid"],
                objective=objective.objective_id)

    def _kernel_arrays(self, solve, batch, grid):
        """The solve's input arrays, with the Monte-Carlo engine for a
        kernel that takes one: warmup and serving must compile the same
        engine."""
        arrays = self._solve_arrays(batch, grid)
        impl = self._resolve_mc_impl()
        if impl != "scan" and getattr(solve, "supports_mc_impl", False):
            arrays["mc_impl"] = impl  # popped host-side by the MC builder
        return arrays

    def _refine_solve(self, solve, arrays, consts, batch, objective, grid):
        """The two-pass coarse -> fine solve; ``(None, None)`` signals a
        dense fallback (grid too narrow, windows as wide as the grid, or
        a custom kernel without per-rate argmins).

        Two OPT-IN hints reshape the passes for simulated objectives (see
        :class:`~repro.core.objectives.RefineHints`): ``coarse_seeds``
        schedules the seed count of the coarse pass — ``k >= 1`` runs it
        with only ``k`` Monte-Carlo seeds, ``0`` skips the simulated
        coarse pass entirely and takes the per-rate centers from a
        full-grid Corollary-1 solve (the bound is a few-microsecond
        closed form, and its per-rate argmin lands in the same basin the
        simulated coarse pass brackets) — and ``refine_rates=K`` prunes
        the fine pass to each scenario's top-``K`` rates as ranked by the
        coarse per-rate minima.  With either hint active the solve never
        falls back to dense on width grounds: the caller opted into an
        approximate (but far cheaper) search, so a wide window at pruned
        rates still beats the dense all-rates pass it would fall back to.

        ``coarse_strides`` stacks extra coarse stages between the first
        pass and the fine windows (the MULTI-LEVEL schedule): stage 0
        sweeps the grid at ``coarse_strides[0]``, each later stage
        re-centres at step ``coarse_strides[i]`` inside the previous
        stage's ``±coarse_strides[i-1]`` bracket, and the fine pass runs
        the dense ``±coarse_strides[-1]`` window.  Rate pruning applies
        after stage 0 and ``coarse_seeds`` throttles every coarse stage,
        so the full ``n_runs`` seed budget is only ever spent on the
        final narrow window.

        Two further schedule hints tune that budget split:
        ``fine_radius`` widens (or narrows) the dense fine window to
        ``±fine_radius`` independently of the last coarse stride, and
        ``coarse_updates`` caps the simulated update horizon of every
        coarse stage (the fine pass always trains the full timeline) —
        a truncated-horizon coarse pass ranks basins almost as well at
        a fraction of the scan cost, and the wide full-horizon fine
        window absorbs the residual center drift.
        """
        with span("planner.refine_host"):
            S, G = grid.shape
            hints = refine_hints_for(objective)
            if G < max(2, hints.min_grid):
                return None, None
            schedulable = getattr(solve, "supports_mc_impl", False)
            ml = hints.coarse_strides if schedulable else None
            if ml is not None:
                ml = tuple(max(2, min(int(s), G - 1)) for s in ml)
            hz = hints.coarse_updates if schedulable else None
            # an objective's explicit stride hint is honoured as-is (clamped
            # to the grid); only the automatic work-minimising default applies
            stride = ((hints.fine_radius if schedulable else None)
                      or (ml[-1] if ml else
                          hints.stride or int(round(np.sqrt(G / 2.0)))))
            stride = max(2, min(int(stride), G - 1))
            cpos = coarse_indices(G, ml[0] if ml else stride)
            if cpos.size < 4:
                return None, None
            guided = schedulable and hints.coarse_seeds == 0
            K = hints.refine_rates if schedulable else None
            R = int(np.asarray(arrays["rates"]).shape[1])
            prune = K is not None and K < R
            scheduled = (guided or prune or ml is not None or hz is not None
                         or (schedulable and bool(hints.coarse_seeds
                                                  or hints.fine_radius)))

            if hints.tail_blocks:
                # first dense index inside the guarded sawtooth tail
                # (N / n_c <= tail_blocks); rows of `grid` are ascending
                tail = np.sum(
                    grid.astype(np.int64) * int(hints.tail_blocks)
                    < batch.N[:, None], axis=1)
            else:
                tail = None
            # tail windows vary per scenario: round the padded width up to a
            # multiple of 8 so a request stream compiles O(G / 8) fine-pass
            # shapes, not one per distinct tail length
            pad_multiple = 8 if tail is not None else 1
            # upper-bound the fine width BEFORE the coarse solve: bracket +
            # longest tail suffix (centers can only merge the two, never
            # widen them), so an unprofitable batch — e.g. one small-N
            # scenario whose guarded tail spans most of the log grid — costs
            # nothing instead of a wasted coarse pass on top of the dense one
            w_ub = 2 * stride + 1 + (G - int(tail.min()) if tail is not None
                                     else 0)
            if not scheduled and cpos.size + min(
                    G, self._pad_width(w_ub, pad_multiple)) >= G:
                return None, None  # two passes would outwork the dense solve

        if guided:
            # bound-guided coarse: the closed-form Corollary-1 solve on
            # the FULL grid supplies per-rate centers (already dense
            # indices) and the per-rate ranking, for ~zero simulation
            bound_arrays = {k: v for k, v in arrays.items()
                            if k not in ("mc_impl", "mc_seeds")}
            out1 = fleet_solve(BoundObjective())(bound_arrays, consts,
                                                 self.shard, batch)
        else:
            with span("planner.refine_host"):
                arrays1 = dict(arrays,
                               grid=np.ascontiguousarray(grid[:, cpos]))
                if schedulable and hints.coarse_seeds:
                    arrays1["mc_seeds"] = int(hints.coarse_seeds)
                if hz:
                    arrays1["mc_updates"] = int(hz)
            out1 = solve(arrays1, consts, self.shard, batch)

        with span("planner.refine_host"):
            if guided:
                centers = np.asarray(out1["gi_per_rate"], np.int64)
            else:
                centers1 = out1.get("gi_per_rate")
                if centers1 is None:  # pre-refinement custom kernel
                    return None, None
                centers = cpos[np.asarray(centers1, np.int64)]  # (S, R)
            sel = None
            if prune and "val_per_rate" in out1:
                # keep each scenario's top-K rates by the coarse per-rate
                # minima; ascending index order preserves the reduction's
                # rate-major tie-breaking among the kept rates
                vpr = np.asarray(out1["val_per_rate"])
                sel = np.sort(np.argsort(vpr, axis=1, kind="stable")[:, :K],
                              axis=1)                              # (S, K)
                centers = np.take_along_axis(centers, sel, axis=1)

        if ml is not None:
            # mid coarse stages: re-centre at each finer step inside the
            # previous stage's bracket.  Windows are host-built per-rate
            # index sets — clipping at the grid edges keeps the width
            # (hence the compiled shape) data-independent.
            for prev, step in zip(ml, ml[1:]):
                with span("planner.refine_host"):
                    offs = np.arange(-(prev // step),
                                     prev // step + 1) * step      # (O,)
                    win = np.clip(centers[:, :, None] + offs, 0, G - 1)
                    arrays_i = dict(arrays, grid=np.ascontiguousarray(
                        np.take_along_axis(grid[:, None, :], win, axis=2)))
                    if sel is not None:
                        arrays_i["rates"] = np.ascontiguousarray(
                            np.take_along_axis(
                                np.asarray(arrays["rates"]), sel, 1))
                        arrays_i["rate_mask"] = np.ascontiguousarray(
                            np.take_along_axis(
                                np.asarray(arrays["rate_mask"]), sel, 1))
                    if hints.coarse_seeds:
                        arrays_i["mc_seeds"] = int(hints.coarse_seeds)
                    if hz:
                        arrays_i["mc_updates"] = int(hz)
                out_i = solve(arrays_i, consts, self.shard, batch)
                with span("planner.refine_host"):
                    gi = np.asarray(out_i["gi_per_rate"], np.int64)
                    centers = np.take_along_axis(
                        win, gi[:, :, None], axis=2)[..., 0]

        with span("planner.refine_host"):
            count_w = refine_window_bounds(centers, stride, G, tail)[-1]
            W = min(G, self._pad_width(int(count_w.max()), pad_multiple))
            if not scheduled and cpos.size + W >= G:
                return None, None  # the merged windows still cover the grid

            if getattr(solve, "supports_refine_windows", False):
                # fused fine pass: windows are built and gathered on
                # device from (centers, tail_start); the host only sizes W
                arrays2 = dict(
                    arrays,
                    centers=np.ascontiguousarray(centers),
                    tail_start=(np.zeros(S, np.int64) + G if tail is None
                                else np.asarray(tail, np.int64)),
                    refine_stride=stride, refine_width=W)
            else:  # e.g. the Monte-Carlo kernel: host-built (S, R, W)
                _, win_grid, _ = refine_grid(grid, centers, stride,
                                             tail_start=tail, width=W)
                arrays2 = dict(arrays, grid=np.ascontiguousarray(win_grid))
            if sel is not None:
                arrays2["rates"] = np.ascontiguousarray(np.take_along_axis(
                    np.asarray(arrays["rates"]), sel, 1))
                arrays2["rate_mask"] = np.ascontiguousarray(
                    np.take_along_axis(np.asarray(arrays["rate_mask"]),
                                       sel, 1))
        out2 = solve(arrays2, consts, self.shard, batch)
        return out2, np.asarray(out2["sel_grid"])

    def cache_context(self, consts: BoundConstants,
                      grid_mode: Optional[str] = None) -> tuple:
        """The cache-key PREFIX ``plan_many`` scopes its entries under —
        ``(consts, grid width, grid mode[, width rule])``.  Exposed so a
        serving layer can address the exact entry a drifted session's
        plan lives at (``PlanCache.invalidate``) without re-deriving the
        planner's keying scheme."""
        mode = self._resolve_grid_mode(grid_mode)
        impl = self._resolve_mc_impl()
        # pow2-padded refine widths can evaluate (strictly more) window
        # points than the data-tight rule, so the two never share entries.
        # A non-default Monte-Carlo engine is tagged in too — the engines
        # are bitwise-matched per objective configuration, but scoping by
        # engine keeps a mis-matched build from ever aliasing plans (the
        # default "scan" resolution stays token-free so existing cache
        # layouts are unchanged).
        return (consts, self.grid_size, mode) + \
            (("pow2w",) if self.pow2_refine_widths else ()) + \
            (("mc_impl", impl) if impl != "scan" else ())

    def _warm_widths(self, G: int, stride: int, n_coarse: int) -> List[int]:
        """Every fine-pass width a stream of ``plan_batch`` calls over a
        ``G``-wide grid can reach under pow2 width padding: powers of two
        from the narrowest possible window (``stride + 1``, a fully
        edge-clamped bracket) up to the dense-fallback threshold."""
        widths: List[int] = []
        w = pow2ceil(stride + 1)
        while n_coarse + w < G:
            widths.append(w)
            w *= 2
        return widths

    def warm(self, scenarios: Sequence[Scenario], consts: BoundConstants,
             objective: Any = None, grid_mode: Optional[str] = None,
             pad_to: Optional[int] = None) -> int:
        """AOT warmup: compile every kernel shape that ``plan_batch`` /
        ``plan_many`` calls with this batch signature can hit, and return
        the number of fresh traces it cost.

        ``scenarios`` fixes the signature — the padded batch length ``S``
        (via ``pad_to``, e.g. a serving bucket), the rate width ``R`` and,
        for the Monte-Carlo objective, the padded scan length (pin it with
        the objective's ``min_updates`` floor).  The sweep compiles the
        dense solve (also the refine fallback) and, in ``"refine"`` mode,
        the coarse pass plus the fine pass at every reachable width.  The
        width sweep is exhaustive only under ``pow2_refine_widths`` (the
        data-tight default admits data-dependent widths no sweep can
        enumerate); a planning service therefore runs with pow2 widths,
        warms each configured ``(objective, grid_mode, bucket)`` and gets
        the zero-traces-after-warmup guarantee the serving tests assert.
        Results are discarded; the cache is never touched.
        """
        consts.validate()
        objective = self._resolve_objective(objective)
        mode = self._resolve_grid_mode(grid_mode)
        batch = ScenarioBatch.from_scenarios(
            _pad_batch(list(scenarios), pad_to))
        grid = self._default_grid(batch, objective)
        solve = fleet_solve(objective)
        arrays = self._kernel_arrays(solve, batch, grid)
        with trace_delta() as traces:
            self._warm_sweep(solve, arrays, consts, batch, grid, mode,
                             objective)
        return traces.total

    def _warm_sweep(self, solve, arrays, consts, batch, grid, mode,
                    objective) -> None:
        # dense pass — the "dense" mode solve AND the refine fallback
        solve(arrays, consts, self.shard, batch)
        if mode == "refine":
            S, G = grid.shape
            hints = refine_hints_for(objective)
            schedulable = getattr(solve, "supports_mc_impl", False)
            ml = hints.coarse_strides if schedulable else None
            if ml is not None:
                ml = tuple(max(2, min(int(s), G - 1)) for s in ml)
            hz = hints.coarse_updates if schedulable else None
            stride = ((hints.fine_radius if schedulable else None)
                      or (ml[-1] if ml else
                          hints.stride or int(round(np.sqrt(G / 2.0)))))
            stride = max(2, min(int(stride), G - 1))
            guided = schedulable and hints.coarse_seeds == 0
            K = hints.refine_rates if schedulable else None
            prune = K is not None and K < batch.n_rates
            scheduled = (guided or prune or ml is not None
                         or hz is not None
                         or (schedulable and bool(hints.coarse_seeds
                                                  or hints.fine_radius)))
            if G >= max(2, hints.min_grid):
                cpos = coarse_indices(G, ml[0] if ml else stride)
                if scheduled:
                    # a scheduled solve never falls back on width grounds
                    # (see _refine_solve), so the reachable fine widths
                    # run all the way to the bracket's pow2 ceiling
                    # (tail_blocks is None for simulated objectives, so
                    # the data-independent 2*stride+1 bound is exact)
                    if self.pow2_refine_widths:
                        widths, w = [], pow2ceil(stride + 1)
                        while w < min(G, pow2ceil(2 * stride + 1)):
                            widths.append(w)
                            w *= 2
                        widths.append(min(G, w))
                    else:
                        # data-tight rule: a fixed schedule reaches ONE
                        # width — the full bracket
                        widths = [min(G, 2 * stride + 1)]
                else:
                    widths = self._warm_widths(G, stride, cpos.size)
                if cpos.size >= 4 and widths:
                    if guided:
                        bound_arrays = {
                            k: v for k, v in arrays.items()
                            if k not in ("mc_impl", "mc_seeds")}
                        fleet_solve(BoundObjective())(
                            bound_arrays, consts, self.shard, batch)
                    else:
                        arrays1 = dict(
                            arrays,
                            grid=np.ascontiguousarray(grid[:, cpos]))
                        if schedulable and hints.coarse_seeds:
                            arrays1["mc_seeds"] = int(hints.coarse_seeds)
                        if hz:
                            arrays1["mc_updates"] = int(hz)
                        solve(arrays1, consts, self.shard, batch)  # coarse
                    n_rates = K if prune else batch.n_rates
                    centers = np.zeros((S, n_rates), np.int64)
                    tail_start = np.full(S, G, np.int64)
                    fine = dict(arrays)
                    if prune:
                        fine["rates"] = np.ascontiguousarray(
                            np.asarray(arrays["rates"])[:, :K])
                        fine["rate_mask"] = np.ascontiguousarray(
                            np.asarray(arrays["rate_mask"])[:, :K])
                    if ml is not None:
                        # mid coarse stages: one data-independent window
                        # shape per (prev, step) pair — clip keeps the
                        # width fixed, so dummy zero centers compile the
                        # exact shapes plan_batch will hit
                        for prev, step in zip(ml, ml[1:]):
                            offs = np.arange(-(prev // step),
                                             prev // step + 1) * step
                            win = np.clip(
                                centers[:, :, None] + offs, 0, G - 1)
                            arrays_i = dict(fine, grid=np.ascontiguousarray(
                                np.take_along_axis(grid[:, None, :], win,
                                                   axis=2)))
                            if hints.coarse_seeds:
                                arrays_i["mc_seeds"] = int(
                                    hints.coarse_seeds)
                            if hz:
                                arrays_i["mc_updates"] = int(hz)
                            solve(arrays_i, consts, self.shard, batch)
                    for W in widths:
                        if getattr(solve, "supports_refine_windows", False):
                            arrays2 = dict(fine, centers=centers,
                                           tail_start=tail_start,
                                           refine_stride=stride,
                                           refine_width=W)
                        else:  # host-built windows (e.g. Monte-Carlo)
                            _, win_grid, _ = refine_grid(grid, centers,
                                                         stride, width=W)
                            arrays2 = dict(
                                fine,
                                grid=np.ascontiguousarray(win_grid))
                        solve(arrays2, consts, self.shard, batch)

    def plan_many(self, scenarios: Sequence[Scenario],
                  consts: BoundConstants,
                  cache: Optional[PlanCache] = None,
                  pad_to: Optional[int] = None,
                  objective: Any = None,
                  grid_mode: Optional[str] = None,
                  timings: Optional[Dict[str, float]] = None
                  ) -> List[PlanRecord]:
        """Plan a request list, deduplicating through the cache.

        Cache hits (and in-batch duplicates, up to key quantisation) skip
        the solve; the remaining unique misses are padded — to ``pad_to``
        when given (a serving loop passes its micro-batch size so ONE
        kernel shape covers every batch), else to the next power of two —
        and solved in ONE ``plan_batch`` call.  Results come back in
        request order.  Cache entries are scoped to ``(consts, grid_size,
        grid_mode)`` AND the objective's ``cache_token()`` so one cache
        can serve several configurations, objectives AND grid modes
        without cross-talk: a refined plan can never answer a dense
        calibration request for the same scenario, even when the two
        coincide.

        ``timings``, when given, receives the phase attribution the
        serving spans report: ``cache_lookup_s`` (quantised-key probes +
        in-batch dedup) and ``solve_s`` (the ``plan_batch`` call,
        including result write-back) are ADDED into the dict, so a caller
        can pass one dict across several calls and read totals.
        """
        scenarios = list(scenarios)
        if not scenarios:
            return []
        objective = self._resolve_objective(objective)
        mode = self._resolve_grid_mode(grid_mode)

        def charge(phase: str, t0: float) -> float:
            now = time.perf_counter()
            if timings is not None:
                timings[phase] = timings.get(phase, 0.0) + (now - t0)
            return now

        records: List[Optional[PlanRecord]] = [None] * len(scenarios)
        count("lanes_live", len(scenarios))
        if cache is None:
            t0 = time.perf_counter()
            count("lanes_unique", len(scenarios))
            with span("planner.build"):
                padded = _pad_batch(scenarios, pad_to)
            fp = self.plan_batch(padded, consts, objective=objective,
                                 grid_mode=mode)
            with span("planner.records"):
                out = [fp.record(i) for i in range(len(scenarios))]
            charge("solve_s", t0)
            return out

        ctx = self.cache_context(consts, mode)
        miss: "OrderedDict[tuple, List[int]]" = OrderedDict()
        t0 = time.perf_counter()
        with span("planner.cache_lookup"):
            for i, sc in enumerate(scenarios):
                rec = cache.get(sc, context=ctx, objective=objective)
                if rec is not None:
                    records[i] = rec
                else:
                    miss.setdefault(
                        cache.key(sc, context=ctx, objective=objective),
                        []).append(i)
        t0 = charge("cache_lookup_s", t0)
        if miss:
            count("lanes_unique", len(miss))
            with span("planner.build"):
                reps = _pad_batch([scenarios[idxs[0]]
                                   for idxs in miss.values()], pad_to)
            fp = self.plan_batch(reps, consts, objective=objective,
                                 grid_mode=mode)
            with span("planner.records"):
                for j, idxs in enumerate(miss.values()):
                    rec = fp.record(j)
                    cache.put(scenarios[idxs[0]], rec, context=ctx,
                              objective=objective)
                    for i in idxs:
                        records[i] = rec
            charge("solve_s", t0)
        return records  # type: ignore[return-value]
