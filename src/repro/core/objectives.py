"""Pluggable planner-objective registry: one planning API for every objective.

The paper's planner minimises the closed-form Corollary-1 bound, but the
paper itself validates that bound against empirical Monte-Carlo SGD runs,
and burst-loss channels admit an exact Markov-reward evaluation the
stationary-loss bound cannot see.  Mirroring the link-model registry in
:mod:`repro.core.links`, this module turns "which scalar does the planner
minimise over the joint ``(rate, n_c)`` grid" into an extension point.

Every objective is a frozen dataclass registered in an
:class:`ObjectiveSpec` table under a stable string ``objective_id`` and
declares

  * a numpy reference evaluation — ``evaluate(scenario, consts, grid,
    rates) -> (R, G)`` objective values over the joint grid (what the
    scalar :class:`~repro.core.scenario.ObjectivePlanner` minimises with
    the canonical rate-major argmin tie-breaking);
  * an effective-overhead map — ``effective_overhead(scenario, n_c,
    rate)``, the link+topology reduction the plan's schedule/boundary are
    reported under (objectives that re-model the channel, e.g. the exact
    burst-aware ARQ solve, override it so the reported schedule matches
    the objective's own physics);
  * a cache signature — ``cache_token()``, a hashable tuple of the id and
    every hyperparameter the optimum depends on (Monte-Carlo seed count,
    data digest, ...); :class:`~repro.fleet.cache.PlanCache` folds it into
    the quantised key so two objectives can never alias one entry;
  * optionally a ``default_grid(N)`` — objectives with expensive
    evaluations (Monte Carlo) declare a coarser default search grid.

The jitted batched counterparts live in
:mod:`repro.fleet.objective_kernels`: registering a batched kernel under
the same ``objective_id`` lets ``FleetPlanner.plan_batch`` solve thousands
of scenarios against the objective in one compiled call (see README
"Planning objectives" for a worked custom-objective plugin).

Built-in objectives (ids are part of the cache contract — never reuse):

  ============  =========================  ================================
  id            class                      minimises
  ============  =========================  ================================
  corollary1    :class:`BoundObjective`    the paper's Corollary-1 bound
  montecarlo    :class:`MonteCarloObjective`  empirical mean final ridge loss
  markov_arq    :class:`MarkovARQObjective`   Corollary 1 under the EXACT
                                             burst-aware ARQ block time
  ============  =========================  ================================
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass
from functools import cached_property
from typing import (Any, ClassVar, Dict, Optional, Protocol, Tuple, Type,
                    runtime_checkable)

import numpy as np

from repro.core.bounds import BoundConstants, corollary1_bound


@runtime_checkable
class Objective(Protocol):
    """What the planners minimise over the joint ``(rate, n_c)`` grid.

    ``evaluate`` must be the REFERENCE semantics: the scalar planner
    minimises exactly this array, and any batched kernel registered in
    :mod:`repro.fleet.objective_kernels` must reproduce its argmin.
    """

    objective_id: ClassVar[str]

    def evaluate(self, scenario, consts, grid, rates) -> np.ndarray: ...

    def effective_overhead(self, scenario, n_c, rate): ...

    def cache_token(self) -> Tuple: ...


@dataclass(frozen=True)
class RefineHints:
    """Per-objective hints for the coarse->fine two-pass fleet solve.

    Objectives may expose an instance as a ``refine_hints`` attribute;
    :class:`~repro.fleet.planner.FleetPlanner` consults it in
    ``grid_mode="refine"``.

      * ``min_grid`` — dense fallback below this dense grid width: a grid
        too narrow to subsample leaves no work for refinement to cut (the
        ISSUE's "bracket would clip at grid edges" degenerate case).
      * ``stride`` — coarse subsampling stride; ``None`` picks the
        work-minimising ``round(sqrt(G / 2))`` (coarse pass ``G/k`` plus
        bracket ``2k + 1`` is minimal at ``k = sqrt(G/2)``).
      * ``tail_blocks`` — densely evaluate the grid suffix where
        ``N / n_c <= tail_blocks``: with few delivery blocks the bound's
        ``ceil(B_d)/B_d`` floor arithmetic is a sawtooth whose teeth a
        coarse bracket cannot follow.  ``None`` disables the guard —
        the Monte-Carlo objective does that (every tail lane would be a
        simulated training run, and its empirical landscape has no
        ``ceil(B_d)`` algebra), trading a small documented parity residue
        for the full lane cut.
      * ``coarse_seeds`` — SEED-COUNT SCHEDULE for simulated objectives:
        run the coarse pass with only this many Monte-Carlo seeds (the
        coarse pass only has to locate basins; the full ``n_runs`` seeds
        are spent where they matter, on the fine windows).  ``None``
        keeps the full seed count on both passes (the pinned reference
        behaviour).  ``0`` is the schedule's limit — a BOUND-GUIDED
        coarse pass: skip the Monte-Carlo coarse solve entirely and take
        the per-rate window centers from a full-grid Corollary-1 solve
        (the closed-form bound as a zeroth-order estimate of the
        empirical landscape; it is ~4 orders of magnitude cheaper than
        one simulated grid point, so the fine pass becomes the whole
        cost).  Ignored by objectives whose kernels don't accept a seed
        override.
      * ``refine_rates`` — keep only the best ``refine_rates`` rates per
        scenario (ranked by the coarse pass's per-rate minima) in the
        fine pass.  ``None`` refines every rate (the pinned reference
        behaviour).  For simulated objectives every pruned rate removes
        a full row of training simulations from the fine pass.
      * ``coarse_strides`` — MULTI-LEVEL STRIDE SCHEDULE (overrides
        ``stride``): a descending tuple such as ``(32, 6)``.  Stage 0
        sweeps the full grid at stride ``coarse_strides[0]`` over all
        rates; each later stage ``i`` re-centres at step
        ``coarse_strides[i]`` within ``±coarse_strides[i - 1]`` of the
        previous stage's per-rate winners; the fine pass then evaluates
        the dense ``±coarse_strides[-1]`` window.  ``coarse_seeds``
        applies to EVERY coarse stage and ``refine_rates`` prunes after
        stage 0, so with ``(32, 6)``/1 seed/1 rate a 128-point grid
        costs ~62 simulated lane-runs instead of 1280.  Ignored by
        objectives whose kernels don't accept a seed override.
      * ``fine_radius`` — widen the dense fine window to ``±fine_radius``
        grid steps, decoupled from the last coarse stride.  A window
        wider than ``±coarse_strides[-1]`` buys back the center drift a
        throttled (few-seed / short-horizon) coarse schedule introduces:
        the full-seed fine pass re-ranks everything inside the bracket,
        so a mildly mis-centred window still recovers the dense argmin.
      * ``coarse_updates`` — HORIZON SCHEDULE for simulated objectives:
        cap every coarse stage's simulated update timeline at this many
        update slots (the fine pass always trains the full horizon).
        Basin ranking stabilises long before training converges, so a
        quarter-horizon coarse pass costs ~1/4 the scan work at nearly
        unchanged fine-pass outcomes; far below that the truncated
        landscape no longer resembles the converged one, so pair a
        small cap with a generous ``fine_radius``.  Ignored by
        objectives whose kernels don't accept a horizon override.
    """

    min_grid: int = 32
    stride: Optional[int] = None
    tail_blocks: Optional[int] = 32
    coarse_seeds: Optional[int] = None
    refine_rates: Optional[int] = None
    coarse_strides: Optional[Tuple[int, ...]] = None
    fine_radius: Optional[int] = None
    coarse_updates: Optional[int] = None


def refine_hints_for(objective) -> RefineHints:
    """The objective's declared refinement hints (registry default if none)."""
    hints = getattr(objective, "refine_hints", None)
    return hints if isinstance(hints, RefineHints) else RefineHints()


@dataclass(frozen=True)
class ObjectiveSpec:
    """Registry entry: the stable id and the objective class."""

    objective_id: str
    name: str
    cls: type


_SPECS_BY_ID: Dict[str, ObjectiveSpec] = {}
_SPECS_BY_CLS: Dict[type, ObjectiveSpec] = {}


def register_objective(cls: Type) -> Type:
    """Class decorator: add an objective class to the registry.

    The class must carry a non-empty string class attribute
    ``objective_id`` (unique) and implement the :class:`Objective` surface
    (``evaluate``, ``effective_overhead``, ``cache_token``).
    """
    objective_id = getattr(cls, "objective_id", None)
    if not isinstance(objective_id, str) or not objective_id:
        raise ValueError(
            f"{cls.__name__}.objective_id must be a non-empty str, got "
            f"{objective_id!r}")
    missing = [m for m in ("evaluate", "effective_overhead", "cache_token")
               if not callable(getattr(cls, m, None))]
    if missing:
        raise TypeError(
            f"{cls.__name__} is missing Objective methods {missing}")
    prior = _SPECS_BY_ID.get(objective_id)
    if prior is not None and prior.cls is not cls:
        raise ValueError(
            f"objective_id {objective_id!r} already registered by "
            f"{prior.name}")
    spec = ObjectiveSpec(objective_id=objective_id, name=cls.__name__,
                         cls=cls)
    _SPECS_BY_ID[objective_id] = spec
    _SPECS_BY_CLS[cls] = spec
    return cls


def unregister_objective(objective_id: str) -> None:
    """Remove a registry entry (plugin teardown / tests).  No-op if absent."""
    spec = _SPECS_BY_ID.pop(objective_id, None)
    if spec is not None:
        _SPECS_BY_CLS.pop(spec.cls, None)


def objective_spec(objective_id: str) -> ObjectiveSpec:
    """Spec for a registered id (KeyError with guidance if not)."""
    try:
        return _SPECS_BY_ID[objective_id]
    except KeyError:
        raise KeyError(
            f"no objective registered under objective_id {objective_id!r}; "
            f"known ids: {sorted(_SPECS_BY_ID)}") from None


def objective_spec_for(objective_or_cls) -> ObjectiveSpec:
    """Spec for an objective instance or class (KeyError if unregistered)."""
    cls = (objective_or_cls if isinstance(objective_or_cls, type)
           else type(objective_or_cls))
    try:
        return _SPECS_BY_CLS[cls]
    except KeyError:
        raise KeyError(
            f"{cls.__name__} is not a registered objective; decorate it "
            "with repro.core.objectives.register_objective") from None


def registered_objectives() -> Tuple[ObjectiveSpec, ...]:
    """All registered specs, sorted by ``objective_id``."""
    return tuple(_SPECS_BY_ID[k] for k in sorted(_SPECS_BY_ID))


def mc_default_grid(N: int, n_points: int = 12) -> np.ndarray:
    """Coarse log grid for Monte-Carlo objectives (MC is expensive)."""
    g = np.unique(np.round(
        np.logspace(0, np.log10(N), n_points)).astype(np.int64))
    return g[g >= 1]


def _corollary1_grid(objective, scenario, consts: BoundConstants, grid,
                     rates) -> np.ndarray:
    """Corollary 1 over the joint grid at the OBJECTIVE's effective
    overhead — one broadcast call, shared by every bound-shaped objective
    so the ``p_good == p_bad`` bitwise-reduction contract between
    :class:`BoundObjective` and :class:`MarkovARQObjective` can never
    drift (they differ ONLY through ``effective_overhead``)."""
    consts.validate()
    grid = np.asarray(grid)
    rates = np.asarray(rates, np.float64)
    n_o_eff = objective.effective_overhead(scenario, grid[None, :],
                                           rates[:, None])
    return corollary1_bound(
        np.broadcast_to(grid[None, :].astype(np.float64), n_o_eff.shape),
        N=scenario.N, T=scenario.T, n_o=n_o_eff, tau_p=scenario.tau_p,
        consts=consts)


# ---------------------------------------------------------------------------
# built-in objectives
# ---------------------------------------------------------------------------


@register_objective
@dataclass(frozen=True)
class BoundObjective:
    """The paper's recipe: Corollary 1 on the joint ``(rate, n_c)`` grid.

    This is the objective extracted verbatim from the pre-registry
    ``BoundPlanner.plan`` — one broadcast :func:`corollary1_bound` call,
    no Python loop — so plans are bitwise-identical to the old path.
    """

    objective_id: ClassVar[str] = "corollary1"
    #: bound-shaped objectives keep the guarded sawtooth tail (see
    #: :class:`RefineHints`) so coarse->fine plans stay argmin-identical
    #: to the dense solve throughout the small-block-count suffix; the
    #: wide fixed stride trades a few extra bracket lanes for a basin
    #: window that also absorbs the bound's resolved-region micro-teeth
    refine_hints: ClassVar[RefineHints] = RefineHints(stride=16)

    def evaluate(self, scenario, consts: BoundConstants, grid, rates):
        return _corollary1_grid(self, scenario, consts, grid, rates)

    def effective_overhead(self, scenario, n_c, rate):
        return scenario.effective_overhead(n_c, rate)

    def cache_token(self) -> Tuple:
        return (self.objective_id,)


@register_objective
@dataclass(frozen=True)
class MarkovARQObjective:
    """Corollary 1 under the EXACT burst-aware expected per-block ARQ time.

    A Gilbert-Elliott link plans, by default, through its stationary loss
    probability — inflation ``1 / (1 - p_bar)`` — which ignores that a
    failed attempt is evidence of the bad state, so failures cluster and
    retransmission runs on sticky chains last longer than the memoryless
    model predicts.  This objective evaluates the same Corollary-1 bound
    but with the expected block duration taken from the link's
    ``exact_expected_block_time`` — the per-(rate, state) Markov-reward
    linear solve in
    :meth:`~repro.core.links.GilbertElliottLink.exact_arq_inflation` —
    whenever the link exposes one, falling back to the stationary
    ``expected_block_time`` otherwise.

    Contracts (tested): for memoryless links, and for a Gilbert-Elliott
    chain with ``p_good == p_bad``, the objective array is bitwise equal to
    :class:`BoundObjective`'s, so the plans coincide exactly; on sticky
    chains the burst-aware plan achieves a strictly lower exact expected
    block time than the stationary-approximation plan.
    """

    objective_id: ClassVar[str] = "markov_arq"
    refine_hints: ClassVar[RefineHints] = RefineHints(stride=16)

    def evaluate(self, scenario, consts: BoundConstants, grid, rates):
        return _corollary1_grid(self, scenario, consts, grid, rates)

    def effective_overhead(self, scenario, n_c, rate):
        if np.any(np.asarray(rate, np.float64) <= 0.0):
            raise ValueError(f"rate must be > 0, got {rate}")
        link = scenario.link
        block_time = getattr(link, "exact_expected_block_time", None)
        if not callable(block_time):
            block_time = link.expected_block_time
        n_c = np.asarray(n_c, np.float64)
        dur = block_time(n_c, scenario.union_overhead, rate)
        return dur - n_c

    def cache_token(self) -> Tuple:
        return (self.objective_id,)


@register_objective
@dataclass(frozen=True, eq=False)
class MonteCarloObjective:
    """Empirical objective: Monte-Carlo mean of the realised final ridge
    loss (the paper's experimental ``n_c*`` search, Sec. 5).

    The reference evaluation is the existing scalar Monte-Carlo path
    (:func:`repro.core.montecarlo.montecarlo_objective_grid`, one vmapped
    seed batch per grid point); the batched fleet kernel vmaps the SAME
    seed streams over scenarios x rates x grid points so fleet plans match
    the scalar planner seed-for-seed.

    ``eq=False``: instances hold the training arrays, so identity (not
    array comparison) is the right equality — the fleet kernel cache keys
    on the instance, reuse one instance per request stream.
    """

    objective_id: ClassVar[str] = "montecarlo"

    X: Any = None
    y: Any = None
    lam: float = 0.05
    alpha: float = 1e-4
    n_runs: int = 3
    seed: int = 0
    grid_points: int = 12  # MC is expensive: default to a coarse grid
    #: floor on the batched kernel's padded update-timeline length.  The
    #: fleet kernel pads its shared ``lax.scan`` to the batch's largest
    #: update count; a serving layer sets this floor so every batch below
    #: it compiles to ONE scan length (padded slots no-op, so plans are
    #: unchanged — deliberately NOT part of ``cache_token``).
    min_updates: int = 0
    #: common random numbers: share ONE uniform draw per update slot
    #: across every simulation lane (all scenarios, rates and grid
    #: points) instead of drawing a per-lane sample index.  The sampled
    #: index is the comonotone ``floor(u * a)``, so nearby grid points
    #: see maximally-correlated trajectories and their loss DIFFERENCES
    #: (what the argmin consumes) converge with far fewer seeds.  A
    #: different (documented) estimator of the same objective: plans are
    #: not bitwise-pinned to the ``crn=False`` reference stream.
    crn: bool = False
    #: per-run RNG-key derivation: ``"fold_in"`` (default) derives run
    #: ``r``'s key as ``fold_in(PRNGKey(seed), r)`` — collision-free
    #: across (seed, run) pairs; ``"legacy"`` reproduces the historical
    #: ``PRNGKey(seed + 97 r)`` streams, which ALIAS across nearby
    #: objective seeds (seed=0 run 1 == seed=97 run 0) and are kept only
    #: as a pinned compatibility mode.
    seed_stream: str = "fold_in"
    #: optional seed-count schedule / rate pruning for the coarse->fine
    #: solve (folded into :attr:`refine_hints`; see
    #: :class:`RefineHints.coarse_seeds` / ``refine_rates``).  ``None``
    #: keeps the reference two-pass behaviour.
    coarse_seeds: Optional[int] = None
    refine_rates: Optional[int] = None
    #: multi-level stride schedule for the refine solve (see
    #: :class:`RefineHints.coarse_strides`); a descending tuple of
    #: positive ints, e.g. ``(32, 6)``.  ``None`` keeps the single
    #: coarse pass at :attr:`RefineHints.stride`.
    coarse_strides: Optional[Tuple[int, ...]] = None
    #: fine-window radius / coarse-pass horizon cap for the refine solve
    #: (see :class:`RefineHints.fine_radius` / ``coarse_updates``).
    #: ``None`` keeps the fine window at the last coarse stride and the
    #: coarse stages on the full update timeline.
    fine_radius: Optional[int] = None
    coarse_updates: Optional[int] = None

    def __post_init__(self):
        if self.X is None or self.y is None:
            raise ValueError("MonteCarloObjective needs the ridge task "
                             "data: MonteCarloObjective(X=..., y=...)")
        if self.n_runs < 1:
            raise ValueError(f"n_runs must be >= 1, got {self.n_runs}")
        if self.min_updates < 0:
            raise ValueError(
                f"min_updates must be >= 0, got {self.min_updates}")
        if self.seed_stream not in ("fold_in", "legacy"):
            raise ValueError(
                f"seed_stream must be 'fold_in' or 'legacy', got "
                f"{self.seed_stream!r}")
        if self.coarse_seeds is not None and self.coarse_seeds < 0:
            raise ValueError(
                f"coarse_seeds must be >= 0 or None, got "
                f"{self.coarse_seeds}")
        if self.refine_rates is not None and self.refine_rates < 1:
            raise ValueError(
                f"refine_rates must be >= 1 or None, got "
                f"{self.refine_rates}")
        if self.coarse_strides is not None:
            strides = tuple(int(s) for s in self.coarse_strides)
            if not strides or any(s < 1 for s in strides):
                raise ValueError(
                    f"coarse_strides must be a non-empty tuple of "
                    f"positive ints, got {self.coarse_strides!r}")
            if any(a <= b for a, b in zip(strides, strides[1:])):
                raise ValueError(
                    f"coarse_strides must be strictly descending, got "
                    f"{self.coarse_strides!r}")
            object.__setattr__(self, "coarse_strides", strides)
        if self.fine_radius is not None and self.fine_radius < 1:
            raise ValueError(
                f"fine_radius must be >= 1 or None, got "
                f"{self.fine_radius}")
        if self.coarse_updates is not None and self.coarse_updates < 1:
            raise ValueError(
                f"coarse_updates must be >= 1 or None, got "
                f"{self.coarse_updates}")

    #: Monte-Carlo refinement hints: a capped engagement width (the
    #: default 12-point MC grid leaves nothing to refine — refinement
    #: engages on explicitly widened grids) and NO sawtooth-tail guard:
    #: every tail point would be a full simulated training run, which is
    #: exactly the work refinement exists to eliminate, and the empirical
    #: loss has no ceil(B_d)/B_d algebra driving the bound's tail teeth.
    #: stride 10 (vs the sqrt(G/2) default) widens the bracket: the
    #: empirical loss landscape is seed-noise-ragged near the optimum, and
    #: the wider window recovers most of the raggedness while the fine
    #: pass still skips most of the dense pass's simulated lanes.
    #: The instance's seed schedule (``coarse_seeds`` / ``refine_rates``)
    #: folds in here, so the planner reads ONE hints object.
    @property
    def refine_hints(self) -> RefineHints:
        return RefineHints(min_grid=24, stride=10, tail_blocks=None,
                           coarse_seeds=self.coarse_seeds,
                           refine_rates=self.refine_rates,
                           coarse_strides=self.coarse_strides,
                           fine_radius=self.fine_radius,
                           coarse_updates=self.coarse_updates)

    def evaluate(self, scenario, consts, grid, rates):
        from repro.core.montecarlo import montecarlo_objective_grid

        return montecarlo_objective_grid(
            self.X, self.y, scenario, grid, rates, lam=self.lam,
            alpha=self.alpha, n_runs=self.n_runs, seed=self.seed,
            seed_stream=self.seed_stream)

    def effective_overhead(self, scenario, n_c, rate):
        return scenario.effective_overhead(n_c, rate)

    def default_grid(self, N: int) -> np.ndarray:
        return mc_default_grid(N, self.grid_points)

    @property
    def default_grid_size(self) -> int:
        """Cap on the DEFAULT fleet grid width: every grid point is a
        simulated training run, so a bound-sized grid would multiply the
        batched solve cost ~10x (explicit ``grid=`` overrides)."""
        return self.grid_points

    @cached_property
    def data_digest(self) -> str:
        """Content hash of (X, y): two objectives over different data must
        never share a cache entry even if every hyperparameter matches."""
        h = hashlib.sha1()
        for a in (self.X, self.y):
            a = np.ascontiguousarray(np.asarray(a))
            h.update(str(a.shape).encode())
            h.update(str(a.dtype).encode())
            h.update(a.tobytes())
        return h.hexdigest()[:16]

    def cache_token(self) -> Tuple:
        # grid_points is part of the token: it sets the DEFAULT search
        # grid (scalar default_grid and the fleet default_grid_size cap),
        # so two objectives differing only in it can plan different n_c.
        # crn / seed_stream change the estimator's sample streams and the
        # seed schedule changes which lanes even get simulated — none of
        # those variants may ever alias a reference plan in the cache.
        return (self.objective_id, int(self.n_runs), int(self.seed),
                float(self.lam), float(self.alpha), int(self.grid_points),
                self.data_digest, bool(self.crn), str(self.seed_stream),
                self.coarse_seeds, self.refine_rates, self.coarse_strides,
                self.fine_radius, self.coarse_updates)
