"""Federated round planning: WHO participates, and at WHAT operating point.

The fleet engine plans every scenario independently; a federated round
couples them.  Given a population of candidate devices (plain
:class:`~repro.core.scenario.Scenario` objects — mixed link families
welcome, Gilbert-Elliott burst chains are the natural stragglers), a
round must pick a participant set and give each participant a
``(rate, n_c)`` operating point such that every participant's local run
finishes by the shared round deadline ``T`` (Corollary 1's
full-delivery regime), and the AGGREGATED loss bound

    ``F(K) = (1/K) sum_{i in topK} b_i - sigma (1 - 1/K)``

is minimal — see :mod:`repro.federated.round_kernels` for the model and
the jitted solve.  :class:`RoundPlanner` is the host wrapper: pad the
population (pow2 or an explicit serving bucket — pad lanes carry a
``valid=False`` flag so they can never join the round), run the one
jitted call, unpad, and return a :class:`RoundPlan`.

``plan_round_reference`` is the scalar-ish numpy oracle (per-device
numpy grids + stable sort + prefix scans) and ``plan_round_bruteforce``
the exponential subset enumeration for small populations; the federated
tests pin the planner to both.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.bounds import BoundConstants, corollary1_bound
from repro.core.objectives import BoundObjective
from repro.core.planner import fleet_grid
from repro.core.scenario import Scenario
from repro.federated.round_kernels import round_solve
from repro.fleet.batch import ScenarioBatch
from repro.fleet.cache import quantise, scenario_key
from repro.fleet.objective_kernels import _maybe_shard
from repro.fleet.planner import _pad_batch
from repro.fleet.tracing import trace_delta
from repro.obs.runtime import record_solve

#: The objective token federated cache entries are scoped under — plays
#: the role ``Objective.cache_token()`` plays for per-device plans, so a
#: federated entry can never alias a single-device plan (see the
#: PlanCache isolation tests).
FEDERATED_TOKEN: Tuple[str, ...] = ("federated_corollary1",)


def population_key(population: Sequence[Scenario], deadline: float,
                   sig_digits: int = 3) -> Tuple:
    """Hashable quantised signature of a ROUND request: the request kind,
    the population size, the quantised round deadline and every member's
    :func:`~repro.fleet.cache.scenario_key` in population order.  Device
    order matters (it is the argmin tie-breaker), so no canonicalisation:
    two requests share an entry only if they are the same population."""
    return ("federated_round", len(population),
            quantise(float(deadline), sig_digits),
            tuple(scenario_key(sc, sig_digits) for sc in population))


@dataclass(frozen=True)
class RoundRecord:
    """Lightweight per-round result — what the cache stores and the
    serving layer streams back.  Per-participant tuples are ordered by
    ascending device index (the ``participants`` order)."""

    participants: Tuple[int, ...]
    n_participants: int
    deadline: float
    round_time: float
    objective_value: float
    n_eligible: int
    feasible: bool
    n_c: Tuple[int, ...]
    rate: Tuple[float, ...]
    objective: str = "federated_corollary1"


@dataclass(frozen=True)
class RoundPlan:
    """Struct-of-arrays round plan over the REAL population (pad lanes
    already stripped).  ``order`` is the full eligibility-then-bound sort
    of the population; the participant set is its first ``k_best``
    entries.  Per-device arrays cover every candidate — non-participants
    keep their best-feasible operating point (or ``inf``/garbage lanes
    when ineligible, flagged by ``eligible``) so callers can inspect the
    margin of devices that just missed the cut."""

    deadline: float
    order: np.ndarray            # (S,) int64  devices by ascending bound
    k_best: int                  # chosen participant count (0: infeasible)
    objective_value: float       # F(k_best); +inf when infeasible
    objective_curve: np.ndarray  # (S,) float64 F(K) for K = 1..S
    round_time: float            # straggler completion; +inf if infeasible
    n_eligible: int
    n_c: np.ndarray              # (S,) int64   per-device block size
    rate: np.ndarray             # (S,) float64 per-device rate
    bound_value: np.ndarray      # (S,) float64 best-feasible Corollary-1
    p_err: np.ndarray            # (S,) float64 loss prob at chosen rate
    n_o_eff: np.ndarray          # (S,) float64 effective overhead
    completion_time: np.ndarray  # (S,) float64 at the chosen point
    eligible: np.ndarray         # (S,) bool    has any feasible point

    def __len__(self) -> int:
        return int(self.order.shape[0])

    @property
    def feasible(self) -> bool:
        return self.k_best >= 1

    @property
    def participants(self) -> np.ndarray:
        """Chosen device indices, ascending."""
        return np.sort(self.order[:self.k_best])

    def record(self) -> RoundRecord:
        part = self.participants
        return RoundRecord(
            participants=tuple(int(i) for i in part),
            n_participants=int(self.k_best),
            deadline=float(self.deadline),
            round_time=float(self.round_time),
            objective_value=float(self.objective_value),
            n_eligible=int(self.n_eligible),
            feasible=self.feasible,
            n_c=tuple(int(self.n_c[i]) for i in part),
            rate=tuple(float(self.rate[i]) for i in part))


@dataclass(frozen=True)
class RoundPlanner:
    """One-jitted-call federated round planner over a population.

    ``grid_size`` is the per-device ``n_c`` grid width G (log-spaced
    1..N per device via :func:`~repro.core.planner.fleet_grid`, exactly
    the fleet planner's rule); ``shard`` lays the population out over the
    local "fleet" mesh like every fleet kernel.  The compiled shape is
    ``(S_pad, R, G)`` — pad populations to serving buckets with
    ``pad_to`` and :meth:`warm` each bucket to keep the zero
    post-warmup-traces guarantee.
    """

    grid_size: int = 64
    shard: bool = True

    @staticmethod
    def resolve_deadline(population: Sequence[Scenario]) -> float:
        """Default round deadline: the population's tightest per-device
        deadline (every member's own ``T`` honours it)."""
        return float(min(sc.T for sc in population))

    def cache_context(self, consts: BoundConstants) -> tuple:
        """Cache-key prefix round entries are scoped under (the federated
        analogue of ``FleetPlanner.cache_context``)."""
        return ("federated", consts, self.grid_size)

    def plan_round(self, population: Sequence[Scenario],
                   consts: BoundConstants, *,
                   deadline: Optional[float] = None,
                   pad_to: Optional[int] = None) -> RoundPlan:
        """Solve one federated round over the population."""
        population = list(population)
        if not population:
            raise ValueError("population must be non-empty")
        if deadline is None:
            deadline = self.resolve_deadline(population)
        S_real = len(population)
        batch = ScenarioBatch.from_scenarios(_pad_batch(population, pad_to))
        return self.plan_round_batch(batch, consts, deadline=deadline,
                                     n_real=S_real)

    def plan_round_batch(self, batch: ScenarioBatch,
                         consts: BoundConstants, *,
                         deadline: Optional[float] = None,
                         n_real: Optional[int] = None,
                         grid: Optional[np.ndarray] = None) -> RoundPlan:
        """Solve a round over a PREBUILT (already padded) batch.

        The zero-conversion entry point: callers that already hold a
        :class:`~repro.fleet.batch.ScenarioBatch` at a warmed pad shape
        (a serving layer, or ``bench_federated``'s timed loop — the same
        prebuilt-batch contract ``FleetPlanner.plan_batch`` times) skip
        the per-call ``Scenario`` -> arrays conversion.  The first
        ``n_real`` lanes are the real population (default: all of them);
        trailing lanes are padding and can never join the round.
        ``grid`` overrides the per-device ``n_c`` grid (must be
        ``(S, G)``; default :func:`~repro.core.planner.fleet_grid` at
        ``grid_size``); ``deadline`` defaults to the tightest real
        per-device ``T`` in the batch.
        """
        consts.validate()
        S = len(batch)
        n_real = S if n_real is None else int(n_real)
        if not 1 <= n_real <= S:
            raise ValueError(
                f"n_real={n_real} outside 1..{S} (batch size)")
        if deadline is None:
            deadline = float(np.min(batch.T[:n_real]))
        deadline = float(deadline)
        if deadline <= 0.0:
            raise ValueError(f"deadline must be > 0, got {deadline}")
        if grid is None:
            grid = fleet_grid(batch.N, self.grid_size)
        grid = np.ascontiguousarray(grid)
        if grid.ndim != 2 or grid.shape[0] != S:
            raise ValueError(
                f"grid has shape {grid.shape}, want ({S}, G)")
        S_real = n_real
        valid = np.zeros(S, bool)
        valid[:S_real] = True
        arrays = {
            "N": np.asarray(batch.N, np.int64),
            "union_no": batch.union_overhead,
            "tau_p": np.asarray(batch.tau_p, np.float64),
            "rates": np.asarray(batch.rates, np.float64),
            "rate_mask": batch.rate_mask,
            "grid": grid,
            "link_model_id": np.asarray(batch.link_model_id, np.int32),
            "link_params": np.asarray(batch.link_params, np.float64),
            "valid": valid,
        }
        fn = round_solve()
        with jax.enable_x64(True):
            if self.shard:
                arrays = _maybe_shard(arrays, S)
            t0 = time.perf_counter()
            out = fn(T=np.float64(deadline),
                     sigma=np.float64(consts.variance_floor),
                     e0=np.float64(consts.init_gap),
                     contraction=np.float64(consts.contraction), **arrays)
            jax.block_until_ready(out)
            t1 = time.perf_counter()
            res = {k: np.asarray(v) for k, v in out.items()}
            record_solve(t1 - t0, time.perf_counter() - t1)

        # unpad: pad lanes are never eligible, so the eligible prefix of
        # the sort consists of real devices only — dropping pad indices
        # from `order` keeps the participant prefix intact
        order = res["order"]
        order_real = np.asarray(order[order < S_real], np.int64)
        n_eligible = int(res["n_eligible"])
        feasible = n_eligible >= 1
        return RoundPlan(
            deadline=deadline,
            order=order_real,
            k_best=int(res["k_best"]) if feasible else 0,
            objective_value=float(res["objective_value"]) if feasible
            else np.inf,
            objective_curve=res["objective_curve"][:S_real],
            round_time=float(res["round_time"]) if feasible else np.inf,
            n_eligible=n_eligible,
            n_c=res["n_c"][:S_real],
            rate=res["rate"][:S_real],
            bound_value=res["bound_value"][:S_real],
            p_err=res["p_err"][:S_real],
            n_o_eff=res["n_o_eff"][:S_real],
            completion_time=res["completion_time"][:S_real],
            eligible=res["eligible"][:S_real])

    def warm(self, population: Sequence[Scenario], consts: BoundConstants,
             pad_to: Optional[int] = None) -> int:
        """AOT warmup: compile the round solve at this population's padded
        shape and return the number of fresh traces it cost.  Results are
        discarded; one call per serving population bucket gives the round
        path the zero-traces-after-warmup guarantee."""
        with trace_delta() as traces:
            self.plan_round(list(population), consts, pad_to=pad_to)
        return traces.total


# ---------------------------------------------------------------------------
# numpy references
# ---------------------------------------------------------------------------


def _best_feasible_numpy(sc: Scenario, consts: BoundConstants,
                         deadline: float, grid_size: int):
    """One device's feasibility-masked joint grid + rate-major argmin,
    in numpy, mirroring the kernel's inner sweep op-for-op."""
    obj = BoundObjective()
    grid = fleet_grid(sc.N, grid_size)                         # (G,)
    rates = np.asarray(sc.link.rates, np.float64)              # (R,)
    g = grid[None, :].astype(np.float64)
    n_o_eff = obj.effective_overhead(sc, g, rates[:, None])    # (R, G)
    vals = corollary1_bound(np.broadcast_to(g, n_o_eff.shape),
                            N=sc.N, T=deadline, n_o=n_o_eff,
                            tau_p=sc.tau_p, consts=consts)
    completion = np.ceil(float(sc.N) / g) * (g + n_o_eff)
    masked = np.where(completion <= deadline, vals, np.inf)
    flat = int(np.argmin(masked))          # C-order == rate-major
    ri, gi = divmod(flat, grid.shape[0])
    return {
        "bound": float(masked[ri, gi]),
        "completion": float(completion[ri, gi]),
        "n_c": int(grid[gi]), "rate": float(rates[ri]),
        "n_o_eff": float(n_o_eff[ri, gi]),
    }


def _participation_curve(best_b: np.ndarray, best_t: np.ndarray,
                         sigma: float):
    """Stable sort + prefix scans over per-device bests — the numpy
    mirror of the kernel's participation axis."""
    S = best_b.shape[0]
    eligible = np.isfinite(best_b)
    sort_key = np.where(eligible, best_b, np.inf)
    order = np.argsort(sort_key, kind="stable")
    K = np.arange(1, S + 1, dtype=np.float64)
    curve = np.cumsum(sort_key[order]) / K - sigma * (1.0 - 1.0 / K)
    n_eligible = int(eligible.sum())
    curve = np.where(np.arange(1, S + 1) <= n_eligible, curve, np.inf)
    t_sorted = np.where(eligible, best_t, -np.inf)[order]
    return order, curve, np.maximum.accumulate(t_sorted), n_eligible


def plan_round_reference(population: Sequence[Scenario],
                         consts: BoundConstants, *,
                         deadline: Optional[float] = None,
                         grid_size: int = 64) -> RoundPlan:
    """The numpy oracle: per-device scalar grid evaluations (a Python
    loop over the population — this IS the baseline ``bench_federated``
    measures the jitted planner against) followed by the same stable
    sort + prefix scans.  Argmin-identical to :meth:`RoundPlanner.
    plan_round` wherever the backend libm agrees (the federated parity
    tests assert participant sets and operating points exactly)."""
    consts.validate()
    population = list(population)
    if not population:
        raise ValueError("population must be non-empty")
    if deadline is None:
        deadline = RoundPlanner.resolve_deadline(population)
    deadline = float(deadline)
    S = len(population)
    best = [_best_feasible_numpy(sc, consts, deadline, grid_size)
            for sc in population]
    best_b = np.asarray([d["bound"] for d in best])
    best_t = np.asarray([d["completion"] for d in best])
    sigma = float(consts.variance_floor)
    order, curve, cmax, n_eligible = _participation_curve(best_b, best_t,
                                                          sigma)
    feasible = n_eligible >= 1
    k_best = int(np.argmin(curve)) + 1 if feasible else 0
    return RoundPlan(
        deadline=deadline, order=np.asarray(order, np.int64),
        k_best=k_best,
        objective_value=float(curve[k_best - 1]) if feasible else np.inf,
        objective_curve=curve,
        round_time=float(cmax[k_best - 1]) if feasible else np.inf,
        n_eligible=n_eligible,
        n_c=np.asarray([d["n_c"] for d in best], np.int64),
        rate=np.asarray([d["rate"] for d in best]),
        bound_value=best_b,
        p_err=np.asarray([0.0] * S),  # not replicated by the oracle
        n_o_eff=np.asarray([d["n_o_eff"] for d in best]),
        completion_time=best_t,
        eligible=np.isfinite(best_b))


def plan_round_bruteforce(population: Sequence[Scenario],
                          consts: BoundConstants, *,
                          deadline: Optional[float] = None,
                          grid_size: int = 64) -> RoundRecord:
    """Exponential ground truth for SMALL populations: scalar double loop
    over every device's ``(rate, n_c)`` points, then every nonempty
    subset of eligible devices scored by the aggregation objective (sums
    accumulated in global sorted order so float rounding matches the
    prefix-scan path).  Ties prefer smaller F, then smaller K, then the
    lexicographically smallest participant tuple."""
    consts.validate()
    population = list(population)
    S = len(population)
    if S > 16:
        raise ValueError(f"brute force caps at 16 devices, got {S}")
    if deadline is None:
        deadline = RoundPlanner.resolve_deadline(population)
    deadline = float(deadline)
    obj = BoundObjective()
    sigma = float(consts.variance_floor)

    best: List[dict] = []
    for sc in population:
        grid = fleet_grid(sc.N, grid_size)
        dev = {"bound": np.inf, "completion": np.inf, "n_c": 0,
               "rate": 0.0}
        for rate in sc.link.rates:          # rate-major: first rate wins
            for n_c in grid:                # then first grid point
                n_o_eff = float(obj.effective_overhead(
                    sc, np.float64(n_c), float(rate)))
                t = np.ceil(sc.N / np.float64(n_c)) * (
                    np.float64(n_c) + n_o_eff)
                if t > deadline:
                    continue
                b = float(corollary1_bound(
                    np.float64(n_c), N=sc.N, T=deadline, n_o=n_o_eff,
                    tau_p=sc.tau_p, consts=consts))
                if b < dev["bound"]:
                    dev = {"bound": b, "completion": float(t),
                           "n_c": int(n_c), "rate": float(rate)}
        best.append(dev)

    eligible = [i for i in range(S) if np.isfinite(best[i]["bound"])]
    if not eligible:
        return RoundRecord(participants=(), n_participants=0,
                           deadline=deadline, round_time=np.inf,
                           objective_value=np.inf, n_eligible=0,
                           feasible=False, n_c=(), rate=())
    # global sorted order (by bound, ties by index) fixes the float
    # accumulation order for EVERY subset, so subset sums of the same
    # members always round identically
    rank = {i: r for r, i in enumerate(
        sorted(eligible, key=lambda i: (best[i]["bound"], i)))}

    from itertools import combinations
    champion = None
    for K in range(1, len(eligible) + 1):
        for subset in combinations(eligible, K):
            total = 0.0
            for i in sorted(subset, key=rank.__getitem__):
                total += best[i]["bound"]
            F = total / K - sigma * (1.0 - 1.0 / K)
            cand = (F, K, tuple(sorted(subset)))
            if champion is None or cand < champion:
                champion = cand
    F, K, subset = champion
    return RoundRecord(
        participants=subset, n_participants=K, deadline=deadline,
        round_time=max(best[i]["completion"] for i in subset),
        objective_value=F, n_eligible=len(eligible), feasible=True,
        n_c=tuple(best[i]["n_c"] for i in subset),
        rate=tuple(best[i]["rate"] for i in subset))
