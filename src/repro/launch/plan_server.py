"""Fleet plan server: micro-batch a scenario request stream, report plans/sec.

Serving loop for the fleet planning engine (``repro.fleet``): requests —
heterogeneous ``Scenario``s, one per edge device asking "what block size /
rate should I use?" — are collected into fixed-size micro-batches, deduped
through the quantised-key :class:`~repro.fleet.cache.PlanCache`, and the
residual misses solved in one jitted ``FleetPlanner.plan_batch`` call per
batch (padded to powers of two so only O(log batch) kernel shapes ever
compile).  The stream may mix every registered link model — cache keys
carry ``(model_id, params)`` and the kernel dispatches per scenario via
``jax.lax.switch`` — AND every registered planning objective: each request
may name the objective it wants (Corollary-1 bound, exact burst-aware
Markov-ARQ, empirical Monte-Carlo), micro-batches group by objective, and
cache keys carry the objective token so answers never cross objectives.
Each request also carries a GRID MODE — ``refine`` (two-pass
coarse->fine) or ``dense`` (single-pass reference) — so serving policies
can mix refined bound traffic with dense calibration traffic;
micro-batches group by (objective, mode), the stats count requests per
mode, and cache keys fold the mode in so the two streams never alias.

  PYTHONPATH=src python -m repro.launch.plan_server \
      --requests 4096 --batch 256 --grid 64 --dup 0.5 \
      --models erasure,fading,gilbert_elliott --objective all \
      --grid-mode all

The synthetic stream mimics a production mix: device classes are drawn
from a finite catalogue with per-request jitter, so a fraction of requests
(--dup, after quantisation) hit the cache.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.chaos import FaultPlan, InjectedFault, parse_chaos_spec
from repro.core.bounds import BoundConstants
from repro.core.links import link_spec, link_spec_for
from repro.core.scenario import Scenario
from repro.fleet import GRID_MODES, FleetPlanner, PlanCache, PlanRecord
# The shared serving catalogue now lives in repro.serve.catalogue (it
# serves both this one-shot driver and the always-on PlanningService);
# re-exported here so existing imports of the plan_server module keep
# working.
from repro.serve.batcher import group_requests
from repro.serve.export import oneshot_metrics
from repro.serve.catalogue import (ALL_MODELS, ALL_OBJECTIVES,  # noqa: F401
                                   LINK_FACTORIES, OBJECTIVE_FACTORIES,
                                   RATE_SET, default_consts,
                                   make_montecarlo_objective, parse_models,
                                   resolve_grid_modes, resolve_objectives,
                                   synth_requests)
from repro.serve.stats import percentiles

# historic private aliases, kept for callers of the old module layout
_parse_models = parse_models
_make_montecarlo_objective = make_montecarlo_objective


@dataclass(frozen=True)
class ServeStats:
    records: List[PlanRecord]
    n_requests: int
    n_batches: int
    seconds: float
    plans_per_sec: float
    cache_hit_rate: float
    #: request counts keyed by link model_id (registry ids)
    requests_per_model: Dict[int, int] = field(default_factory=dict)
    #: request counts keyed by planning objective_id (registry ids)
    requests_per_objective: Dict[str, int] = field(default_factory=dict)
    #: request counts keyed by grid mode ("dense" / "refine")
    requests_per_grid_mode: Dict[str, int] = field(default_factory=dict)
    #: wall-clock per-micro-batch solve latency percentiles (milliseconds);
    #: 0.0 on an empty stream — the batch is the serving unit, so these
    #: are what a per-request SLO inherits from batching
    batch_p50_ms: float = 0.0
    batch_p99_ms: float = 0.0
    batch_max_ms: float = 0.0
    #: requests served by the dense Corollary-1 bound fallback instead of
    #: their requested objective (injected solve failure, or a per-batch
    #: budget the estimated solve would blow) — every one is stamped
    #: ``fallback="bound"`` on its record
    n_degraded: int = 0
    #: fault-injection fire counts per point (empty without a chaos spec)
    faults_injected: Dict[str, int] = field(default_factory=dict)


def serve(requests: Sequence[Scenario], *, planner: FleetPlanner,
          consts: BoundConstants, cache: Optional[PlanCache] = None,
          batch_size: int = 256, warm: bool = True,
          objectives: Optional[Sequence[Any]] = None,
          grid_modes: Optional[Sequence[str]] = None,
          faults: Optional[FaultPlan] = None,
          budget_s: Optional[float] = None) -> ServeStats:
    """Micro-batch the request list and plan it end to end.

    Single-objective streams pad every miss-batch to ``batch_size``
    (``plan_many(pad_to=)``) so the stream exercises exactly ONE kernel
    shape and ``warm=True`` compiles it up front — reported throughput is
    steady-state, not jit compilation.  Mixed-objective streams pad each
    per-objective sub-group to the next power of two instead (O(log
    batch) shapes per objective, without re-solving ``batch_size``-wide
    pad filler per group); warm-up replays the FIRST micro-batch window's
    exact grouping plus one batch per remaining objective, so the common
    shapes are precompiled but a first-seen pow2 shape later in the
    stream still compiles inside the timed loop.

    ``objectives`` assigns each request a planning objective: ``None``
    (the planner's default for every request) or a per-request sequence
    of objective INSTANCES (reuse one instance per distinct objective —
    identity keys the jitted Monte-Carlo kernel cache; registry ids
    resolve through :func:`resolve_objectives`).  ``grid_modes``
    likewise assigns each request a grid mode (``None`` means the
    planner's default for every request; names resolve through
    :func:`resolve_grid_modes`), so one stream can mix refined bound
    traffic with dense calibration traffic.  Micro-batches group by
    (objective, grid mode), so a mixed stream dispatches every
    registered kernel and both solve strategies in one pass.

    The reported hit-rate covers THIS stream only (delta of the cache
    counters, not its lifetime totals) and is 0.0 — never NaN — on an
    empty stream; ``requests_per_model`` / ``requests_per_objective`` /
    ``requests_per_grid_mode`` count requests by link ``model_id``,
    ``objective_id`` and grid mode so mixed traffic is visible in the
    stats.

    ``faults`` (a :class:`~repro.chaos.FaultPlan`) injects the one-shot
    loop's resilience path: each micro-batch group draws
    ``solve.latency`` (artificial delay) and ``solve.error`` before its
    solve; a failed solve is retried once, and a second failure degrades
    the group to the dense Corollary-1 bound fallback — every request
    still gets an answer, stamped ``fallback="bound"`` and counted in
    ``n_degraded``.  ``budget_s`` is a per-micro-batch solve budget: when
    the running estimate (EWMA of observed solve seconds for that
    (objective, mode) group) says the full solve would blow it, the group
    goes straight to the bound fallback instead.  Both default off, and
    with both off the records are bitwise identical to a run without
    this machinery.
    """
    requests = list(requests)
    if batch_size < 1:
        raise ValueError(f"batch_size must be >= 1, got {batch_size}")
    if objectives is None:
        objs: List[Any] = [None] * len(requests)
    else:
        objs = list(objectives)
        if len(objs) != len(requests):
            raise ValueError(
                f"objectives has length {len(objs)}, want one per request "
                f"({len(requests)})")
    if grid_modes is None:
        modes: List[str] = [planner.grid_mode] * len(requests)
    else:
        modes = [planner._resolve_grid_mode(m) for m in grid_modes]
        if len(modes) != len(requests):
            raise ValueError(
                f"grid_modes has length {len(modes)}, want one per request "
                f"({len(requests)})")
    per_model: Dict[int, int] = {}
    per_objective: Dict[str, int] = {}
    per_mode: Dict[str, int] = {}
    default_id = planner._resolve_objective(None).objective_id
    for sc, obj, mode in zip(requests, objs, modes):
        mid = link_spec_for(sc.link).model_id
        per_model[mid] = per_model.get(mid, 0) + 1
        oid = default_id if obj is None else obj.objective_id
        per_objective[oid] = per_objective.get(oid, 0) + 1
        per_mode[mode] = per_mode.get(mode, 0) + 1

    def _grouped(idxs):
        """Consecutive request indices grouped by (objective identity,
        grid mode), first-seen order (one plan_many call per group) —
        the same canonical grouping the always-on batcher uses."""
        return group_requests(list(idxs),
                              key=lambda i: (id(objs[i]), modes[i]))

    # single-group streams pad every micro-batch to ONE kernel shape;
    # mixed streams pad each per-(objective, mode) sub-group to the next
    # power of two instead (still O(log batch) shapes per group, but no
    # lanes wasted re-solving the pad filler batch_size-wide per group)
    mixed = len({(id(o), m) for o, m in zip(objs, modes)}) > 1
    pad_to = None if mixed else batch_size
    # the degradation target: dense Corollary-1 bound — the cheapest
    # objective in the catalogue, solved without the cache so a degraded
    # answer can never shadow a full one under the requested objective
    fallback_obj = None
    if faults is not None or budget_s is not None:
        fallback_obj = resolve_objectives(("corollary1",))["corollary1"]

    def _degrade(idxs):
        recs = planner.plan_many([requests[i] for i in idxs], consts,
                                 cache=None, pad_to=pad_to,
                                 objective=fallback_obj, grid_mode="dense")
        return [dataclasses.replace(r, fallback="bound") for r in recs]

    if warm and requests:
        warmed = set()
        # the first window's exact grouping: compiles the shapes the
        # timed loop starts with
        for idxs in _grouped(range(min(batch_size, len(requests)))):
            planner.plan_many([requests[i] for i in idxs], consts,
                              cache=None, pad_to=pad_to,
                              objective=objs[idxs[0]],
                              grid_mode=modes[idxs[0]])
            warmed.add((id(objs[idxs[0]]), modes[idxs[0]]))
        # groups absent from the first window still warm once
        for idxs in _grouped(range(len(requests))):
            if (id(objs[idxs[0]]), modes[idxs[0]]) not in warmed:
                planner.plan_many([requests[i] for i in idxs[:batch_size]],
                                  consts, cache=None, pad_to=pad_to,
                                  objective=objs[idxs[0]],
                                  grid_mode=modes[idxs[0]])
        if fallback_obj is not None:
            # a degraded group may fire any time once chaos/budgets are
            # on, so its kernel shape warms with everything else
            planner.plan_many(requests[:batch_size], consts, cache=None,
                              pad_to=pad_to, objective=fallback_obj,
                              grid_mode="dense")
    hits0, misses0 = (cache.hits, cache.misses) if cache is not None \
        else (0, 0)
    records: List[Optional[PlanRecord]] = [None] * len(requests)
    n_batches = 0
    n_degraded = 0
    # per-(objective, mode) EWMA of observed full-solve seconds: the
    # budget triage's estimate of what the NEXT group solve will cost
    solve_est: Dict[Tuple[int, str], float] = {}
    batch_seconds: List[float] = []
    t0 = time.perf_counter()
    for lo in range(0, len(requests), batch_size):
        for idxs in _grouped(range(lo, min(lo + batch_size,
                                           len(requests)))):
            gkey = (id(objs[idxs[0]]), modes[idxs[0]])
            tb = time.perf_counter()
            degraded = False
            if budget_s is not None \
                    and solve_est.get(gkey, 0.0) > budget_s:
                recs, degraded = _degrade(idxs), True
            else:
                try:
                    if faults is not None:
                        stall = faults.draw("solve.latency")
                        if stall is not None:
                            time.sleep(stall.duration_s)
                        if faults.draw("solve.error") is not None:
                            raise InjectedFault("solve.error")
                    recs = planner.plan_many(
                        [requests[i] for i in idxs], consts, cache=cache,
                        pad_to=pad_to, objective=objs[idxs[0]],
                        grid_mode=modes[idxs[0]])
                except Exception:
                    if fallback_obj is None:
                        raise  # no chaos, no budget: a failure is a fault
                    # one retry (the fault draw advances, so a transient
                    # fault clears), then degrade to the bound fallback
                    try:
                        if faults is not None \
                                and faults.draw("solve.error") is not None:
                            raise InjectedFault("solve.error")
                        recs = planner.plan_many(
                            [requests[i] for i in idxs], consts,
                            cache=cache, pad_to=pad_to,
                            objective=objs[idxs[0]],
                            grid_mode=modes[idxs[0]])
                    except Exception:
                        recs, degraded = _degrade(idxs), True
            dt_b = time.perf_counter() - tb
            batch_seconds.append(dt_b)
            if degraded:
                n_degraded += len(idxs)
            elif budget_s is not None:
                prev = solve_est.get(gkey)
                solve_est[gkey] = dt_b if prev is None \
                    else 0.5 * prev + 0.5 * dt_b
            for i, rec in zip(idxs, recs):
                records[i] = rec
            n_batches += 1
    dt = time.perf_counter() - t0
    if cache is not None:
        d_hits = cache.hits - hits0
        d_total = d_hits + (cache.misses - misses0)
        hit_rate = d_hits / d_total if d_total else 0.0
    else:
        hit_rate = 0.0
    b50, b99 = percentiles(batch_seconds)
    return ServeStats(
        records=records, n_requests=len(requests), n_batches=n_batches,
        seconds=dt, plans_per_sec=len(requests) / dt if dt > 0 else 0.0,
        cache_hit_rate=hit_rate, requests_per_model=per_model,
        requests_per_objective=per_objective,
        requests_per_grid_mode=per_mode,
        batch_p50_ms=b50 * 1e3, batch_p99_ms=b99 * 1e3,
        batch_max_ms=(max(batch_seconds) * 1e3 if batch_seconds else 0.0),
        n_degraded=n_degraded,
        faults_injected=dict(faults.fires) if faults is not None else {})


def _parse_models(spec: str) -> Sequence[str]:
    if spec == "all":
        return ALL_MODELS
    return tuple(m.strip() for m in spec.split(",") if m.strip())


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--grid", type=int, default=64)
    ap.add_argument("--cache-size", type=int, default=8192)
    ap.add_argument("--sig-digits", type=int, default=3)
    ap.add_argument("--dup", type=float, default=0.5,
                    help="fraction of requests hitting a known device class")
    ap.add_argument("--models", default="erasure",
                    help="comma-separated link model mix, or 'all' "
                         f"({', '.join(ALL_MODELS)})")
    ap.add_argument("--objective", default="corollary1",
                    help="comma-separated planning-objective mix, or 'all' "
                         f"({', '.join(ALL_OBJECTIVES)})")
    ap.add_argument("--grid-mode", default="dense",
                    help="comma-separated grid-mode mix, or 'all' "
                         f"({', '.join(GRID_MODES)}); 'refine' is the "
                         "two-pass coarse->fine solve, 'dense' the "
                         "single-pass reference")
    ap.add_argument("--n-max", type=int, default=32768,
                    help="cap on drawn dataset sizes (keep small when the "
                         "mix includes the simulated montecarlo objective)")
    ap.add_argument("--no-cache", action="store_true")
    ap.add_argument("--metrics-textfile", default=None,
                    help="write the run's Prometheus text exposition here "
                         "(repro_plan_server_* families + cache + traces)")
    ap.add_argument("--budget-ms", type=float, default=0.0,
                    help="per-micro-batch solve budget in ms (0 = off); "
                         "groups whose estimated solve would blow it are "
                         "degraded to the dense Corollary-1 bound fallback")
    ap.add_argument("--chaos-spec", default=None,
                    help="deterministic fault-injection spec, e.g. "
                         "'seed=7,solve_error=0.2,solve_latency=0.1:5ms,"
                         "cache_corrupt=0.05'")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        catalogue = resolve_objectives(args.objective)
        mode_mix = resolve_grid_modes(args.grid_mode)
        faults = parse_chaos_spec(args.chaos_spec) \
            if args.chaos_spec else None
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    requests = synth_requests(args.requests, seed=args.seed,
                              dup_frac=args.dup,
                              models=_parse_models(args.models),
                              n_max=args.n_max)
    instances = list(catalogue.values())
    rng = np.random.default_rng(args.seed + 1)
    objectives = [instances[int(rng.integers(len(instances)))]
                  for _ in requests]
    grid_modes = [mode_mix[int(rng.integers(len(mode_mix)))]
                  for _ in requests]
    planner = FleetPlanner(grid_size=args.grid)
    corruptor = None
    if faults is not None and faults.enabled("cache.corrupt"):
        corruptor = lambda: faults.draw("cache.corrupt") is not None  # noqa: E731
    cache = None if args.no_cache else PlanCache(
        maxsize=args.cache_size, sig_digits=args.sig_digits,
        checksums=faults is not None, corruptor=corruptor)
    stats = serve(requests, planner=planner, consts=default_consts(),
                  cache=cache, batch_size=args.batch, objectives=objectives,
                  grid_modes=grid_modes, faults=faults,
                  budget_s=args.budget_ms / 1e3 if args.budget_ms > 0
                  else None)
    print(f"served {stats.n_requests} plan requests in {stats.n_batches} "
          f"micro-batches of <= {args.batch}")
    print(f"throughput: {stats.plans_per_sec:,.0f} plans/sec "
          f"({stats.seconds * 1e3:.1f} ms total, grid={args.grid})")
    print(f"micro-batch latency: p50={stats.batch_p50_ms:.2f} ms "
          f"p99={stats.batch_p99_ms:.2f} ms max={stats.batch_max_ms:.2f} ms")
    by_model = ", ".join(
        f"{link_spec(mid).name}[{mid}]={n}"
        for mid, n in sorted(stats.requests_per_model.items()))
    print(f"request mix: {by_model}")
    by_objective = ", ".join(
        f"{oid}={n}"
        for oid, n in sorted(stats.requests_per_objective.items()))
    print(f"objective mix: {by_objective}")
    by_mode = ", ".join(
        f"{mode}={n}"
        for mode, n in sorted(stats.requests_per_grid_mode.items()))
    print(f"grid-mode mix: {by_mode}")
    if cache is not None:
        print(f"cache: {cache.hits} hits / {cache.misses} misses "
              f"(hit rate {stats.cache_hit_rate:.1%}, {len(cache)} entries)")
        if cache.corruptions:
            print(f"cache corruptions detected (re-solved): "
                  f"{cache.corruptions}")
    if faults is not None or args.budget_ms > 0:
        fired = ", ".join(f"{p}={n}" for p, n in
                          sorted(stats.faults_injected.items())) or "none"
        n_ok = sum(r is not None for r in stats.records)
        print(f"resilience: degraded={stats.n_degraded} "
              f"(bound fallback), completed={n_ok}/{stats.n_requests}, "
              f"faults fired: {fired}")
    if stats.records:
        sample = stats.records[0]
        print(f"sample plan: n_c={sample.n_c} rate={sample.rate} "
              f"objective={sample.objective} "
              f"bound={sample.bound_value:.4g}")
    if args.metrics_textfile:
        oneshot_metrics(stats, cache).write_textfile(args.metrics_textfile)
        print(f"metrics: wrote Prometheus textfile {args.metrics_textfile}")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
