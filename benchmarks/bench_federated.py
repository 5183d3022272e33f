"""Federated round-planner benchmark: fan-in speedup, parity, zero traces.

Exercises :mod:`repro.federated` at fleet scale:

  1. **Speedup** — one jitted :meth:`RoundPlanner.plan_round_batch` call
     over a 1024-device population vs :func:`plan_round_reference`, the
     per-device scalar numpy planning loop (grid evaluation per device in
     Python) + host-side participation scan.  Asserts >= 20x — the
     acceptance floor for folding the participation axis into the
     batched kernel instead of looping the fleet.
  2. **Parity** — the jitted round's participant set and every
     participant's ``(rate, n_c)`` must equal the reference argmin
     exactly (the same tie-breaking contract the fleet planner's
     scalar-equivalence tests enforce, plus the participation axis).
  3. **Serving SLO** — a warmed :class:`PlanningService` plans rounds
     through ``submit_round`` with ZERO post-warmup jit traces, read
     through the unified metrics registry (``repro_federated_*``
     families render and parse on the way); a repeated round is a cache
     hit.
  4. **Artifact** — ``BENCH_federated.json`` at the repo root
     (provenance-stamped, schema v2), uploaded by CI.

Standalone:  PYTHONPATH=src python -m benchmarks.bench_federated
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.common import bench_stamp, emit, save_artifact
from repro.core.planner import fleet_grid
from repro.federated import RoundPlanner, plan_round_reference
from repro.fleet.batch import ScenarioBatch
from repro.serve import (PlanningService, ServiceConfig, default_consts,
                         synth_population)

POPULATION = 1024
GRID_SIZE = 64
POP_BUCKETS = (64, 1024)
N_MAX = 4096
REPS = 15
#: acceptance floor: the jitted round solve vs the per-device scalar
#: planning loop at population >= 512
SPEEDUP_FLOOR = 20.0

#: perf-trajectory artifact written at the repo root
BENCH_JSON = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_federated.json")


def run():
    consts = default_consts()
    population, deadline = synth_population(POPULATION, seed=11,
                                            n_max=N_MAX)
    planner = RoundPlanner(grid_size=GRID_SIZE)

    # ---- jitted round solve (warm, then timed) -----------------------------
    # the prebuilt-batch contract: Scenario -> array conversion and the
    # per-device grids are hoisted out of the timed region on BOTH sides
    # (the scalar loop reads the Scenario objects directly and its
    # per-device fleet_grid calls are noise at this scale)
    batch = ScenarioBatch.from_scenarios(population)
    grid = fleet_grid(batch.N, GRID_SIZE)
    t0 = time.perf_counter()
    planner.warm(population, consts, pad_to=POPULATION)
    warm_s = time.perf_counter() - t0
    samples = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        plan = planner.plan_round_batch(batch, consts, deadline=deadline,
                                        grid=grid)
        samples.append(time.perf_counter() - t0)
    # min over repeats: single-core boxes are noisy and the floor is
    # what the 20x assertion is calibrated against
    jit_s = min(samples)
    emit("federated_round", jit_s * 1e6,
         f"S={POPULATION} G={GRID_SIZE} K={plan.k_best} "
         f"eligible={plan.n_eligible} warm={warm_s:.2f}s")
    t0 = time.perf_counter()
    full_plan = planner.plan_round(population, consts, deadline=deadline,
                                   pad_to=POPULATION)
    emit("federated_round_convert", (time.perf_counter() - t0) * 1e6,
         "plan_round incl. Scenario->batch conversion")
    assert np.array_equal(full_plan.participants, plan.participants)

    # ---- the per-device scalar planning loop (the baseline) ----------------
    t0 = time.perf_counter()
    ref = plan_round_reference(population, consts, deadline=deadline,
                               grid_size=GRID_SIZE)
    ref_s = time.perf_counter() - t0
    speedup = ref_s / jit_s
    emit("federated_scalar_loop", ref_s * 1e6,
         f"S={POPULATION} speedup={speedup:.1f}x")
    assert speedup >= SPEEDUP_FLOOR, (
        f"jitted round solve is only {speedup:.1f}x the per-device scalar "
        f"loop at S={POPULATION} (floor {SPEEDUP_FLOOR}x)")

    # ---- argmin parity vs the reference ------------------------------------
    assert np.array_equal(plan.participants, ref.participants), (
        f"participant sets differ: {plan.participants[:8]}... vs "
        f"{ref.participants[:8]}...")
    assert plan.k_best == ref.k_best
    assert plan.n_eligible == ref.n_eligible
    assert np.array_equal(plan.n_c, ref.n_c), "per-device n_c differ"
    assert np.array_equal(plan.rate, ref.rate), "per-device rates differ"
    # values may differ in the last ulp where the backend libm disagrees
    # (argmins exact, bounds within 1e-9 relative)
    finite = np.isfinite(ref.bound_value)
    assert np.array_equal(finite, np.isfinite(plan.bound_value))
    gap = np.abs(plan.bound_value[finite] - ref.bound_value[finite])
    assert np.all(gap <= 1e-9 * np.abs(ref.bound_value[finite])), (
        f"best-feasible bounds diverge beyond 1e-9 relative: "
        f"max gap {gap.max()}")

    # ---- serving path: zero post-warmup traces + cache hit -----------------
    config = ServiceConfig(grid_size=GRID_SIZE, batch_buckets=(8,),
                           grid_modes=("dense",),
                           objective_ids=("corollary1",),
                           population_buckets=POP_BUCKETS, n_max=N_MAX)
    service = PlanningService(config)
    warm_traces = service.warmup()
    t0 = time.perf_counter()
    record = service.submit_round(population, deadline=deadline)
    serve_s = time.perf_counter() - t0
    repeat = service.submit_round(population, deadline=deadline)
    assert repeat == record, "repeated round missed the cache"

    metrics = service.metrics_snapshot()
    post_traces = int(metrics["repro_serve_post_warmup_traces_total"][()])
    assert post_traces == 0, (
        f"{post_traces} jit trace(s) after warmup on the federated round "
        "path — the population-bucket sweep missed a shape")
    assert int(metrics["repro_federated_rounds_total"][()]) == 2
    assert int(metrics["repro_federated_participants_total"][()]) == \
        2 * record.n_participants
    cache = service.cache.stats()
    assert cache["hits_by_objective"].get("federated_round", 0) == 1, (
        f"expected 1 federated cache hit, got {cache['hits_by_objective']}")
    assert record.participants == tuple(int(i) for i in plan.participants)
    emit("federated_serve", serve_s * 1e6,
         f"K={record.n_participants} post_warm_traces={post_traces} "
         f"cache_hit=1")

    payload = {
        "bench": "federated",
        **bench_stamp(),
        "population": POPULATION, "grid_size": GRID_SIZE,
        "population_buckets": list(POP_BUCKETS),
        "deadline": deadline,
        "round_us": jit_s * 1e6,
        "scalar_loop_us": ref_s * 1e6,
        "speedup_vs_scalar": speedup,
        "rounds_per_sec": 1.0 / jit_s,
        "devices_per_sec": POPULATION / jit_s,
        "k_best": int(plan.k_best),
        "n_eligible": int(plan.n_eligible),
        "round_time": float(plan.round_time),
        "objective_value": float(plan.objective_value),
        "warmup_traces": warm_traces,
        "warmup_seconds": service.warmup_seconds,
        "post_warmup_traces": post_traces,
        "serve_round_us": serve_s * 1e6,
    }
    with open(BENCH_JSON, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    save_artifact("federated", payload)
    return speedup


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
