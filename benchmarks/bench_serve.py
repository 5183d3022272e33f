"""Always-on planning-service benchmark: warmup, zero-trace SLO, latency.

Exercises :class:`repro.serve.PlanningService` the way production would:

  1. **Warmup** — AOT-compile every configured (objective, grid mode,
     batch bucket) executable; the warmup trace count and wall time are
     reported.
  2. **Mixed stream** — a heterogeneous request stream drawing from
     EVERY registered link model, cycled through every served objective
     and both grid modes (plus a slice routed by the admission policy),
     pushed through the continuous micro-batcher from a producer thread.
  3. **Assertions** — the serving SLOs this PR introduces:

       * ZERO post-warmup jit traces, read from the UNIFIED metrics
         registry (the same series a Prometheus scrape sees — so the
         gate also validates the export path end to end);
       * per-request phase spans SUM EXACTLY (<= 1 µs) to the reported
         enqueue-to-plan latency, and the solve fraction
         clears a sanity floor (the spans are attributing real compute,
         not noise);
       * enqueue-to-plan p99 under a generous bound (the flush deadline
         plus a worst-case solve; this is a smoke floor, not a perf
         target — CI boxes are noisy);
       * service throughput >= 0.5x the one-shot ``plan_server`` driver
         on the SAME stream — with span recording on, so this floor is
         also the <= 5% span-overhead budget's enforcement point;
       * plans BITWISE-identical to direct ``FleetPlanner.plan_many``
         calls (the service adds routing, never arithmetic).

  4. **Artifact** — ``BENCH_serve.json`` at the repo root (schema: one
     row per (objective, grid_mode, bucket) plus the headline latency /
     throughput numbers), the perf-trajectory artifact CI uploads.

Standalone:  PYTHONPATH=src python -m benchmarks.bench_serve
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.common import bench_stamp, emit, save_artifact
from repro.fleet import FleetPlanner, PlanCache
from repro.launch.plan_server import serve as oneshot_serve
from repro.serve import (ALL_MODELS, PlanningService, ServiceConfig,
                         synth_requests)

N_REQUESTS = 2048
GRID_SIZE = 64
BUCKETS = (64, 256)
FLUSH_INTERVAL = 0.01
OBJECTIVE_IDS = ("corollary1", "markov_arq")
N_MAX = 8192
#: generous p99 bound (seconds): the flush deadline + a worst-case padded
#: solve + scheduler noise on a shared CI box.  A healthy run sits far
#: below this; tripping it means batching stalled, not that a solve was
#: slow.
P99_CEILING_S = 2.0
#: continuous batching must stay in the same class as offline batching;
#: spans/histograms/metrics are ON during the measured stream, so this
#: floor also bounds the observability overhead (a >5% span-recording
#: tax would show up here long before it hit 50%)
THROUGHPUT_FLOOR = 0.5
#: the spans must attribute REAL device compute: over a whole stream the
#: solve share of enqueue-to-plan latency cannot round to zero
SOLVE_FRACTION_FLOOR = 1e-3
#: phase intervals are cut from one monotonic clock: sums are exact up
#: to float addition error
PHASE_SUM_TOL_S = 1e-6

#: perf-trajectory artifact written at the repo root
BENCH_JSON = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_serve.json")


def _mixed_stream(service, requests, seed):
    """Submit every request: half cycled explicitly through every served
    (objective, grid mode) pair, half routed by the admission policy —
    returns (records, stream wall-clock seconds, first-submit to
    last-plan)."""
    rng = np.random.default_rng(seed)
    instances = list(service.objectives.items())
    modes = service.config.grid_modes
    futures = []
    t0 = time.perf_counter()
    for i, scenario in enumerate(requests):
        if rng.random() < 0.5:
            fut = service.submit(scenario)          # admission policy
        else:
            _, obj = instances[i % len(instances)]
            mode = modes[i % len(modes)]
            fut = service.submit(scenario, objective=obj, grid_mode=mode)
        futures.append(fut)
    records = [f.result(timeout=300) for f in futures]
    return records, time.perf_counter() - t0


def run():
    config = ServiceConfig(grid_size=GRID_SIZE, batch_buckets=BUCKETS,
                           flush_interval=FLUSH_INTERVAL,
                           objective_ids=OBJECTIVE_IDS, n_max=N_MAX)
    service = PlanningService(config)
    warm_traces = service.warmup()
    emit("serve_warmup", service.warmup_seconds * 1e6,
         f"traces={warm_traces} objectives={len(service.objectives)} "
         f"modes={len(config.grid_modes)} buckets={len(BUCKETS)}")

    # dup_frac=0: every request is a distinct device class.  A duplicate
    # stream would serve jittered repeats from the quantised cache, whose
    # records were solved for a NEIGHBOURING scenario — correct serving
    # semantics, but not bitwise-comparable against a fresh direct solve.
    requests = synth_requests(N_REQUESTS, seed=31, dup_frac=0.0,
                              n_classes=N_REQUESTS, models=ALL_MODELS,
                              n_max=N_MAX)
    with service:
        records, stream_s = _mixed_stream(service, requests, seed=32)
    stats = service.stats()
    service_pps = N_REQUESTS / stream_s

    # ---- zero post-warmup traces (the tentpole SLO) ------------------------
    # read through the unified metrics registry, not the raw counter: the
    # value a Prometheus scrape would see is the value the gate checks,
    # and taking the snapshot parses the full exposition (an export
    # regression fails here, not on a dashboard later)
    metrics = service.metrics_snapshot()
    post_traces = int(metrics["repro_serve_post_warmup_traces_total"][()])
    assert post_traces == stats.counters.get("post_warmup_traces", 0), (
        "metrics registry and raw counter disagree on post-warmup traces")
    assert post_traces == 0, (
        f"{post_traces} jit trace(s) after warmup — the bucketed AOT sweep "
        f"missed a shape the stream reached: {stats.buckets}")
    assert stats.n_planned == N_REQUESTS, (
        f"planned {stats.n_planned} of {N_REQUESTS} requests")

    # ---- span decomposition ------------------------------------------------
    spans = service.spans.snapshot()
    assert spans, "no request spans recorded"
    worst = max(abs(s.phase_sum - s.latency_s) for s in spans)
    assert worst <= PHASE_SUM_TOL_S, (
        f"phase spans do not sum to enqueue-to-plan latency "
        f"(max gap {worst * 1e6:.2f} µs > {PHASE_SUM_TOL_S * 1e6:.0f} µs) "
        "— a phase interval is missing or double-counted")
    phases = stats.phases
    assert phases["batch_wait"] > 0.0, (
        "zero cumulative batch-wait over a whole stream: spans are not "
        "measuring queueing")
    assert stats.solve_fraction >= SOLVE_FRACTION_FLOOR, (
        f"solve fraction {stats.solve_fraction:.5f} is below "
        f"{SOLVE_FRACTION_FLOOR} — solve attribution lost the actual "
        "compute")

    # ---- latency SLO -------------------------------------------------------
    p99_s = stats.latency_p99_ms / 1e3
    assert p99_s < P99_CEILING_S, (
        f"enqueue-to-plan p99 {p99_s:.3f}s exceeds the generous "
        f"{P99_CEILING_S:.1f}s ceiling — continuous batching is stalling")

    # ---- bitwise parity vs direct plan_many --------------------------------
    # same planner configuration, fresh instance: the service must add
    # routing/batching/caching around the solver, never arithmetic
    direct_planner = FleetPlanner(grid_size=GRID_SIZE, shard=config.shard,
                                  pow2_refine_widths=True)
    rng = np.random.default_rng(33)
    sample = rng.choice(N_REQUESTS, size=64, replace=False)
    mismatches = []
    for i in sample:
        rec = records[i]
        obj = service.objectives[rec.objective]
        # re-solve alone (bucket pad 1): padding must not change answers
        direct = direct_planner.plan_many([requests[i]], service.consts,
                                          objective=obj)[0]
        if direct != rec:
            mismatches.append((int(i), rec, direct))
    # grid-mode of the service pick is unknown here for policy-routed
    # requests; dense vs refine argmin-match is already asserted by the
    # fleet bench, and plan_many defaults to the planner's dense mode —
    # re-check any mismatch under refine before declaring failure
    real_mismatches = []
    for i, rec, direct in mismatches:
        obj = service.objectives[rec.objective]
        refined = direct_planner.plan_many([requests[i]], service.consts,
                                           objective=obj,
                                           grid_mode="refine")[0]
        if refined != rec:
            real_mismatches.append((i, rec, direct, refined))
    assert not real_mismatches, (
        f"{len(real_mismatches)} service plan(s) differ from direct "
        f"plan_many under BOTH grid modes; first: {real_mismatches[0]}")

    # ---- throughput floor vs the one-shot driver ---------------------------
    oneshot_planner = FleetPlanner(grid_size=GRID_SIZE)
    instances = list(service.objectives.values())
    modes = list(config.grid_modes)
    objectives = [instances[i % len(instances)] for i in range(N_REQUESTS)]
    grid_modes = [modes[i % len(modes)] for i in range(N_REQUESTS)]
    oneshot = oneshot_serve(requests, planner=oneshot_planner,
                            consts=service.consts,
                            cache=PlanCache(maxsize=config.cache_size),
                            batch_size=config.max_batch,
                            objectives=objectives, grid_modes=grid_modes)
    ratio = service_pps / oneshot.plans_per_sec \
        if oneshot.plans_per_sec else float("inf")
    assert ratio >= THROUGHPUT_FLOOR, (
        f"service throughput {service_pps:,.0f} plans/s is "
        f"{ratio:.2f}x the one-shot driver's {oneshot.plans_per_sec:,.0f} "
        f"(floor {THROUGHPUT_FLOOR}x) — continuous batching is losing too "
        "much to queueing")

    emit("serve_stream", stream_s * 1e6,
         f"S={N_REQUESTS} {service_pps:,.0f}plans/s "
         f"p50={stats.latency_p50_ms:.1f}ms p99={stats.latency_p99_ms:.1f}ms "
         f"post_warm_traces={post_traces} vs_oneshot={ratio:.2f}x")
    means = service.spans.phase_means_ms()
    emit("serve_phases", means["latency"] * 1e3,
         f"batch_wait={means['batch_wait']:.2f}ms pad={means['pad']:.2f}ms "
         f"cache={means['cache_lookup']:.2f}ms "
         f"solve={means['solve']:.2f}ms resolve={means['resolve']:.2f}ms "
         f"solve_frac={stats.solve_fraction:.3f}")

    rows = [{"objective": oid, "grid_mode": mode, "bucket": bucket,
             "requests": slot["requests"], "batches": slot["batches"],
             "compiles": slot["compiles"]}
            for (oid, mode, bucket), slot in sorted(stats.buckets.items())]
    payload = {
        "bench": "serve",
        **bench_stamp(),
        "n_requests": N_REQUESTS, "grid_size": GRID_SIZE,
        "buckets": list(BUCKETS), "flush_interval_s": FLUSH_INTERVAL,
        "warmup_traces": warm_traces,
        "warmup_seconds": service.warmup_seconds,
        "post_warmup_traces": post_traces,
        "plans_per_sec": service_pps,
        "stream_seconds": stream_s,
        "latency_p50_ms": stats.latency_p50_ms,
        "latency_p99_ms": stats.latency_p99_ms,
        "latency_max_ms": stats.latency_max_ms,
        "phase_means_ms": means,
        "solve_fraction": stats.solve_fraction,
        "solve_device_seconds": phases.get("solve_device", 0.0),
        "oneshot_plans_per_sec": oneshot.plans_per_sec,
        "throughput_vs_oneshot": ratio,
        "cache": stats.cache,
        "rows": rows,
    }
    with open(BENCH_JSON, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    save_artifact("serve", payload)
    return stats, ratio


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
