"""Always-on planning service driver: warm up, serve a mixed stream, report.

Entry point for :class:`repro.serve.PlanningService` — the long-lived
front end over the fleet planning engine.  It AOT-warms every configured
(objective, grid mode, batch bucket) executable, then feeds a synthetic
heterogeneous request stream (every registered link model, mixed
objectives and grid modes, drift-prone Gilbert-Elliott sessions) through
the continuous micro-batcher and prints the service stats: enqueue-to-
plan p50/p99, plans/sec, per-bucket compile/request counters, cache
hit/miss/invalidation counters and the post-warmup trace count (the
zero-trace SLO).

  PYTHONPATH=src python -m repro.launch.serve \
      --requests 2048 --buckets 64,256 --flush-ms 10 --grid 64 \
      --models all --objective corollary1,markov_arq --policy link_aware \
      --metrics-textfile metrics.prom --journal events.jsonl

Observability hooks: ``--metrics-textfile`` dumps the unified Prometheus
exposition (optionally every ``--metrics-interval`` seconds from a
background thread, node-exporter textfile style, plus a final dump);
``--journal`` appends every audit event (warmup, drift, session
lifecycle) to a JSONL file; ``--profile-dir`` wraps the serving stream
in a ``jax.profiler`` trace.  The final report includes the per-phase
latency breakdown (batch-wait / pad / cache-lookup / solve / resolve)
and the solve fraction with the time spent waiting on the device.

Unknown model/objective/grid-mode/policy names exit with code 2 (usage
error), like the other launch drivers.  The LLM decode driver that
previously lived at this path is now ``repro.launch.serve_decode``.
"""
from __future__ import annotations

import argparse
import sys
import threading
from typing import Optional, Sequence

import numpy as np

from repro.chaos import parse_chaos_spec
from repro.fleet import GRID_MODES
from repro.obs import profile_capture
from repro.serve import (ALL_MODELS, ALL_OBJECTIVES, PlanningService,
                         RequestShed, ServiceConfig, mc_update_floor,
                         parse_models, policy_spec, resolve_grid_modes,
                         resolve_objectives, synth_requests)


def _parse_buckets(spec: str):
    try:
        buckets = tuple(int(s) for s in spec.split(",") if s.strip())
    except ValueError as e:
        raise ValueError(f"bad bucket list {spec!r}: {e}") from None
    if not buckets:
        raise ValueError(f"bad bucket list {spec!r}: no buckets")
    return buckets


def run_service(args) -> int:
    """Build/warm the service, push the stream through, print stats."""
    try:
        models = parse_models(args.models)
        objective_ids = tuple(resolve_objectives(args.objective))
        grid_modes = tuple(resolve_grid_modes(args.grid_mode))
        policy_spec(args.policy)  # fail fast on a typo'd policy id
        if args.chaos_spec:
            parse_chaos_spec(args.chaos_spec)  # usage-error on a typo
        config = ServiceConfig(
            grid_size=args.grid, batch_buckets=_parse_buckets(args.buckets),
            flush_interval=args.flush_ms / 1e3, objective_ids=objective_ids,
            grid_modes=grid_modes, policy_id=args.policy,
            cache_size=args.cache_size, sig_digits=args.sig_digits,
            n_max=args.n_max, warm_models=models,
            mc_impl=args.mc_impl, mc_crn=args.mc_crn,
            mc_seed_stream=args.mc_seed_stream,
            mc_coarse_seeds=args.mc_coarse_seeds,
            mc_refine_rates=args.mc_refine_rates,
            mc_coarse_strides=(tuple(
                int(s) for s in args.mc_coarse_strides.split(","))
                if args.mc_coarse_strides else None),
            mc_fine_radius=args.mc_fine_radius,
            mc_coarse_updates=args.mc_coarse_updates,
            journal_path=args.journal,
            journal_max_bytes=args.journal_max_bytes,
            journal_keep=args.journal_keep,
            journal_fsync=args.journal_fsync,
            max_pending=args.max_pending,
            default_budget_s=(args.budget_ms / 1e3
                              if args.budget_ms > 0 else None),
            retry_attempts=args.retry_attempts,
            breaker_threshold=args.breaker_threshold,
            breaker_cooldown_s=args.breaker_cooldown_ms / 1e3,
            chaos_spec=args.chaos_spec or None)
        requests = synth_requests(args.requests, seed=args.seed,
                                  dup_frac=args.dup, models=models,
                                  n_max=args.n_max)
    except (KeyError, ValueError) as e:
        # KeyError str() wraps its message in quotes; unwrap for the CLI
        print(f"error: {e.args[0] if isinstance(e, KeyError) else e}",
              file=sys.stderr)
        return 2

    service = PlanningService(config)
    n_traces = service.warmup()
    print(f"warmup: {n_traces} kernel traces in "
          f"{service.warmup_seconds:.2f}s over "
          f"{len(service.objectives)} objective(s) x "
          f"{len(config.grid_modes)} grid mode(s) x "
          f"{len(config.batch_buckets)} bucket(s)")

    # round-robin some requests through explicit (objective, mode)
    # assignments so the stream exercises every configured pair even if
    # the admission policy wouldn't route there; the rest go through the
    # policy (objective=None) like un-annotated production traffic
    rng = np.random.default_rng(args.seed + 1)
    instances = list(service.objectives.values())

    # optional background metrics dumper: a node-exporter-style textfile
    # refreshed every --metrics-interval seconds while the stream runs
    dumper_stop = threading.Event()
    dumper = None
    if args.metrics_textfile and args.metrics_interval > 0:
        def _dump_loop():
            while not dumper_stop.wait(args.metrics_interval):
                service.metrics.write_textfile(args.metrics_textfile)
        dumper = threading.Thread(target=_dump_loop, daemon=True,
                                  name="metrics-dumper")
        dumper.start()

    try:
        with profile_capture(args.profile_dir), service:
            futures = []
            n_shed = 0
            for i, scenario in enumerate(requests):
                try:
                    if rng.random() < args.policy_frac:
                        futures.append(service.submit(scenario))
                    else:
                        obj = instances[i % len(instances)]
                        mode = config.grid_modes[i % len(config.grid_modes)]
                        futures.append(service.submit(
                            scenario, objective=obj, grid_mode=mode))
                except RequestShed:
                    n_shed += 1  # explicit overload rejection, not a bug
            records = []
            n_failed = 0
            for f in futures:
                try:
                    records.append(f.result(timeout=args.timeout))
                except Exception as e:  # noqa: BLE001 — counted, reported
                    n_failed += 1
                    print(f"request failed: {type(e).__name__}: {e}",
                          file=sys.stderr)
    finally:
        dumper_stop.set()
        if dumper is not None:
            dumper.join(timeout=5.0)
        service.journal.close()
    stats = service.stats()

    print(f"served {stats.n_planned} plans in {stats.n_batches} "
          f"micro-batches (flush <= {config.max_batch} or "
          f"{args.flush_ms:.0f} ms)")
    print(f"throughput: {stats.plans_per_sec:,.0f} plans/sec; "
          f"enqueue-to-plan latency p50={stats.latency_p50_ms:.2f} ms "
          f"p99={stats.latency_p99_ms:.2f} ms "
          f"max={stats.latency_max_ms:.2f} ms")
    post = stats.counters.get("post_warmup_traces", 0)
    print(f"post-warmup jit traces: {post} "
          f"({'SLO met' if post == 0 else 'SLO VIOLATED'})")
    res = stats.resilience
    if n_shed or n_failed or res.get("fallbacks") \
            or res.get("faults_injected") or res.get("retries"):
        import collections
        levels = collections.Counter(r.fallback for r in records)
        print(f"resilience: {n_failed} failed, {n_shed} shed, "
              f"levels {dict(levels)}; retries={res.get('retries', 0)} "
              f"backoff={res.get('backoff_seconds', 0.0):.3f}s "
              f"faults={res.get('faults_injected', {})}")
        for key, b in sorted(res.get("breakers", {}).items()):
            print(f"  breaker {key[0]}/{key[1]}: {b['state']} "
                  f"(trips={b['trips']} probes={b['probes']} "
                  f"recoveries={b['recoveries']})")
    print(f"health: {service.health().state}")
    means = service.spans.phase_means_ms()
    breakdown = " ".join(f"{name}={means[name]:.2f}"
                         for name in ("batch_wait", "pad", "cache_lookup",
                                      "solve", "resolve"))
    print(f"phase breakdown (mean ms/request): {breakdown} "
          f"| latency={means['latency']:.2f}")
    print(f"solve fraction: {stats.solve_fraction:.1%} of enqueue-to-plan "
          f"latency (waiting on the device "
          f"{stats.phases.get('solve_device', 0.0):.3f}s of "
          f"{stats.phases.get('solve', 0.0):.3f}s solve)")
    for (oid, mode, bucket), slot in sorted(stats.buckets.items()):
        print(f"  bucket {oid}/{mode}/{bucket}: "
              f"{slot['requests']} requests, {slot['batches']} batches, "
              f"{slot['compiles']} compiles")
    cache = stats.cache
    print(f"cache: {cache.get('hits', 0)} hits / "
          f"{cache.get('misses', 0)} misses "
          f"(hit rate {cache.get('hit_rate', 0.0):.1%}, "
          f"{cache.get('size', 0)} entries, "
          f"{cache.get('invalidations', 0)} invalidations)")
    if records:
        sample = records[0]
        print(f"sample plan: n_c={sample.n_c} rate={sample.rate} "
              f"objective={sample.objective} "
              f"bound={sample.bound_value:.4g}")
    if args.metrics_textfile:
        service.metrics.write_textfile(args.metrics_textfile)
        print(f"metrics: wrote Prometheus textfile "
              f"{args.metrics_textfile}")
    if args.journal:
        rotated = (f" ({service.journal.rotations} rotations)"
                   if service.journal.rotations else "")
        print(f"journal: {service.journal.emitted} events appended to "
              f"{args.journal}{rotated}")
    # without injected faults or a budget nothing may degrade: a rung
    # other than "full" then means a real solve failed
    n_unplanned = 0 if (args.chaos_spec or args.budget_ms > 0) else sum(
        r.fallback != "full" for r in records)
    if n_unplanned:
        print(f"error: {n_unplanned} requests answered by the degradation "
              "ladder with no faults injected and no budget set — see "
              "the journal's solve_failed events", file=sys.stderr)
    return 0 if (post == 0 and n_failed == 0 and n_unplanned == 0) else 1


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=2048)
    ap.add_argument("--buckets", default="64,256",
                    help="comma-separated pow2 micro-batch pad shapes; the "
                         "largest is the flush size")
    ap.add_argument("--flush-ms", type=float, default=10.0,
                    help="deadline: flush when the oldest pending request "
                         "has waited this long")
    ap.add_argument("--grid", type=int, default=64)
    ap.add_argument("--cache-size", type=int, default=8192)
    ap.add_argument("--sig-digits", type=int, default=3)
    ap.add_argument("--dup", type=float, default=0.5,
                    help="fraction of requests hitting a known device class")
    ap.add_argument("--models", default="all",
                    help="comma-separated link model mix, or 'all' "
                         f"({', '.join(ALL_MODELS)})")
    ap.add_argument("--objective", default="corollary1,markov_arq",
                    help="comma-separated served objectives, or 'all' "
                         f"({', '.join(ALL_OBJECTIVES)}); montecarlo "
                         "warmup cost scales with --n-max")
    ap.add_argument("--grid-mode", default="all",
                    help="comma-separated served grid modes, or 'all' "
                         f"({', '.join(GRID_MODES)})")
    ap.add_argument("--policy", default="link_aware",
                    help="admission policy id for un-annotated requests")
    ap.add_argument("--policy-frac", type=float, default=0.5,
                    help="fraction of the stream routed by the admission "
                         "policy (the rest cycles through every configured "
                         "(objective, mode) pair explicitly)")
    ap.add_argument("--n-max", type=int, default=32768,
                    help="cap on drawn dataset sizes (keep small when the "
                         "mix includes the simulated montecarlo objective)")
    ap.add_argument("--mc-impl", default="auto",
                    choices=["auto", "scan", "pallas"],
                    help="Monte-Carlo simulation engine: the fused Pallas "
                         "kernel, the lax.scan reference, or auto "
                         "(pallas on TPU, scan elsewhere)")
    ap.add_argument("--mc-crn", action="store_true",
                    help="common random numbers for the Monte-Carlo "
                         "objective: share the per-slot uniform draw "
                         "across all simulation lanes (a lower-variance "
                         "estimator of the same objective; plans are not "
                         "bitwise-pinned to the reference stream)")
    ap.add_argument("--mc-seed-stream", default="fold_in",
                    choices=["fold_in", "legacy"],
                    help="per-run RNG key derivation (legacy reproduces "
                         "the historical colliding seed+97r streams)")
    ap.add_argument("--mc-coarse-seeds", type=int, default=None,
                    help="Monte-Carlo seed count for refine-mode coarse "
                         "passes (0 = bound-guided coarse pass)")
    ap.add_argument("--mc-refine-rates", type=int, default=None,
                    help="keep only the top-K rates per scenario in the "
                         "refine-mode fine pass")
    ap.add_argument("--mc-coarse-strides", default=None,
                    help="comma-separated descending multi-level stride "
                         "schedule for refine mode, e.g. '32,6'")
    ap.add_argument("--mc-fine-radius", type=int, default=None,
                    help="widen the refine-mode dense fine window to "
                         "+/- this many grid steps (decoupled from the "
                         "last coarse stride)")
    ap.add_argument("--mc-coarse-updates", type=int, default=None,
                    help="cap the simulated update horizon of refine-mode "
                         "coarse passes (the fine pass always trains the "
                         "full horizon); keep >= 2048")
    ap.add_argument("--timeout", type=float, default=120.0,
                    help="per-request future timeout, seconds")
    ap.add_argument("--metrics-textfile", default=None,
                    help="write the Prometheus text exposition here (final "
                         "dump always; periodic with --metrics-interval)")
    ap.add_argument("--metrics-interval", type=float, default=0.0,
                    help="refresh --metrics-textfile every N seconds from "
                         "a background thread while serving (0 = final "
                         "dump only)")
    ap.add_argument("--journal", default=None,
                    help="append audit events (warmup, drift, session "
                         "lifecycle) to this JSONL file")
    ap.add_argument("--journal-max-bytes", type=int, default=0,
                    help="rotate the journal file at this size, keeping "
                         "--journal-keep rotated files (0 = never)")
    ap.add_argument("--journal-keep", type=int, default=3,
                    help="rotated journal files to keep")
    ap.add_argument("--journal-fsync", action="store_true",
                    help="fsync every journal event (durable crash "
                         "journal; serialises on disk latency)")
    ap.add_argument("--budget-ms", type=float, default=0.0,
                    help="per-request enqueue-to-plan latency budget; "
                         "requests the service can't solve in time "
                         "degrade along the fallback ladder (0 = none)")
    ap.add_argument("--chaos-spec", default=None,
                    help="deterministic fault injection, e.g. 'seed=7,"
                         "solve_error=0.2,solve_latency=0.1:25ms,"
                         "cache_corrupt=0.05,queue_stall=0.02:10ms'")
    ap.add_argument("--max-pending", type=int, default=0,
                    help="bound the ingestion queue; a full queue sheds "
                         "new submits explicitly (0 = unbounded)")
    ap.add_argument("--retry-attempts", type=int, default=3,
                    help="solve attempts per chunk before degrading")
    ap.add_argument("--breaker-threshold", type=int, default=5,
                    help="consecutive failures tripping a per-"
                         "(objective, grid mode) circuit breaker")
    ap.add_argument("--breaker-cooldown-ms", type=float, default=250.0,
                    help="open -> half-open probe cooldown")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler trace of the serving "
                         "stream into this directory")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if "montecarlo" in args.objective and args.n_max > 4096:
        # the MC scan floor is ~6 n_max slots; keep warmup tractable
        print(f"note: clamping --n-max {args.n_max} -> 2048 for the "
              f"montecarlo mix (scan floor {mc_update_floor(args.n_max)} "
              "slots is too heavy to warm)", file=sys.stderr)
        args.n_max = 2048
    return run_service(args)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
