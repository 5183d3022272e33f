"""Federated round driver: warm the round path, plan rounds, validate.

Entry point for the federated subsystem (:mod:`repro.federated`) through
the serving layer: AOT-warm the round kernel at every configured
population bucket, then plan ``--rounds`` federated rounds over
synthetic candidate populations (mixed link families — Gilbert-Elliott
burst chains are the natural stragglers) and print each round's
participant count, straggler-bounded round time and aggregated bound.

  PYTHONPATH=src python -m repro.launch.federated \\
      --devices 64 --rounds 4 --pop-buckets 64 --grid 64 \\
      --models all --verify --simulate \\
      --metrics-textfile metrics.prom

``--verify`` re-plans every round with the pure-numpy reference
(:func:`repro.federated.plan_round_reference`) and exits 1 on any
participant-set or operating-point mismatch; ``--simulate`` runs the
first round end-to-end through :class:`repro.federated.
FederatedSimulator` (sharded local SGD + deadline-gated averaging) on a
small synthetic ridge task.  Exit codes: 2 on unknown names (usage), 1
on post-warmup traces or a parity mismatch, 0 otherwise.
"""
from __future__ import annotations

import argparse
import sys
from typing import Optional, Sequence

import numpy as np

from repro.federated import FederatedSimulator, plan_round_reference
from repro.serve import (ALL_MODELS, PlanningService, ServiceConfig,
                         parse_models, synth_population)
from repro.serve.export import write_textfile


def _parse_buckets(spec: str):
    try:
        buckets = tuple(int(s) for s in spec.split(",") if s.strip())
    except ValueError as e:
        raise ValueError(f"bad bucket list {spec!r}: {e}") from None
    if not buckets:
        raise ValueError(f"bad bucket list {spec!r}: no buckets")
    return buckets


def run_federated(args) -> int:
    try:
        models = parse_models(args.models)
        config = ServiceConfig(
            grid_size=args.grid, batch_buckets=(8,),
            grid_modes=("dense",), objective_ids=("corollary1",),
            population_buckets=_parse_buckets(args.pop_buckets),
            n_max=args.n_max, warm_models=models,
            journal_path=args.journal)
        # fail fast on unknown model names before paying warmup
        synth_population(1, seed=args.seed, models=models,
                         n_max=args.n_max)
    except (KeyError, ValueError) as e:
        print(f"error: {e.args[0] if isinstance(e, KeyError) else e}",
              file=sys.stderr)
        return 2

    service = PlanningService(config)
    n_traces = service.warmup()
    print(f"warmup: {n_traces} kernel traces in "
          f"{service.warmup_seconds:.2f}s over "
          f"{len(config.population_buckets)} population bucket(s) "
          f"{list(config.population_buckets)}")

    mismatches = 0
    records = []
    for r in range(args.rounds):
        pop, deadline = synth_population(
            args.devices, seed=args.seed + r, models=models,
            n_max=args.n_max, deadline_frac=args.deadline_frac)
        record = service.submit_round(pop, deadline=deadline)
        records.append((pop, deadline, record))
        if record.feasible:
            print(f"round {r}: K={record.n_participants} of "
                  f"{record.n_eligible} eligible "
                  f"({len(pop)} candidates); round_time="
                  f"{record.round_time:.1f} of deadline={deadline:.1f}; "
                  f"F={record.objective_value:.6g}")
        else:
            print(f"round {r}: INFEASIBLE — no device can deliver by "
                  f"deadline={deadline:.1f}")
        if args.verify:
            ref = plan_round_reference(pop, service.consts,
                                       deadline=deadline,
                                       grid_size=args.grid).record()
            if (ref.participants != record.participants
                    or ref.n_c != record.n_c or ref.rate != record.rate):
                mismatches += 1
                print(f"round {r}: PARITY MISMATCH vs numpy reference\n"
                      f"  served:    {record.participants} {record.n_c}\n"
                      f"  reference: {ref.participants} {ref.n_c}",
                      file=sys.stderr)

    if args.simulate and records:
        pop, deadline, record = next(
            ((p, d, rec) for p, d, rec in records if rec.feasible),
            records[0])
        if record.feasible:
            from repro.data.synthetic import make_regression_dataset
            X, y, _ = make_regression_dataset(n=512, d=8, seed=args.seed)
            from repro.core.scenario import RidgeTask
            plan = service.round_planner.plan_round(
                pop, service.consts, deadline=deadline,
                pad_to=service._population_bucket(len(pop)))
            report = FederatedSimulator().run_round(
                pop, plan, RidgeTask(X=X, y=y), seed=args.seed)
            print(f"simulate: {report.n_completed}/"
                  f"{len(report.participants)} participants completed "
                  f"by T={report.deadline:.1f}; aggregated ridge loss "
                  f"{report.aggregated_loss:.4f}")
        else:
            print("simulate: skipped (no feasible round)")

    stats = service.stats()
    post = stats.counters.get("post_warmup_traces", 0)
    print(f"post-warmup jit traces: {post} "
          f"({'SLO met' if post == 0 else 'SLO VIOLATED'})")
    snap = service.federated.snapshot()
    print(f"rounds: {snap['rounds']} planned, "
          f"{snap['participants']} participants selected, "
          f"{snap['infeasible_rounds']} infeasible")
    if args.verify:
        print(f"verify: {mismatches} mismatches over {args.rounds} "
              f"round(s) vs the numpy reference")
    if args.metrics_textfile:
        write_textfile(service.metrics, args.metrics_textfile)
        print(f"metrics: wrote Prometheus textfile "
              f"{args.metrics_textfile}")
    service.journal.close()
    return 0 if post == 0 and mismatches == 0 else 1


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--devices", type=int, default=64,
                    help="candidate devices per round")
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--models", default="all",
                    help="comma-separated link model mix, or 'all' "
                         f"({', '.join(ALL_MODELS)})")
    ap.add_argument("--grid", type=int, default=64,
                    help="per-device n_c grid width")
    ap.add_argument("--pop-buckets", default="64,256",
                    help="comma-separated pow2 population pad shapes "
                         "(AOT-warmed; rounds inside the largest pay no "
                         "trace)")
    ap.add_argument("--n-max", type=int, default=4096,
                    help="cap on drawn per-device dataset sizes")
    ap.add_argument("--deadline-frac", type=float, default=1.6,
                    help="round deadline as a multiple of the median "
                         "device dataset size")
    ap.add_argument("--verify", action="store_true",
                    help="check every round against the numpy reference "
                         "(exit 1 on mismatch)")
    ap.add_argument("--simulate", action="store_true",
                    help="run the first feasible round end-to-end through "
                         "FederatedSimulator on a synthetic ridge task")
    ap.add_argument("--metrics-textfile", default=None,
                    help="write the Prometheus text exposition here")
    ap.add_argument("--journal", default=None,
                    help="append audit events to this JSONL file")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    return run_federated(args)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
