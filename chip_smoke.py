#!/usr/bin/env python3
"""Drive the system's main path once on a TPU chip and check its answers.

    python3 chip_smoke.py               # one chip: phases 1-5 below
    python3 chip_smoke.py --four-chips  # four chips: the sharded fleet solve

One process holds the chip for the whole run.  Phases, each printing its
wall time, its compile time and what it checked:

  1. device — JAX must find a TPU; any other platform exits non-zero.
  2. served planning — ``PlanningService`` (buckets 64/256, grid 128) after
     ``warmup()``: 1792 bound requests (n_max 32768; ``corollary1`` and
     ``markov_arq``, dense and refine) and 256 Monte-Carlo requests (n_max
     2048, ``mc_impl="auto"``, the fast refine schedule) over all four
     link families.  Every record must come from the full solve, with no
     retry, breaker trip or post-warmup trace; bound plans must equal the
     numpy scalar planner; the Pallas engine's Monte-Carlo plans must equal
     the ``lax.scan`` engine's on the same batch.
  3. federated round — S=1024 devices through ``submit_round``, checked
     against the numpy ``plan_round_reference``.
  4. ridge — the paper's Fig.-4 task on the full 18,576-sample set at
     T = 1.5 N: the bound-planned pipelined run must beat sequential.
  5. trainer — ``llama3.2-1b`` at published width, cut to 2 of 16 layers,
     through Scenario -> BoundPlanner -> Simulator(StreamingTask): the
     loss must stay finite and fall.

``--four-chips`` runs only the fleet solve of one 256-scenario mixed batch
sharded over four chips and on one chip, for ``corollary1``,
``markov_arq`` and ``montecarlo``, and requires equal plans.

The last line of stdout is ``{"ok": true, "device": {...}}``; a failed
check exits non-zero before it.  The compile cache follows
``JAX_COMPILATION_CACHE_DIR`` or a fixed directory in the checkout.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

#: Monte-Carlo serving schedule (README "fast schedule"): common random
#: numbers, strides 32 then 6, one coarse seed, the best rate only, a
#: 2048-update coarse horizon and a +/-10 fine window.
MC_FAST = dict(mc_crn=True, mc_coarse_seeds=1, mc_refine_rates=1,
               mc_coarse_strides=(32, 6), mc_fine_radius=10,
               mc_coarse_updates=2048)
BUCKETS = (64, 256)
GRID = 128


class CheckFailed(Exception):
    """A phase's answer disagreed with its reference."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


class CompileClock:
    """Seconds JAX spent compiling (or fetching from the persistent
    cache) since the process started."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


class Phase:
    """Times one phase on the wall clock and the compile clock."""

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), self.clock.seconds
        return self

    def __exit__(self, *exc):
        self.wall = time.perf_counter() - self.t0
        self.compile = self.clock.seconds - self.c0

    def report(self, checked: str) -> None:
        print(f"[{self.name}] wall={self.wall:.3f}s "
              f"compile={self.compile:.3f}s | {checked}", flush=True)


# ---------------------------------------------------------------------------
# phase 1: the device
# ---------------------------------------------------------------------------


def require_tpu(count: int):
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"error: JAX found platform {dev.platform!r} "
              f"({dev.device_kind}), not a TPU", file=sys.stderr)
        raise SystemExit(1)
    if len(devices) != count:
        print(f"error: need {count} TPU chip(s), JAX found {len(devices)}",
              file=sys.stderr)
        raise SystemExit(1)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------------------
# phase 2: served planning
# ---------------------------------------------------------------------------


def _serve(service, requests, assign):
    """Submit every request with its (objective, grid mode) and wait."""
    futures = [service.submit(sc, objective=oid, grid_mode=mode)
               for sc, (oid, mode) in zip(requests, assign)]
    return [f.result(timeout=1200) for f in futures]


def _service_health(service, label: str) -> str:
    stats = service.stats()
    res = stats.resilience
    trips = sum(b["trips"] for b in res.get("breakers", {}).values())
    post = stats.counters.get("post_warmup_traces", 0)
    check(post == 0, f"{label}: {post} post-warmup traces")
    check(res.get("retries", 0) == 0, f"{label}: {res['retries']} retries")
    check(trips == 0, f"{label}: {trips} breaker trips")
    check(not res.get("fallbacks"), f"{label}: fallbacks {res['fallbacks']}")
    ph = stats.phases
    return (f"{stats.n_planned} planned in {stats.n_batches} batches, "
            f"p50={stats.latency_p50_ms:.3f}ms "
            f"p99={stats.latency_p99_ms:.3f}ms, solve per request's batch "
            f"{1e3 * ph['solve'] / ph['count']:.3f}ms (fenced "
            f"{1e3 * ph['solve_device'] / ph['count']:.3f}ms), warmup "
            f"{service.warmup_seconds:.3f}s/{service.warmup_traces} traces")


def served_planning(n_bound: int = 1792, n_mc: int = 256,
                    bound_n_max: int = 32768, mc_n_max: int = 2048,
                    buckets=BUCKETS, grid: int = GRID, seed: int = 0):
    """Phase 2.  Returns the bound service (phase 3 reuses it) and a
    summary line."""
    from repro.core.planner import fleet_grid
    from repro.core.scenario import ObjectivePlanner
    from repro.fleet import FleetPlanner
    from repro.serve import ALL_MODELS, PlanningService, ServiceConfig
    from repro.serve.catalogue import synth_requests

    bound = PlanningService(ServiceConfig(
        grid_size=grid, batch_buckets=tuple(buckets),
        objective_ids=("corollary1", "markov_arq"),
        grid_modes=("dense", "refine"), n_max=bound_n_max))
    bound.warmup()
    pairs = [(o, m) for o in ("corollary1", "markov_arq")
             for m in ("dense", "refine")]
    # no near-duplicates: every request is solved on the device, not
    # answered from the plan cache for a slightly different scenario
    reqs = synth_requests(n_bound, seed=seed, dup_frac=0.0,
                          models=ALL_MODELS, n_max=bound_n_max)
    assign = [pairs[i % len(pairs)] for i in range(n_bound)]
    with bound:
        records = _serve(bound, reqs, assign)
    check(all(r.fallback == "full" for r in records),
          "bound: a record came from the degradation ladder")
    bound_line = _service_health(bound, "bound service")

    # numpy reference: the scalar planner over the same per-request grid.
    # Every plan, dense or refined, must pick its (n_c, rate), and agree
    # with its bound value there.
    worst, mismatches = 0.0, []
    for i, (sc, (oid, mode), rec) in enumerate(zip(reqs, assign, records)):
        objective = bound.objectives[oid]
        ref = ObjectivePlanner(objective=objective,
                               grid=fleet_grid(sc.N, grid)).plan(
            sc, bound.consts)
        rel = abs(rec.bound_value - ref.bound_value) / abs(ref.bound_value)
        if (rec.n_c, rec.rate) != (ref.n_c, ref.rate) or not rel <= 1e-9:
            mismatches.append((i, oid, mode, (rec.n_c, rec.rate),
                               (ref.n_c, ref.rate), rec.bound_value,
                               ref.bound_value))
        worst = max(worst, rel)
    check(not mismatches, f"bound plans differ from numpy on "
          f"{len(mismatches)} requests, first {mismatches[:3]}")
    n_refine = sum(mode == "refine" for _, mode in assign)

    mc = PlanningService(ServiceConfig(
        grid_size=grid, batch_buckets=tuple(buckets),
        objective_ids=("montecarlo",), grid_modes=("refine",),
        n_max=mc_n_max, mc_impl="auto", **MC_FAST))
    impl = mc.planner._resolve_mc_impl()
    check(impl == "pallas", f"mc_impl=auto resolved to {impl!r}")
    mc.warmup()
    mc_reqs = synth_requests(n_mc, seed=seed + 1, dup_frac=0.0,
                             models=ALL_MODELS, n_max=mc_n_max)
    with mc:
        mc_records = _serve(mc, mc_reqs,
                            [("montecarlo", "refine")] * n_mc)
    check(all(r.fallback == "full" for r in mc_records),
          "montecarlo: a record came from the degradation ladder")
    mc_line = _service_health(mc, "montecarlo service")

    # the same batch through each engine
    batch = mc_reqs[:buckets[-1]]
    objective = mc.objectives["montecarlo"]
    plans = {}
    for engine in (impl, "scan"):
        planner = FleetPlanner(grid_size=grid, pow2_refine_widths=True,
                               mc_impl=engine)
        plans[engine] = planner.plan_batch(batch, mc.consts,
                                           objective=objective,
                                           grid_mode="refine")
    a, b = plans[impl], plans["scan"]
    same = (np.array_equal(a.n_c, b.n_c) and np.array_equal(a.rate, b.rate))
    check(same, f"{impl} and scan Monte-Carlo plans differ on "
          f"{int(np.sum((a.n_c != b.n_c) | (a.rate != b.rate)))} of "
          f"{len(batch)} scenarios")
    bitwise = np.array_equal(a.bound_value, b.bound_value)
    diff = float(np.max(np.abs(a.bound_value - b.bound_value)
                        / np.abs(b.bound_value)))
    return bound, (
        f"bound: {bound_line}; (n_c, rate) == numpy on all {n_bound} "
        f"({n_bound - n_refine} dense, {n_refine} refine), max rel "
        f"bound_value diff from numpy {worst:.3e} | montecarlo ({impl}): "
        f"{mc_line}; {impl} plans == scan plans on {len(batch)} scenarios, "
        f"losses bitwise {bitwise}, max rel diff {diff:.3e}")


# ---------------------------------------------------------------------------
# phase 3: a federated round
# ---------------------------------------------------------------------------


def federated_round(service, devices: int = 1024, seed: int = 0) -> str:
    from repro.federated.round import plan_round_reference
    from repro.serve import ALL_MODELS
    from repro.serve.catalogue import synth_population

    pop, deadline = synth_population(devices, seed=seed, models=ALL_MODELS)
    record = service.submit_round(pop, deadline=deadline)
    ref = plan_round_reference(pop, service.consts, deadline=deadline,
                               grid_size=service.config.grid_size).record()
    check(record.participants == ref.participants
          and record.n_c == ref.n_c and record.rate == ref.rate,
          f"round plan differs from numpy: K={record.n_participants} "
          f"vs {ref.n_participants}")
    check(record.feasible, "round infeasible")
    return (f"S={devices}: K={record.n_participants} of "
            f"{record.n_eligible} eligible == numpy reference")


# ---------------------------------------------------------------------------
# phase 4: the paper's ridge task
# ---------------------------------------------------------------------------


def ridge(n: int = 18_576) -> str:
    from repro.core import (BoundConstants, BoundPlanner, RidgeTask,
                            Scenario, Simulator)
    from repro.data import make_regression_dataset

    X, y, _ = make_regression_dataset(n=n)
    scenario = Scenario(N=n, T=1.5 * n, n_o=500.0)
    consts = BoundConstants(L=1.908, c=0.061, M=1.0, M_G=1.0, D=6.0,
                            alpha=1e-4)
    task = RidgeTask(X=X, y=y)
    plan = BoundPlanner().plan(scenario, consts)
    piped = Simulator().run(scenario, plan, task).final_loss
    seq = Simulator().run(scenario, BoundPlanner(grid=[n]).plan(
        scenario, consts), task).final_loss
    check(np.isfinite(piped) and np.isfinite(seq),
          f"non-finite ridge loss: {piped}, {seq}")
    check(piped < seq, f"pipelined loss {piped} not below sequential {seq}")
    return (f"N={n}, T=1.5N: pipelined n_c={plan.n_c} loss {piped:.6f} < "
            f"sequential loss {seq:.6f}")


# ---------------------------------------------------------------------------
# phase 5: one trainer at published width
# ---------------------------------------------------------------------------


def trainer(arch: str = "llama3.2-1b", layers: int = 2, batch: int = 4,
            seq: int = 1024, steps: int = 64, lr: float = 1e-3,
            seed: int = 0, memory_limit=None) -> str:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core import (BoundConstants, BoundPlanner, Scenario,
                            Simulator, StreamingTask)
    from repro.data.synthetic import SyntheticTokens
    from repro.models import init_params, make_train_step
    from repro.optim import linear_warmup_cosine
    from repro.optim.optimizers import make_optimizer

    full = get_config(arch)
    cfg = dataclasses.replace(full, num_layers=layers)
    opt = make_optimizer("adamw", linear_warmup_cosine(lr, 5, steps))
    params = init_params(cfg, seed)
    opt_state = opt.init(params)
    batch_spec = {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}
    step = jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1)).lower(
        params, opt_state, jax.ShapeDtypeStruct((), jnp.int32),
        batch_spec).compile()
    mem = step.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    if memory_limit is not None:
        check(total < 0.8 * memory_limit,
              f"train step needs {total} bytes of {memory_limit}")

    n_seqs = max(steps * batch // 4, batch * 4)
    data = SyntheticTokens(cfg.vocab_size, seq + 1, n_seqs, seed).batch(0)
    scenario = Scenario(N=n_seqs, T=float(steps), n_o=8.0, tau_p=1.0)
    consts = BoundConstants(L=1.0, c=0.05, M=1.0, M_G=1.0, D=2.0, alpha=lr)
    plan = BoundPlanner().plan(scenario, consts)
    task = StreamingTask(
        train_step=step, params=params, opt_state=opt_state,
        dataset=np.asarray(data), batch_size=batch,
        make_batch=lambda tok: {"tokens": jnp.asarray(tok[:, :seq])},
        seed=seed)
    report = Simulator().run(scenario, plan, task)
    losses = [h["loss"] for h in report.state.history]
    check(len(losses) >= 2, f"only {len(losses)} logged losses")
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    return (f"{arch} d_model={cfg.d_model} heads={cfg.num_heads}/"
            f"{cfg.num_kv_heads} d_ff={cfg.d_ff} vocab={cfg.vocab_size}, "
            f"depth cut {full.num_layers}->{layers} layers, batch={batch} "
            f"seq={seq}; step bytes: args={mem.argument_size_in_bytes} "
            f"out={mem.output_size_in_bytes} temp={mem.temp_size_in_bytes} "
            f"alias={mem.alias_size_in_bytes} total={total}; n_c={plan.n_c}, "
            f"{report.delivered}/{n_seqs} seqs delivered, "
            f"loss {losses[0]:.4f} -> {losses[-1]:.4f} "
            f"over {report.state.step + 1} slots")


# ---------------------------------------------------------------------------
# --four-chips: the sharded fleet solve
# ---------------------------------------------------------------------------


def sharded_fleet(n: int = 256, n_max: int = 2048, grid: int = GRID,
                  seed: int = 0) -> str:
    from repro.fleet import FleetPlanner, ScenarioBatch
    from repro.serve import ALL_MODELS, resolve_objectives
    from repro.serve.catalogue import (default_consts, mc_update_floor,
                                       synth_requests)

    consts = default_consts()
    objectives = resolve_objectives(
        ("corollary1", "markov_arq", "montecarlo"),
        mc_min_updates=mc_update_floor(n_max),
        mc_options={k[3:]: v for k, v in MC_FAST.items()})
    batch = ScenarioBatch.from_scenarios(
        synth_requests(n, seed=seed, models=ALL_MODELS, n_max=n_max))
    lines = []
    for oid, objective in objectives.items():
        for mode in (("dense", "refine") if oid != "montecarlo"
                     else ("refine",)):
            plans, warm_s = [], []
            for shard in (True, False):
                planner = FleetPlanner(grid_size=grid, shard=shard,
                                       pow2_refine_widths=True)
                planner.plan_batch(batch, consts, objective=objective,
                                   grid_mode=mode)          # compiles
                t0 = time.perf_counter()
                plans.append(planner.plan_batch(
                    batch, consts, objective=objective, grid_mode=mode))
                warm_s.append(time.perf_counter() - t0)
            a, b = plans
            check(np.array_equal(a.n_c, b.n_c)
                  and np.array_equal(a.rate, b.rate),
                  f"{oid}/{mode}: sharded and one-chip plans differ on "
                  f"{int(np.sum((a.n_c != b.n_c) | (a.rate != b.rate)))} "
                  f"of {n}")
            lines.append(f"{oid}/{mode} equal (bound_value bitwise "
                         f"{np.array_equal(a.bound_value, b.bound_value)}; "
                         f"warm plan_batch {warm_s[0]:.4f}s sharded, "
                         f"{warm_s[1]:.4f}s one chip)")
    return f"{n} scenarios, sharded over 4 chips vs one: " + "; ".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the fleet solve sharded over four chips "
                         "against one chip")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    device = require_tpu(4 if args.four_chips else 1)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"error: cannot import repro from {ROOT / 'src'}: {e}",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    print(f"[device] {device['kind']} x{device['count']}; compile cache "
          f"{cache_dir}", flush=True)

    import jax
    try:
        if args.four_chips:
            with Phase("sharded-fleet", clock) as ph:
                line = sharded_fleet()
            ph.report(line)
        else:
            with Phase("served-planning", clock) as ph:
                service, line = served_planning()
            ph.report(line)
            with Phase("federated-round", clock) as ph:
                line = federated_round(service)
            ph.report(line)
            with Phase("ridge", clock) as ph:
                line = ridge()
            ph.report(line)
            limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
            with Phase("trainer", clock) as ph:
                line = trainer(memory_limit=limit)
            ph.report(line)
    except CheckFailed as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    print(f"[total] wall={time.perf_counter() - t_start:.3f}s "
          f"compile={clock.seconds:.3f}s", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
