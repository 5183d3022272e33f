"""Leaf spans of the host path: per-thread chunk records, the span ring's
columns and identifiers, the garbage-collection hook, and the program
spans a profiler trace of a served stream shows."""
import gc
import glob
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.core import BoundConstants
from repro.fleet import FleetPlanner, PlanCache
from repro.fleet.objective_kernels import pow2ceil
from repro.obs import LEAVES, RequestSpan, SpanRecorder, runtime
from repro.serve import (MicroBatcher, PlanningService, PlanRequest,
                         ServiceConfig, synth_requests)

CONSTS = BoundConstants(L=1.908, c=0.061, M=1.0, M_G=1.0, D=1.0, alpha=1e-4)
SMALL = dict(grid_size=16, batch_buckets=(4, 8), flush_interval=0.01,
             objective_ids=("corollary1", "markov_arq"), n_max=512)
#: planner leaves timed inside ``plan_many``'s solve interval
SOLVE_LEAVES = ("planner.build", "planner.dispatch", "planner.device_wait",
                "planner.fetch", "planner.refine_host", "planner.records")


@pytest.fixture(scope="module")
def service():
    svc = PlanningService(ServiceConfig(**SMALL), consts=CONSTS)
    svc.warmup()
    svc.start()
    yield svc
    svc.stop()


def _serve(svc, n, seed, mode="refine"):
    reqs = synth_requests(n, seed=seed, dup_frac=0.0, n_classes=n,
                          models=("erasure", "gilbert_elliott"), n_max=512)
    futures = [svc.submit(sc, objective="corollary1", grid_mode=mode)
               for sc in reqs]
    for f in futures:
        f.result(timeout=60)


def test_phase_accumulation_is_per_thread():
    seen = {}

    def worker(name, n):
        runtime.open_record()
        for _ in range(n):
            with runtime.span(name):
                pass
        runtime.count("dispatches", n)
        seen[name] = runtime.take_record()
        runtime.close_record()

    runtime.open_record()
    try:
        with runtime.span("planner.fetch"):
            threads = [threading.Thread(target=worker, args=(leaf, k + 2))
                       for k, leaf in enumerate(("serve.wait",
                                                 "planner.dispatch"))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        phases, counts, _ = runtime.take_record()
    finally:
        runtime.close_record()
    # each thread saw only its own leaves and counters
    assert set(phases) == {"planner.fetch"} and counts == {}
    assert set(seen["serve.wait"][0]) == {"serve.wait"}
    assert seen["serve.wait"][1] == {"dispatches": 2}
    assert set(seen["planner.dispatch"][0]) == {"planner.dispatch"}
    assert seen["planner.dispatch"][1] == {"dispatches": 3}
    # taking a record opens a fresh one; with none open, spans add nothing
    runtime.open_record()
    runtime.take_record()
    assert runtime.take_record()[:2] == ({}, {})
    runtime.close_record()
    with runtime.span("serve.take"):
        runtime.count("dispatches")
    assert runtime.take_record() is None


def test_planner_leaves_within_solve_and_phases_sum_to_latency(service):
    _serve(service, 12, seed=60)
    spans = [s for s in service.spans.snapshot() if s.bucket > 0]
    assert spans
    for s in spans:
        # the five request phases still partition the latency exactly
        assert abs(s.phase_sum - s.latency_s) <= 1e-6, s
        leaves = s.leaves()
        solve_leaves = sum(leaves[name] for name in SOLVE_LEAVES)
        assert solve_leaves <= s.solve_s + 1e-9, s
        assert leaves["planner.cache_lookup"] <= s.cache_lookup_s + 1e-9
        assert s.solve_device_s == pytest.approx(
            min(leaves["planner.device_wait"], s.solve_s))
        assert s.dispatches >= 1 and s.h2d_arrays >= s.dispatches
        assert s.d2h_arrays >= s.dispatches
        assert 1 <= s.lanes_unique <= s.lanes_live <= s.bucket
    # a refined solve on a grid wide enough to refine: a coarse and a
    # fine pass with host work between them, all inside solve_s
    reqs = synth_requests(6, seed=63, dup_frac=0.0, n_classes=6,
                          models=("erasure",), n_max=4096)
    timings = {}
    runtime.open_record()
    try:
        FleetPlanner(grid_size=128).plan_many(
            reqs, CONSTS, cache=PlanCache(maxsize=64), pad_to=8,
            grid_mode="refine", timings=timings)
        phases, counts, _ = runtime.take_record()
    finally:
        runtime.close_record()
    assert counts["dispatches"] == 2
    assert counts["lanes_live"] == counts["lanes_unique"] == 6
    assert phases["planner.refine_host"] > 0.0
    assert sum(phases[name] for name in SOLVE_LEAVES) <= timings["solve_s"]


def test_requests_of_one_chunk_share_chunk_id(service):
    before = service.spans.recorded
    batches = service.stats().counters["batches"]
    _serve(service, 16, seed=61, mode="dense")
    spans = service.spans.snapshot()[-(service.spans.recorded - before):]
    by_chunk = {}
    for s in spans:
        by_chunk.setdefault(s.chunk_id, []).append(s)
    assert len(by_chunk) >= 2 and min(by_chunk) >= 0
    for members in by_chunk.values():
        # chunk-shared fields agree within a chunk
        assert len({(m.flush_id, m.solve_s, m.pad_s, m.dispatches,
                     m.bucket) for m in members}) == 1
        assert members[0].lanes_live == len(members)
    # distinct chunks carry distinct identifiers, in recording order
    ids = [s.chunk_id for s in spans]
    assert ids == sorted(ids)
    assert len(by_chunk) == service.stats().counters["batches"] - batches


def test_span_ring_columns_and_identifiers():
    rec = SpanRecorder(capacity=6)
    a = rec.record_chunk(objective="corollary1", grid_mode="dense",
                         bucket=4, enqueue_t=[0.0, 0.5, 1.0], admit_s=1e-5,
                         t_start=2.0, t_end=3.0, pad_s=0.1, solve_s=0.5,
                         flush_id=7, phases={"planner.dispatch": 0.2,
                                             "planner.device_wait": 0.1},
                         counts={"dispatches": 1, "lanes_live": 3},
                         gc_s=0.01)
    b = rec.record_chunk(objective="markov_arq", grid_mode="refine",
                         bucket=4, enqueue_t=[2.5, 2.6], admit_s=[0.0, 0.0],
                         t_start=3.0, t_end=3.5, flush_id=8)
    assert rec.record_chunk(objective="x", grid_mode="dense", bucket=4,
                            enqueue_t=[], admit_s=0.0, t_start=0.0,
                            t_end=0.0) == -1
    assert (a, b) == (0, 1)
    spans = rec.snapshot()
    assert [s.chunk_id for s in spans] == [0, 0, 0, 1, 1]
    assert [s.flush_id for s in spans] == [7, 7, 7, 8, 8]
    first = spans[0]
    assert first.batch_wait_s == pytest.approx(2.0)
    assert first.latency_s == pytest.approx(3.0)
    assert first.resolve_s == pytest.approx(0.4)   # the remainder
    assert first.planner_dispatch_s == pytest.approx(0.2)
    assert first.solve_device_s == pytest.approx(0.1)
    assert (first.dispatches, first.lanes_live, first.gc_s) == (1, 3, 0.01)
    assert spans[3].objective == "markov_arq"
    assert all(abs(s.phase_sum - s.latency_s) < 1e-12 for s in spans)
    # wrapping evicts the oldest requests; no request outlives its chunk
    for i in range(4):
        rec.record_chunk(objective="corollary1", grid_mode="dense",
                         bucket=4, enqueue_t=[10.0 + i], admit_s=0.0,
                         t_start=11.0 + i, t_end=12.0 + i)
    spans = rec.snapshot()
    assert len(spans) == 6 and rec.recorded == 9
    assert [s.chunk_id for s in spans] == [1, 1, 2, 3, 4, 5]
    totals = rec.totals()
    assert totals["chunks"] == 6 and totals["count"] == 9
    assert totals["planner.dispatch"] == pytest.approx(0.2)


def test_recording_100k_spans_keeps_no_python_objects():
    rec = SpanRecorder()
    assert rec.capacity >= 131072
    phases = {name: 1e-4 for name in LEAVES}
    counts = {"dispatches": 2, "lanes_live": 64, "lanes_unique": 64}
    enq = np.arange(64, dtype=np.float64)
    admit = np.zeros(64)
    gc.collect()
    before = len(gc.get_objects())
    for c in range(1563):                      # 100,032 requests
        rec.record_chunk(objective="corollary1", grid_mode="dense",
                         bucket=64, enqueue_t=enq + c, admit_s=admit,
                         t_start=c + 64.0, t_end=c + 64.01, pad_s=1e-4,
                         solve_s=1e-2, flush_id=c, phases=phases,
                         counts=counts)
    gc.collect()
    assert len(gc.get_objects()) - before < 1000
    assert rec.recorded == 100032 and len(rec) == 100032
    spans = rec.snapshot()
    assert len(spans) == 100032 and spans[-1].chunk_id == 1562
    assert isinstance(spans[0], RequestSpan) and spans[0].lanes_live == 64


def test_batcher_stamps_flush_ids_and_times_its_leaves():
    taken = []

    def plan_group(reqs):
        taken.append(([r.flush_id for r in reqs], runtime.take_record()))
        for r in reqs:
            r.future.set_result(r.scenario)

    b = MicroBatcher(plan_group, max_batch=3, flush_interval=0.005)
    b.start()
    # let the worker reach its idle wait first: a full batch queued before
    # it looks is taken at once, with no wait in that flush's record
    time.sleep(0.05)
    try:
        for batch in ((0, 1, 2), (3,)):
            futs = [b.submit(PlanRequest(scenario=i)) for i in batch]
            for f in futs:
                f.result(timeout=5.0)
    finally:
        b.stop()
    ids = [i for flush, _ in taken for i in flush]
    assert ids == sorted(ids) and ids[0] == 0 and len(set(ids)) == len(taken)
    assert b.taken == len(taken)
    for _, (phases, _, _) in taken:
        assert {"serve.wait", "serve.take"} <= set(phases)


def _host_lines(directory):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{directory}/**/*.xplane.pb",
                            recursive=True))[-1]
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device"):
            continue
        for line in plane.lines:
            lines.append([(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events])
    return lines


def _profile(directory, body):
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(directory), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return _host_lines(directory)


def test_profiled_stream_names_program_spans_on_the_host(service, tmp_path):
    lines = _profile(tmp_path, lambda: _serve(service, 12, seed=62))
    names = {name for line in lines for name, _, _ in line}
    assert {"serve.wait", "planner.dispatch", "planner.fetch",
            "serve.resolve"} <= names
    # the worker's program spans are leaves: none overlaps another
    worker = [line for line in lines
              if any(name == "serve.resolve" for name, _, _ in line)]
    assert len(worker) == 1
    leaves = sorted((a, b, name) for name, a, b in worker[0]
                    if name.startswith(("serve.", "planner.")))
    assert len(leaves) >= 10
    for (_, end, prev), (start, _, name) in zip(leaves, leaves[1:]):
        assert start >= end, (prev, name)


def test_gc_collect_is_annotated_and_counted(tmp_path):
    runtime.install_gc_hook()
    try:
        before = runtime.gc_totals()
        runtime.open_record()
        lines = _profile(tmp_path, lambda: gc.collect())
        _, _, gc_s = runtime.take_record()
        runtime.close_record()
        after = runtime.gc_totals()
    finally:
        runtime.remove_gc_hook()
    assert "gc.gen2" in {name for line in lines for name, _, _ in line}
    assert after["collections"][2] > before["collections"][2]
    assert after["pause_s"][2] > before["pause_s"][2]
    assert gc_s > 0.0


def test_idle_worker_shows_in_a_trace_begun_mid_wait(tmp_path):
    b = MicroBatcher(lambda reqs: None, max_batch=4, flush_interval=0.005)
    b.start()
    try:
        time.sleep(0.05)        # the worker is already waiting
        lines = _profile(tmp_path, lambda: time.sleep(0.2))
    finally:
        b.stop()
    waits = [(a, z) for line in lines for name, a, z in line
             if name == "serve.wait"]
    # the idle wait is cut into pieces, so the trace holds most of it
    assert sum(z - a for a, z in waits) >= 0.5 * 0.2e9


def test_grid_solves_copy_back_once_per_call(service):
    """A grid solve returns its outputs packed into one device buffer: a
    served chunk of grid-objective requests records one copy back per
    jitted call, dense or two-pass."""
    before = service.spans.recorded
    _serve(service, 12, seed=64, mode="dense")
    _serve(service, 12, seed=65, mode="refine")
    spans = service.spans.snapshot()[-(service.spans.recorded - before):]
    solved = [s for s in spans if s.dispatches]
    assert solved
    for s in solved:
        assert s.d2h_arrays == s.dispatches, s
    reqs = synth_requests(6, seed=66, dup_frac=0.0, n_classes=6,
                          models=("erasure",), n_max=4096)
    runtime.open_record()
    try:
        FleetPlanner(grid_size=128).plan_many(
            reqs, CONSTS, cache=PlanCache(maxsize=64), pad_to=8,
            grid_mode="refine")
        _, counts, _ = runtime.take_record()
    finally:
        runtime.close_record()
    assert counts["d2h_arrays"] == counts["dispatches"] == 2


_MC_COUNTS_SCRIPT = """
import json
import numpy as np, jax
from repro.core import BoundConstants
from repro.core.objectives import MonteCarloObjective
from repro.core.scenario import ErasureLink, Scenario
from repro.fleet import FleetPlanner
from repro.obs import runtime

rng = np.random.default_rng(0)
X = rng.normal(size=(48, 4))
y = X @ rng.normal(size=4) + 0.1 * rng.normal(size=48)
mc = MonteCarloObjective(X=X, y=y, n_runs=2, alpha=1e-3, seed=0)
scs = [Scenario(N=int(n), T=1.3 * n, n_o=float(o), tau_p=tau,
                link=ErasureLink(beta=0.4, p_base=0.05, rates=(1.0, 2.0)))
       for n, o, tau in zip((256, 384, 512, 320, 288, 448, 352, 400),
                            (20, 90, 45, 150, 60, 10, 120, 75),
                            (1.0, 0.5, 2.0, 1.0, 0.5, 1.0, 2.0, 1.0))]
consts = BoundConstants(L=1.908, c=0.061, M=1.0, M_G=1.0, D=1.0,
                        alpha=1e-4)
planner = FleetPlanner(grid_size=8, mc_impl="scan")
out = {}
for n, pad_to in ((8, 8), (6, 6), (5, 8)):
    runtime.open_record()
    planner.plan_many(scs[:n], consts, pad_to=pad_to, objective=mc)
    out[f"{n}/{pad_to}"] = runtime.take_record()[1]
    runtime.close_record()
print("COUNTS", json.dumps({"devices": jax.device_count(), "counts": out}))
"""


@pytest.mark.parametrize("devices", [1, 4])
def test_montecarlo_chunk_counts_lane_slots_and_sharded_passes(devices):
    """Each Monte-Carlo pass counts its lanes' padded slots and the slots
    before each lane's deadline (pad lanes repeat the smallest scenario),
    and counts a sharded dispatch only where the batch splits evenly over
    every device: never on one device, nor for a batch of 6 on four.
    Each pass copies its arguments in as one packed buffer and its
    results back as one."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.pathsep.join(
                   ["src", os.environ.get("PYTHONPATH", "")]))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", _MC_COUNTS_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=repo)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("COUNTS")]
    got = json.loads(line[0].split(" ", 1)[1])
    assert got["devices"] == devices
    N = (256, 384, 512, 320, 288, 448, 352, 400)
    tau = (1.0, 0.5, 2.0, 1.0, 0.5, 1.0, 2.0, 1.0)
    per_scenario = 2 * 8        # rates x grid points
    for key, counts in got["counts"].items():
        n, pad_to = (int(v) for v in key.split("/"))
        small = min(range(n), key=lambda i: N[i])
        rows = list(range(n)) + [small] * (pad_to - n)
        total = [int(np.floor(1.3 * N[i] / tau[i])) for i in rows]
        horizon = pow2ceil(max(total))
        assert counts["dispatches"] == 1
        assert counts["h2d_arrays"] == counts["d2h_arrays"] == 1, key
        assert counts["mc_lane_slots"] == 2 * per_scenario * pad_to * horizon
        assert counts["mc_live_slots"] == 2 * per_scenario * sum(
            min(t, horizon) for t in total)
        sharded = devices > 1 and pad_to % devices == 0
        assert counts.get("mc_sharded_dispatches", 0) == int(sharded), key


def test_montecarlo_run_slots_step_each_block_to_its_longest_deadline():
    """``mc_run_slots`` for a hand-built batch: 64 lanes a scenario, so
    two scenarios share a 128-lane block and every lane of a block steps
    to the longer deadline; pad lanes of a part block count nothing.
    Ordering by deadline shortens the blocks, and live <= run <= lane."""
    from repro.fleet.objective_kernels import _count_mc, _mc_horizons, \
        _mc_order

    def counts(deadlines, kernel_devices, order=None):
        h = np.asarray(deadlines, np.float64)
        arrays = {"T": 2.0 * h, "tau_p": np.full(h.size, 2.0),
                  "rates": np.zeros((h.size, 2)),
                  "grid": np.zeros((h.size, 32))}
        if order is not None:
            arrays = {k: v[order] for k, v in arrays.items()}
        runtime.open_record()
        try:
            _count_mc(arrays, 2, 512, False, kernel_devices)
            return runtime.take_record()[1]
        finally:
            runtime.close_record()

    h = [5, 40, 7, 1000, 30, 30, 2, 9]          # 1000 is capped at 512
    got = counts(h, 1)
    assert got["mc_run_slots"] == 2 * 128 * (40 + 512 + 30 + 9)
    assert got["mc_live_slots"] == 2 * 64 * (5 + 40 + 7 + 512 + 30 + 30
                                             + 2 + 9)
    assert got["mc_lane_slots"] == 2 * 64 * 8 * 512
    horizon = _mc_horizons({"T": 2.0 * np.asarray(h, np.float64),
                            "tau_p": np.full(8, 2.0)}, 512)
    # sorted: 2 5 7 9 30 30 40 512, in blocks of two
    got = counts(h, 1, _mc_order(horizon, 1))
    assert got["mc_run_slots"] == 2 * 128 * (5 + 9 + 30 + 512)
    # dealt over four devices: (2, 30) (5, 30) (7, 40) (9, 512)
    got = counts(h, 4, _mc_order(horizon, 4))
    assert got["mc_run_slots"] == 2 * 128 * (30 + 30 + 40 + 512)
    assert got["mc_live_slots"] <= got["mc_run_slots"] \
        <= got["mc_lane_slots"]
    # three scenarios: one full block (5, 40), one of 64 lanes (7)
    assert counts(h[:3], 1)["mc_run_slots"] == 2 * (128 * 40 + 64 * 7)
    # the scan engines run no kernel
    assert "mc_run_slots" not in counts(h, 0)


@pytest.mark.parametrize("devices", [1, 4])
def test_montecarlo_pallas_pass_counts_run_slots(devices):
    """A served pallas pass counts its run slots: 16 lanes a scenario, so
    each device's lanes fill at most one block, which steps to the
    longest deadline the device holds after the deal by deadline; the
    pass copies in and back once."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.pathsep.join(
                   ["src", os.environ.get("PYTHONPATH", "")]))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = _MC_COUNTS_SCRIPT.replace('mc_impl="scan"', 'mc_impl="pallas"')
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=repo)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("COUNTS")]
    got = json.loads(line[0].split(" ", 1)[1])
    N = (256, 384, 512, 320, 288, 448, 352, 400)
    tau = (1.0, 0.5, 2.0, 1.0, 0.5, 1.0, 2.0, 1.0)
    for key, counts in got["counts"].items():
        n, pad_to = (int(v) for v in key.split("/"))
        small = min(range(n), key=lambda i: N[i])
        rows = list(range(n)) + [small] * (pad_to - n)
        total = sorted(int(np.floor(1.3 * N[i] / tau[i])) for i in rows)
        parts = devices if pad_to % devices == 0 else 1
        per_part = pad_to // parts
        # part c holds sorted positions c, c + parts, ...: its longest is
        # the last of them
        longest = [total[c + parts * (per_part - 1)] for c in range(parts)]
        assert counts["mc_run_slots"] == 2 * 16 * per_part * sum(longest)
        assert counts["mc_live_slots"] <= counts["mc_run_slots"] \
            <= counts["mc_lane_slots"]
        assert counts["h2d_arrays"] == counts["d2h_arrays"] == 1, key
