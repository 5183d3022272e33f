"""One run of one cell: set-up, the measured window, the check, the metrics.

Set-up (counted in ``setup_s``, from the process's first statement to
the window's start): device check, import, the service built from the
configuration and warmed up for the cell's own shapes (from the
persistent compile cache at ``<checkout>/.bench_cache/jax-<platform>``), the
traffic drawn from the seed, and a short pre-roll of real requests.
Then the window: ``seconds`` of load, traced by the profiler with
``trace``.  After it: every request due in the window is waited for (a
minute past the close at most), the peak device memory is read, the
service is stopped, and a sample of the plans drawn from the seed is
compared with the configuration's plain reference.
"""
from __future__ import annotations

import contextlib
import gc
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from harness import loops, spec as spec_mod, stats, system, traffic as gen

#: How long past the window's close a request due in it is waited for.
GRACE_S = 60.0


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def device_info(chips: int, require_chip: bool) -> Dict:
    import jax
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"JAX found platform {devs[0].platform!r}, not a TPU")
        if len(devs) != chips:
            raise NoChip(f"the cell runs on {chips} chip(s); JAX found "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def enable_compile_cache(root: Path, platform: str) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    one directory per platform: the CPU rehearsals write entries that a
    TPU run must not meet (where the cache evicts, an entry written
    without eviction breaks every later write)."""
    import jax
    path = str(Path(root) / ".bench_cache" / f"jax-{platform}")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


@dataclass
class Context:
    """What a metric reader may read."""
    config: Dict
    traffic: Dict
    device: Dict
    seconds: float
    setup_s: float
    latency_s: List[float]
    completed_in_window: int
    spans: List = field(default_factory=list)
    trace: Optional[object] = None


@contextlib.contextmanager
def _profiling(directory: Path):
    """A profiler trace of the block, with the Python tracer off (it
    would slow every Python call of the service)."""
    import jax
    shutil.rmtree(directory, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(directory), profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _annotation(name: Optional[str]):
    if name is None:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def _merged(base: Dict, extra: Optional[Dict]) -> Dict:
    out = dict(base)
    for k, v in (extra or {}).items():
        out[k] = _merged(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def _ok(outcome) -> bool:
    return isinstance(outcome, dict) and outcome["fallback"] == "full"


def run_cell(root: Path, cell: str, seed: int, seconds: float, trace: bool,
             t_process: float, *, require_chip: bool = True,
             overrides: Optional[Dict] = None,
             substitute: Optional[str] = None,
             keep: Optional[List] = None) -> Dict:
    """Run one cell and return its result line as a dict.

    ``overrides`` (tests, and the sweep tool's rates) replaces parts of the
    configuration or the traffic; ``substitute="control"`` puts the
    reference's lower-precision plans in the program's place before the
    comparison."""
    overrides = overrides or {}
    spec = spec_mod.load_cell(root, cell)
    config = _merged(spec.config, overrides.get("config"))
    mix = _merged(spec.traffic, overrides.get("traffic"))
    device = device_info(spec.chips, require_chip)
    system.import_program(root)
    log(f"cell={cell} seed={seed} seconds={seconds} trace={int(trace)}; "
        f"device {device['kind']} x{device['count']}")
    log(f"compile cache {enable_compile_cache(root, device['platform'])}")
    counter = system.TraceCounter()
    reference = spec_mod.reference(root, config["reference"])

    service = system.build_service(config)
    t_w = time.perf_counter()
    warm_traces = service.warmup()
    warm = counter.delta()
    log(f"warmup {time.perf_counter() - t_w:.3f} s, {warm_traces} fleet "
        f"traces, {warm['xla_compiles']} XLA programs, {warm['cache_hits']} "
        f"of them from the persistent cache")

    tally = loops.Tally(keep=system.record)
    times = gen.open_schedule(mix, seed, seconds)
    reqs = gen.requests(mix, seed, len(times), stream=1)
    scenarios = [system.scenario(r) for r in reqs]
    pre_s = float(mix["preroll_s"])
    pre_times = gen.open_schedule(mix, seed, pre_s)
    pre_reqs = gen.requests(mix, seed, len(pre_times), stream=2)
    pre_scen = [system.scenario(r) for r in pre_reqs]
    service.start()
    pre = loops.Tally(keep=system.record)
    loops.open_loop(service, pre, pre_reqs, pre_scen, pre_times,
                    time.perf_counter())
    loops.wait_all(pre, range(len(pre_reqs)), time.perf_counter() + GRACE_S)

    gc.collect()
    gc.freeze()
    profiler_dir = Path(root) / ".bench_cache" / "trace" / cell
    with contextlib.ExitStack() as stack:
        if trace:
            stack.enter_context(_profiling(profiler_dir))
        counter.start()
        t0 = time.perf_counter()
        setup_s = t0 - t_process
        t_end = t0 + seconds
        with _annotation("bench.window" if trace else None):
            loops.open_loop(service, tally, reqs, scenarios, times, t0)
            time.sleep(max(0.0, t_end - time.perf_counter()))
        in_window = counter.delta()
    window_idx = list(range(len(tally.due)))
    loops.wait_all(tally, window_idx, t_end + GRACE_S)
    device["memory_peak_bytes"] = memory_peak_bytes()
    service.stop(drain=True)
    spans = [s for s in service.spans.snapshot() if t0 <= s.enqueue_t < t_end]
    del service
    gc.unfreeze()
    gc.collect()

    # -- what the window served
    cap = t_end + GRACE_S
    failed = [k for k in window_idx if not _ok(tally.outcome[k])]
    bad = set(failed)
    latency = [(tally.done[k] if k not in bad else cap) - tally.due[k]
               for k in window_idx]
    completed = sum(1 for k in window_idx if k not in bad
                    and t0 <= tally.done[k] <= t_end)
    late = [tally.sent[k] - tally.due[k] for k in window_idx]
    log(f"compiles inside the window: {in_window['fleet_traces']} fleet "
        f"traces, {in_window['xla_compiles']} XLA compiles")
    log(f"generator lateness p50 {1e3 * stats.percentile(late, 50):.3f} "
        f"ms, max {1e3 * max(late):.3f} ms")
    done = sorted(tally.done[k] for k in window_idx if k not in bad)
    if len(done) > 1:
        pause, at = max((b - a, a) for a, b in zip(done, done[1:]))
        log(f"longest pause between two answers {1e3 * pause:.3f} ms, "
            f"{at - t0:.3f} s into the window")
    if spans:
        s = max(spans, key=lambda s: s.latency_s - s.batch_wait_s)
        log(f"longest chunk {1e3 * (s.latency_s - s.batch_wait_s):.3f} ms "
            f"(solve {1e3 * s.solve_s:.3f} ms), started "
            f"{s.enqueue_t + s.batch_wait_s - t0:.3f} s into the window")
    log(f"peak device memory {device['memory_peak_bytes']} bytes "
        f"(fullest chip)")
    pairs: Dict[str, int] = {}
    for k in window_idx:
        req = tally.requests[k]
        key = f"{req['objective']}/{req['grid_mode']}"
        pairs[key] = pairs.get(key, 0) + 1
    log("requests per (objective, mode): " + ", ".join(
        f"{k}={v}" for k, v in sorted(pairs.items())))
    log(f"attempted {len(window_idx)}, failed {len(failed)}, completed in "
        f"the window {completed}")

    # -- the check
    checked = _check(reference, config, mix, tally, window_idx, bad, seed,
                     substitute)
    correct = all(v["value"] <= v["limit"] for v in checked.values()) \
        and bool(checked)

    ctx = Context(config=config, traffic=mix, device=device,
                  seconds=seconds, setup_s=setup_s, latency_s=latency,
                  completed_in_window=completed, spans=spans)
    result = {"correct": correct, "attempted": len(window_idx),
              "failed": len(failed)}
    if trace:
        from harness import trace as trace_mod
        t = time.perf_counter()
        reduced = trace_mod.reduce_dir(profiler_dir, "bench.window")
        shutil.rmtree(profiler_dir, ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t:.3f} s")
        ctx.trace = reduced
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        metrics = _read(root, spec.per_layer, ctx)
        result["breakdown"] = reduced.breakdown()
    else:
        metrics = _read(root, spec.end_to_end, ctx)
    result["metrics"] = metrics
    result["device"] = device
    for name, v in checked.items():
        print(f"check {name} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    result["checked"] = checked
    if keep is not None:
        keep.append(ctx)
    return result


def _read(root: Path, entries: List[Dict], ctx: Context) -> Dict:
    out = {}
    for m in entries:
        value = spec_mod.metric_reader(root, m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _check(reference, config: Dict, mix: Dict, tally: loops.Tally,
           window_idx: List[int], bad: set, seed: int,
           substitute: Optional[str]) -> Dict:
    """Compare a sample of the window's plans, drawn from the seed, with
    the reference."""
    ok = [k for k in window_idx if k not in bad]
    if not ok:
        return {}
    want = int(mix["check"]["sample"])
    rng = np.random.default_rng([int(seed), 4])
    sample = list(rng.choice(ok, size=min(want, len(ok)), replace=False))
    reqs = [tally.requests[k] for k in sample]
    t = time.perf_counter()
    if substitute == "control":
        records = reference.control(reqs, config)
    else:
        records = [tally.outcome[k] for k in sample]
    numbers = reference.compare(reqs, records, config)
    log(f"reference compared {len(reqs)} plans in "
        f"{time.perf_counter() - t:.3f} s")
    limits = config["correct"]
    return {name: {"value": numbers[name], "limit": limits[name]}
            for name in limits}
