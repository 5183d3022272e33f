"""The four-chip Monte-Carlo deployment (``bench/configs/plan-montecarlo-x4
.json``) against its plain reference (``bench/reference/montecarlo.py``),
at a tiny size on four virtual CPU devices.

The served objective's fixed settings are the configuration's; plans
served with ``shard=True`` meet the reference within the limits the
configuration states, with the scan engine and with the interpreted
Pallas engine; and sharded plans are bitwise those of one device.
"""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
CONFIG = json.loads(
    (ROOT / "bench" / "configs" / "plan-montecarlo-x4.json").read_text())


def _load_reference():
    """The reference module, loaded by path with the bench directory on
    the path (it imports ``reference.common``)."""
    bench = str(ROOT / "bench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    spec = importlib.util.spec_from_file_location(
        "bench_reference_montecarlo",
        ROOT / "bench" / "reference" / "montecarlo.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_served_objective_is_the_configurations():
    from repro.serve import PlanningService, ServiceConfig

    cfg = {k: tuple(v) if isinstance(v, list) else v
           for k, v in CONFIG["service"].items()}
    svc = PlanningService(ServiceConfig(**cfg))
    objective = svc.objectives["montecarlo"]
    want = CONFIG["objective"]
    data = want["dataset"]
    X, y = _load_reference().make_dataset(data["rows"], data["features"],
                                          data["seed"])
    np.testing.assert_array_equal(np.asarray(objective.X), X)
    np.testing.assert_array_equal(np.asarray(objective.y), y)
    assert (objective.n_runs, objective.alpha, objective.lam,
            objective.seed, objective.seed_stream, objective.grid_points,
            objective.crn) == (want["n_runs"], want["alpha"], want["lam"],
                               want["seed"], want["seed_stream"],
                               want["grid_points"], cfg["mc_crn"])


_SCRIPT = """
import json, sys
import numpy as np, jax
assert jax.device_count() == 4, jax.devices()
from repro.core.bounds import BoundConstants
from repro.fleet import FleetPlanner
from repro.serve import PlanningService, ServiceConfig
sys.path.insert(0, "bench")
from harness import system, traffic
import test_mc_x4_reference as t

config, mix = t.tiny_config(), t.tiny_traffic()
reqs = traffic.requests(mix, 11, 16)
scenarios = [system.scenario(r) for r in reqs]
ref = t._load_reference()
cfg = {k: tuple(v) if isinstance(v, list) else v
       for k, v in config["service"].items()}
consts = BoundConstants(**config["bound_constants"])
for impl in ("scan", "pallas"):
    svc = PlanningService(ServiceConfig(**dict(cfg, mc_impl=impl)),
                          consts=consts)
    svc.warmup()
    with svc:
        futures = [svc.submit(sc, objective="montecarlo", grid_mode="refine")
                   for sc in scenarios]
        records = [system.record(f.result(timeout=300)) for f in futures]
    assert all(r["fallback"] == "full" for r in records), records
    numbers = ref.compare(reqs, records, config)
    for name, limit in config["correct"].items():
        assert numbers[name] <= limit, (impl, name, numbers)
    objective = svc.objectives["montecarlo"]
    plans = {}
    for shard in (True, False):
        planner = FleetPlanner(grid_size=cfg["grid_size"], shard=shard,
                               pow2_refine_widths=True, mc_impl=impl)
        plans[shard] = planner.plan_batch(scenarios, consts,
                                          objective=objective,
                                          grid_mode="refine")
    for field in ("n_c", "rate", "bound_value"):
        np.testing.assert_array_equal(getattr(plans[True], field),
                                      getattr(plans[False], field))
    print(impl, json.dumps(numbers))
print("MC-X4-OK")
"""


def tiny_config():
    """The configuration at a tiny size: a 16-point grid (the objective
    keeps its own 12 points), buckets of 8 and 16, ``n_max`` 320 (the
    service's warmup draws requests below ``n_max``, which must exceed
    256; 320 keeps the padded timeline at 2,048 slots)."""
    config = json.loads(json.dumps(CONFIG))
    config["service"].update(grid_size=16, batch_buckets=[8, 16],
                             n_max=320)
    return config


def tiny_traffic():
    """The cell's request mix with ``N`` below the tiny ``n_max``."""
    mix = json.loads((ROOT / "bench" / "traffic" / "mc-x4-open.json")
                     .read_text())
    mix["requests"]["N"] = [256, 320]
    return mix


def test_sharded_plans_meet_the_reference_and_one_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), str(ROOT / "tests"),
                    os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MC-X4-OK" in out.stdout, out.stdout


@pytest.mark.parametrize("precision", ["float32 SGD on a float64 timeline",
                                       "bfloat16",
                                       "float32 SGD on a float32 timeline"])
def test_reference_lane_values_do_not_depend_on_the_batch(precision):
    """A request's values are the same alone or simulated beside others
    (lanes past their deadline are skipped exactly)."""
    sys.path.insert(0, str(ROOT / "bench"))
    from harness import traffic
    ref = _load_reference()
    reqs = traffic.requests(tiny_traffic(), 5, 3)
    together = ref.evaluate(reqs, tiny_config(), precision)
    for req, (grid, rates, vals) in zip(reqs, together):
        ((g1, r1, v1),) = ref.evaluate([req], tiny_config(), precision)
        np.testing.assert_array_equal(grid, g1)
        np.testing.assert_array_equal(vals, v1)


def test_reference_reads_the_sgd_and_timeline_precisions():
    import ml_dtypes
    ref = _load_reference()
    assert ref.dtypes(CONFIG["precision"]) == (np.float32, np.float64)
    assert ref.dtypes(CONFIG["control_precision"]) == (ml_dtypes.bfloat16,
                                                       np.float64)
    assert ref.dtypes("float32 SGD on a float32 timeline") == (np.float32,
                                                              np.float32)
    for bad in ("float16", "float32 SGD on a float16 timeline"):
        with pytest.raises(ValueError):
            ref.dtypes(bad)
