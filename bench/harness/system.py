"""The system under test: the planning service, built from a
configuration file's ``service`` and ``bound_constants`` sections.

This is the one module that imports the program (``repro``, from the
checkout's ``src``), and it takes from it only the service, its spans
and its trace counters.
"""
from __future__ import annotations

import sys
from pathlib import Path
from typing import Dict


class ProgramMissing(Exception):
    """The checkout holds no planning service to run."""


def import_program(root: Path):
    src = str(Path(root) / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    try:
        import repro.serve  # noqa: F401
    except ImportError as e:
        raise ProgramMissing(f"cannot import the planning service from "
                             f"{src}: {e}") from None


def build_service(config: Dict):
    """A warmed-up-able ``PlanningService`` as the configuration states."""
    from repro.core.bounds import BoundConstants
    from repro.serve import PlanningService, ServiceConfig

    kwargs = {k: tuple(v) if isinstance(v, list) else v
              for k, v in config["service"].items()}
    return PlanningService(ServiceConfig(**kwargs),
                           consts=BoundConstants(**config["bound_constants"]))


def scenario(req: Dict):
    """The program's ``Scenario`` for one generated request."""
    from repro.core.scenario import (ErasureLink, FadingLink,
                                     GilbertElliottLink, IdealLink,
                                     MultiDevice, Scenario, SingleDevice)

    link = dict(req["link"])
    family = link.pop("family")
    rates = tuple(req["rates"])
    cls = {"ideal": IdealLink, "erasure": ErasureLink, "fading": FadingLink,
           "gilbert_elliott": GilbertElliottLink}[family]
    topology = MultiDevice(req["D"]) if req["D"] > 1 else SingleDevice()
    return Scenario(N=req["N"], T=req["T"], n_o=req["n_o"],
                    tau_p=req["tau_p"], link=cls(rates=rates, **link),
                    topology=topology)


def record(plan) -> Dict:
    """A served ``PlanRecord`` as the plain dict the references read."""
    return {"n_c": int(plan.n_c), "rate": float(plan.rate),
            "value": float(plan.bound_value), "fallback": str(plan.fallback)}


class TraceCounter:
    """Jitted fleet-kernel traces, and XLA programs compiled or loaded
    from the persistent compilation cache, seen since ``start``."""

    def __init__(self):
        import jax
        from repro.fleet.tracing import trace_count
        self._trace_count = trace_count
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        self.start()

    def _on_duration(self, event, duration, **_):
        # recorded around every backend compile, cache hits included
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def start(self):
        self._traces0 = self._trace_count()
        self._compiles0 = self.compiles
        self._hits0 = self.cache_hits

    def delta(self) -> Dict[str, int]:
        return {"fleet_traces": self._trace_count() - self._traces0,
                "xla_compiles": self.compiles - self._compiles0,
                "cache_hits": self.cache_hits - self._hits0}
