"""Always-on planning service over the fleet engine.

The serving subsystem: a long-lived :class:`PlanningService` that
ingests planning requests from any thread, forms continuous
size-or-deadline micro-batches grouped by (objective, grid mode), pads
them to power-of-two buckets whose executables were AOT-compiled during
warmup (zero post-warmup ``jax.jit`` traces), routes un-annotated
requests through a pluggable admission policy, and re-plans live
sessions when their observed channel drifts away from what the cached
plan priced.  See ``README.md`` ("Serving") for the architecture sketch.
"""
from repro.serve import export
from repro.serve.batcher import (MicroBatcher, PlanRequest, QueueFull,
                                 group_requests)
from repro.serve.catalogue import (ALL_MODELS, ALL_OBJECTIVES,
                                   FEDERATED_KIND, LINK_FACTORIES,
                                   OBJECTIVE_FACTORIES, RATE_SET,
                                   default_consts, mc_update_floor,
                                   parse_models, resolve_grid_modes,
                                   resolve_objectives, synth_population,
                                   synth_requests)
from repro.serve.policy import (AdmissionDecision, LinkAwarePolicy,
                                LoadSheddingPolicy, PolicySpec,
                                StaticPolicy, policy_spec,
                                register_policy, registered_policies,
                                unregister_policy)
from repro.serve.resilience import (BREAKER_STATES, FALLBACK_LEVELS,
                                    HEALTH_STATES, CircuitBreaker,
                                    DegradationExhausted, HealthReport,
                                    RequestShed, ResilienceManager,
                                    RetryPolicy, SolveTimeEstimator)
from repro.serve.service import PlanningService, ServiceConfig
from repro.serve.sessions import Session, SessionTracker, reestimate_link
from repro.serve.stats import FederatedRecorder, ServiceStats, StatsRecorder

__all__ = [
    "ALL_MODELS", "ALL_OBJECTIVES", "AdmissionDecision",
    "BREAKER_STATES", "CircuitBreaker", "DegradationExhausted",
    "FALLBACK_LEVELS", "FEDERATED_KIND",
    "FederatedRecorder", "HEALTH_STATES", "HealthReport",
    "LINK_FACTORIES",
    "LinkAwarePolicy", "LoadSheddingPolicy", "MicroBatcher",
    "OBJECTIVE_FACTORIES",
    "PlanRequest", "PlanningService", "PolicySpec", "QueueFull",
    "RATE_SET", "RequestShed", "ResilienceManager", "RetryPolicy",
    "ServiceConfig", "ServiceStats", "Session", "SessionTracker",
    "SolveTimeEstimator", "StaticPolicy", "StatsRecorder",
    "default_consts", "export", "group_requests",
    "mc_update_floor", "parse_models", "policy_spec",
    "reestimate_link", "register_policy", "registered_policies",
    "resolve_grid_modes", "resolve_objectives", "synth_population",
    "synth_requests", "unregister_policy",
]
