"""Fleet planning engine: batched, sharded, cached Corollary-1 planning.

The PR-1 ``Scenario``/``Planner``/``Simulator`` triple makes ONE
device-edge pair plannable; this package makes the FLEET the unit of work:

  * :class:`~repro.fleet.batch.ScenarioBatch` — struct-of-arrays stacking
    of thousands of heterogeneous scenarios (round-trips to ``Scenario``);
  * :class:`~repro.fleet.planner.FleetPlanner` — the joint ``(rate, n_c)``
    grid for every scenario evaluated in one jitted, x64, device-sharded
    call against ANY registered planning objective;
  * :mod:`~repro.fleet.link_kernels` — the jax side of the pluggable link
    registry: one ``p_err(params, rate)`` kernel per registered model,
    dispatched per scenario via ``jax.lax.switch`` so ONE compilation
    plans batches mixing every channel family;
  * :mod:`~repro.fleet.objective_kernels` — the jax side of the pluggable
    OBJECTIVE registry (:mod:`repro.core.objectives`): batched kernels
    for the Corollary-1 bound (``jax.numpy`` port in
    :mod:`~repro.fleet.bounds_jax`), the exact burst-aware Markov-ARQ
    bound, and the vmapped empirical Monte-Carlo ridge objective;
  * :class:`~repro.fleet.cache.PlanCache` — quantised-key LRU so repeated
    or near-identical requests skip the solve (keys carry the link's
    ``(model_id, params)`` signature AND the objective's cache token);

The request stream is served by :class:`repro.serve.PlanningService`
(``python -m repro.launch.serve``), which micro-batches it through
``FleetPlanner.plan_many``.
"""
from repro.fleet.batch import ScenarioBatch
from repro.fleet.bounds_jax import corollary1_bound_jax
from repro.fleet.cache import PlanCache, objective_token, scenario_key
from repro.fleet.link_kernels import (kernel_table, kernel_table_version,
                                      register_link_kernel,
                                      unregister_link_kernel)
from repro.fleet.objective_kernels import (fleet_solve,
                                           grid_objective_builder,
                                           objective_kernel_version,
                                           register_objective_kernel,
                                           unregister_objective_kernel)
from repro.fleet.planner import (GRID_MODES, MC_IMPLS, FleetPlan,
                                 FleetPlanner, PlanRecord)
from repro.fleet.tracing import record_trace, trace_count, trace_events

__all__ = [
    "ScenarioBatch", "corollary1_bound_jax",
    "PlanCache", "scenario_key", "objective_token",
    "FleetPlan", "FleetPlanner", "PlanRecord", "GRID_MODES", "MC_IMPLS",
    "register_link_kernel", "unregister_link_kernel",
    "kernel_table", "kernel_table_version",
    "register_objective_kernel", "unregister_objective_kernel",
    "objective_kernel_version", "grid_objective_builder", "fleet_solve",
    "record_trace", "trace_count", "trace_events",
]
