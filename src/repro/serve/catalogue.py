"""Shared serving catalogue: device classes, objectives, grid modes.

Everything the planning service (:mod:`repro.serve.service`) and its
launch drivers need to turn NAMES into planning configuration:

  * link factories for the synthetic device-class catalogue
    (:data:`LINK_FACTORIES` / :data:`ALL_MODELS`) and the heterogeneous
    request stream generator :func:`synth_requests`;
  * objective factories (:data:`OBJECTIVE_FACTORIES` /
    :data:`ALL_OBJECTIVES`) and :func:`resolve_objectives`, which
    instantiates each requested objective ONCE (instance identity keys
    the jitted Monte-Carlo kernel cache);
  * :func:`resolve_grid_modes` validating grid-mode mixes;
  * :func:`default_consts`, the paper's edge-ridge bound constants.

Unknown names raise ``ValueError`` everywhere — the CLIs map that to
exit code 2 — because a typo silently falling back to a default would
skew the stream it was meant to describe.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

from repro.configs.edge_ridge import EDGE_RIDGE_PARAMS as EP
from repro.core.bounds import BoundConstants
from repro.core.objectives import (BoundObjective, MarkovARQObjective,
                                   MonteCarloObjective)
from repro.core.scenario import (ErasureLink, FadingLink, GilbertElliottLink,
                                 IdealLink, MultiDevice, Scenario,
                                 SingleDevice)
from repro.fleet import GRID_MODES
from repro.fleet.objective_kernels import pow2ceil

RATE_SET = (1.0, 1.25, 1.5, 2.0, 3.0)

#: Request kind for federated round planning (the population-level
#: workload served by ``PlanningService.submit_round``); also the leading
#: element of the federated cache key, so round entries can never alias
#: per-device plan entries.
FEDERATED_KIND = "federated_round"


def default_consts() -> BoundConstants:
    """The paper's edge-ridge bound constants (Sec. 5)."""
    return BoundConstants(L=EP.L, c=EP.c, M=EP.M, M_G=EP.M_G, D=1.0,
                          alpha=EP.alpha)


def _draw_ideal(rng) -> IdealLink:
    return IdealLink(rates=RATE_SET)


def _draw_erasure(rng) -> ErasureLink:
    return ErasureLink(beta=float(rng.uniform(0.05, 1.5)),
                       p_base=float(rng.uniform(0.0, 0.5)), rates=RATE_SET)


def _draw_fading(rng) -> FadingLink:
    return FadingLink(snr=float(rng.uniform(2.0, 50.0)), rates=RATE_SET)


def _draw_gilbert_elliott(rng) -> GilbertElliottLink:
    p_good = float(rng.uniform(0.0, 0.2))
    return GilbertElliottLink(
        p_gb=float(rng.uniform(0.01, 0.3)),
        p_bg=float(rng.uniform(0.2, 0.9)),
        p_good=p_good,
        p_bad=float(rng.uniform(p_good, 0.9)),
        beta=float(rng.uniform(0.05, 1.0)), rates=RATE_SET)


#: Synthetic device-class link factories, by model name (--models values).
LINK_FACTORIES = {
    "ideal": _draw_ideal,
    "erasure": _draw_erasure,
    "fading": _draw_fading,
    "gilbert_elliott": _draw_gilbert_elliott,
}

#: The full mixed-model catalogue (every built-in channel family).
ALL_MODELS = tuple(LINK_FACTORIES)


def make_montecarlo_objective(min_updates: int = 0, *, crn: bool = False,
                              seed_stream: str = "fold_in",
                              coarse_seeds=None, refine_rates=None,
                              coarse_strides=None, fine_radius=None,
                              coarse_updates=None,
                              dataset_rows: int = 256) -> MonteCarloObjective:
    """Deterministic ridge task (the canonical generator, 8 features) of
    ``dataset_rows`` rows for Monte-Carlo objective serving: 256 by
    default, the paper's 18,576 (arXiv:1906.04488 Sec. V) for a device
    that holds the whole set.  ``min_updates`` floors the
    batched kernel's padded scan length so a service compiles ONE scan
    shape for every stream below the floor.  The keyword options expose
    the estimator/schedule knobs (common random numbers, RNG stream
    derivation, coarse seed counts, rate pruning, the multi-level stride
    schedule, the fine-window radius and the coarse-pass horizon cap) —
    they flow into the objective's ``cache_token``, so
    differently-configured services never alias cache entries."""
    from repro.data.synthetic import make_regression_dataset

    X, y, _ = make_regression_dataset(n=int(dataset_rows), d=8, seed=0)
    return MonteCarloObjective(X=X, y=y, n_runs=2, alpha=1e-3, seed=0,
                               min_updates=min_updates, crn=crn,
                               seed_stream=seed_stream,
                               coarse_seeds=coarse_seeds,
                               refine_rates=refine_rates,
                               coarse_strides=coarse_strides,
                               fine_radius=fine_radius,
                               coarse_updates=coarse_updates)


#: Planning-objective factories, by registry id (--objective values).
OBJECTIVE_FACTORIES = {
    "corollary1": BoundObjective,
    "markov_arq": MarkovARQObjective,
    "montecarlo": make_montecarlo_objective,
}

#: The full mixed-objective catalogue (every built-in objective).
ALL_OBJECTIVES = tuple(OBJECTIVE_FACTORIES)


def mc_update_floor(n_max: int) -> int:
    """The padded-scan-length floor covering every stream
    :func:`synth_requests` can draw under ``n_max``: update slots number
    ``floor(T / tau_p)`` with ``T < 3 N <= 3 n_max`` and
    ``tau_p >= 0.5``, rounded to the kernel's power-of-two padding."""
    return pow2ceil(max(1, int(6 * n_max)))


def resolve_objectives(spec, mc_min_updates: int = 0,
                       mc_options: Dict[str, Any] = None) -> Dict[str, Any]:
    """Instantiate the requested objectives ONCE each (instance identity
    keys the jitted Monte-Carlo kernel cache).  ``spec`` is "all", a
    comma-separated string, or a sequence of registry ids; unknown names
    raise ``ValueError`` with the available ids.  ``mc_min_updates``
    pins the Monte-Carlo scan-length floor (serving; see
    :func:`mc_update_floor`) and ``mc_options`` forwards estimator /
    schedule keywords to :func:`make_montecarlo_objective` (``crn``,
    ``seed_stream``, ``coarse_seeds``, ``refine_rates``,
    ``coarse_strides``, ``fine_radius``, ``coarse_updates``,
    ``dataset_rows``).
    """
    if spec == "all":
        names: Sequence[str] = ALL_OBJECTIVES
    elif isinstance(spec, str):
        names = tuple(s.strip() for s in spec.split(",") if s.strip())
    else:
        names = tuple(spec)
    unknown = [o for o in names if o not in OBJECTIVE_FACTORIES]
    if unknown:
        raise ValueError(
            f"unregistered planning objective(s) {unknown}; "
            f"available: {sorted(OBJECTIVE_FACTORIES)}")
    if not names:
        raise ValueError("no planning objective requested; "
                         f"available: {sorted(OBJECTIVE_FACTORIES)}")
    out: Dict[str, Any] = {}
    for name in names:
        if name == "montecarlo":
            out[name] = make_montecarlo_objective(mc_min_updates,
                                                  **(mc_options or {}))
        else:
            out[name] = OBJECTIVE_FACTORIES[name]()
    return out


def resolve_grid_modes(spec) -> Sequence[str]:
    """Validate a grid-mode mix: "all", one mode, or a comma list of
    :data:`repro.fleet.GRID_MODES`.  Unknown names raise ``ValueError``
    (the CLIs map that to exit code 2) — serving policies mix refined
    bound traffic with dense calibration traffic, and a typo silently
    falling back to one mode would skew both streams."""
    if spec == "all":
        return GRID_MODES
    names = (tuple(s.strip() for s in spec.split(",") if s.strip())
             if isinstance(spec, str) else tuple(spec))
    unknown = [m for m in names if m not in GRID_MODES]
    if unknown:
        raise ValueError(
            f"unknown grid mode(s) {unknown}; available: {list(GRID_MODES)}")
    if not names:
        raise ValueError(f"no grid mode requested; "
                         f"available: {list(GRID_MODES)}")
    return names


def parse_models(spec: str) -> Sequence[str]:
    """"all" or a comma-separated subset of :data:`ALL_MODELS` (unknown
    names are rejected downstream by :func:`synth_requests`)."""
    if spec == "all":
        return ALL_MODELS
    return tuple(m.strip() for m in spec.split(",") if m.strip())


def synth_requests(n: int, *, seed: int = 0, dup_frac: float = 0.5,
                   n_classes: int = 64,
                   models: Sequence[str] = ("erasure",),
                   n_max: int = 32768) -> List[Scenario]:
    """Heterogeneous request stream over a catalogue of device classes.

    ``dup_frac`` of the requests resample a previously seen class with
    tiny parameter jitter (below the cache's quantisation step), the rest
    draw a fresh class — so the achievable cache hit-rate is ~``dup_frac``.
    Each fresh class draws its link from one of ``models`` (keys of
    :data:`LINK_FACTORIES`) uniformly, so ``models=ALL_MODELS`` yields a
    stream mixing every channel family.  ``n_max`` caps the drawn dataset
    sizes — Monte-Carlo serving simulates the update timeline, so its
    streams use a small cap to bound the scan length.
    """
    unknown = [m for m in models if m not in LINK_FACTORIES]
    if unknown:
        raise ValueError(
            f"unknown link model name(s) {unknown}; "
            f"available: {sorted(LINK_FACTORIES)}")
    if n_max <= 256:
        raise ValueError(f"n_max must be > 256, got {n_max}")
    rng = np.random.default_rng(seed)
    classes: List[dict] = []

    def fresh_class() -> dict:
        N = int(rng.integers(256, n_max))
        return dict(
            N=N, T=float(rng.uniform(1.1, 3.0)) * N,
            n_o=float(rng.uniform(1.0, 1000.0)),
            tau_p=float(rng.choice([0.5, 1.0, 2.0])),
            link=LINK_FACTORIES[models[int(rng.integers(len(models)))]](rng),
            D=int(rng.choice([1, 1, 2, 4, 8])))

    out: List[Scenario] = []
    for _ in range(n):
        if classes and rng.random() < dup_frac:
            c = classes[int(rng.integers(len(classes)))]
        else:
            c = fresh_class()
            if len(classes) < n_classes:
                classes.append(c)
        jitter = 1.0 + rng.uniform(-1e-5, 1e-5)   # below quantisation step
        out.append(Scenario(
            N=c["N"], T=c["T"] * jitter, n_o=c["n_o"], tau_p=c["tau_p"],
            link=c["link"],
            topology=MultiDevice(c["D"]) if c["D"] > 1 else SingleDevice()))
    return out


def synth_population(n_devices: int, *, seed: int = 0,
                     models: Sequence[str] = ALL_MODELS,
                     n_max: int = 4096, deadline_frac: float = 1.6):
    """Synthetic federated-round candidate population.

    Draws ``n_devices`` heterogeneous devices (dataset size, overhead,
    update period and a link from ``models`` — Gilbert-Elliott rows are
    the natural stragglers) and one SHARED round deadline
    ``deadline_frac * median(N)``; every scenario carries the deadline as
    its own ``T``, so :meth:`RoundPlanner.resolve_deadline` (the
    population minimum) recovers it.  Returns ``(population, deadline)``.
    Unknown model names raise ``ValueError`` (CLIs map that to exit 2).
    """
    unknown = [m for m in models if m not in LINK_FACTORIES]
    if unknown:
        raise ValueError(
            f"unknown link model name(s) {unknown}; "
            f"available: {sorted(LINK_FACTORIES)}")
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    if n_max <= 256:
        raise ValueError(f"n_max must be > 256, got {n_max}")
    rng = np.random.default_rng(seed)
    Ns = [int(rng.integers(256, n_max)) for _ in range(n_devices)]
    deadline = float(deadline_frac) * float(np.median(Ns))
    population = [
        Scenario(N=N, T=deadline, n_o=float(rng.uniform(1.0, 1000.0)),
                 tau_p=float(rng.choice([0.5, 1.0, 2.0])),
                 link=LINK_FACTORIES[
                     models[int(rng.integers(len(models)))]](rng))
        for N in Ns]
    return population, deadline
