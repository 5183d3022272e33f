"""Jitted batched objective kernels, one per registered planning objective.

The jax side of the pluggable objective registry
(:mod:`repro.core.objectives`), mirroring how
:mod:`repro.fleet.link_kernels` is the jax side of the link registry.  A
kernel BUILDER is registered per ``objective_id``; ``fleet_solve`` turns an
objective instance into a host-level solver

    ``solve(arrays, consts, shard, batch) -> dict of (S,)-leading arrays``

that evaluates the objective over the joint ``(rate, n_c)`` grid of every
scenario in one jitted x64 call and reduces it with the canonical
rate-major argmin tie-breaking — the exact layout the scalar
``ObjectivePlanner`` uses, so batched and scalar plans coincide.

Built-in kernels:

  * ``corollary1`` — the Corollary-1 bound at the stationary link-induced
    effective overhead (the pre-registry fleet solve, op-for-op);
  * ``markov_arq`` — the same bound, but Gilbert-Elliott scenarios get
    their expected block duration from the EXACT per-(rate, state)
    Markov-reward linear solve (closed-form 2x2, vectorised over the
    batch) instead of the stationary-loss approximation; the degenerate
    ``p_good == p_bad`` rows keep the stationary division form so the
    reduction to ``corollary1`` stays bitwise;
  * ``montecarlo`` — the empirical ridge objective: the scalar seed loop
    of ``average_final_loss`` vmapped over scenarios x rates x grid
    points x seeds over a shared padded update timeline.  RNG streams
    (per-run keys via ``seed_stream``, per-step splits, per-update
    sample draws) replicate the scalar path exactly, so fleet plans match
    the scalar Monte-Carlo planner seed-for-seed; training math runs in
    float32 (like the scalar path) while the timeline/overhead arithmetic
    stays float64.  Three simulation engines share that contract: the
    reference ``lax.scan`` (per-slot RNG in the loop), the table-driven
    CRN scan (``objective.crn=True``: slab-precomputed index/mask tables
    + shared per-slot uniforms + affine-fused update), and the pallas
    slab kernel (``mc_impl="pallas"``,
    :func:`repro.kernels.mc_ridge.mc_ridge_slab`, interpreted on the CPU)
    which consumes the same tables bitwise.

Registering a kernel for a custom grid objective needs only its value
function (see README "Planning objectives")::

    def _my_values(g, N, T, n_o_eff, tau_p, sigma, e0, contraction):
        return (g + n_o_eff) / g          # expected time per sample

    register_objective_kernel("throughput",
                              grid_objective_builder(_my_values))

Registration bumps :func:`objective_kernel_version`; jitted solves are
additionally keyed on the LINK kernel-table version, so late link plugins
retrace rather than stale-dispatch.
"""
from __future__ import annotations

import math
from functools import lru_cache, partial
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.links import P_ERR_MAX, GilbertElliottLink
from repro.core.objectives import objective_spec
from repro.core.pipeline import ridge_dot, ridge_grad_sample, ridge_losses
from repro.fleet.bounds_jax import corollary1_bound_jax
from repro.fleet.link_kernels import kernel_table, kernel_table_version
from repro.fleet.tracing import record_trace
from repro.obs.runtime import count, span

_BUILDERS: Dict[str, Callable] = {}
_VERSION = 0


def register_objective_kernel(objective_id: str, builder: Callable) -> None:
    """Register the batched kernel builder for ``objective_id``.

    ``builder(objective)`` must return a host-level callable
    ``solve(arrays, consts, shard, batch)``.  The objective must already
    be registered with :func:`repro.core.objectives.register_objective`.
    """
    global _VERSION
    objective_spec(objective_id)  # KeyError with guidance if no spec
    prior = _BUILDERS.get(objective_id)
    if prior is builder:
        return  # idempotent re-registration: no version bump
    if prior is not None:
        raise ValueError(
            f"objective {objective_id!r} already has a registered kernel")
    _BUILDERS[objective_id] = builder
    _VERSION += 1


def unregister_objective_kernel(objective_id: str) -> None:
    """Remove a kernel builder (plugin teardown / tests).  No-op if absent."""
    global _VERSION
    if _BUILDERS.pop(objective_id, None) is not None:
        _VERSION += 1


def objective_kernel_version() -> int:
    """Monotone counter bumped on (un)registration."""
    return _VERSION


def fleet_solve(objective) -> Callable:
    """The batched solver for an objective instance (KeyError if none)."""
    objective_id = getattr(objective, "objective_id", None)
    builder = _BUILDERS.get(objective_id)
    if builder is None:
        raise KeyError(
            f"objective {objective_id!r} has no registered fleet kernel; "
            "call repro.fleet.objective_kernels.register_objective_kernel "
            f"(known: {sorted(_BUILDERS)})")
    return builder(objective)


def _count_in(n_in: int) -> None:
    """One jitted call with ``n_in`` host-to-device copies, on the open
    chunk record.  A copy is one array however many devices it is laid
    over: the grid solves copy each argument, the Monte-Carlo solve its
    one packed buffer."""
    count("dispatches")
    count("h2d_arrays", n_in)


def _fetch(out: dict) -> dict:
    """Wait on a jitted call's results and copy them back to host
    arrays, as two leaves: one transfer per array.

    The federated round fetches this way.  The grid and Monte-Carlo
    solves pack their outputs into one buffer (:func:`_fetch_packed`)."""
    with span("planner.device_wait"):
        jax.block_until_ready(out)
    with span("planner.fetch"):
        res = {k: np.asarray(v) for k, v in out.items()}
        count("d2h_arrays", len(res))
    return res


def _pack(out: dict, xp=jnp):
    """Every ``(S, ...)`` array as float64 columns of one ``(S, K)``
    buffer, and the static layout ``((key, start, stop, dtype, trailing
    shape), ...)`` that :func:`_unpack` cuts it back with.

    With ``xp=jnp`` it runs while tracing, on a solve's outputs; with
    ``xp=np`` on the host, on a solve's arguments.  Integers and bools
    round-trip exactly: block sizes (at most the service's ``n_max``),
    link-family ids and indices (below the grid width) are below 2**24,
    exact even in the TPU's emulated float64.  Concatenating along axis 1
    keeps a scenario-sharded batch sharded."""
    S = next(iter(out.values())).shape[0]
    cols, layout, start = [], [], 0
    for key, v in out.items():
        trailing = tuple(v.shape[1:])
        width = math.prod(trailing)
        cols.append(v.reshape(S, width).astype(np.float64))
        layout.append((key, start, start + width, np.dtype(v.dtype),
                       trailing))
        start += width
    return xp.concatenate(cols, axis=1), tuple(layout)


def _unpack(buf, layout) -> dict:
    """The dict :func:`_pack` packed: each column block cast back to its
    dtype and trailing shape (host or traced arrays alike)."""
    return {key: buf[:, a:b].astype(dtype).reshape((-1,) + trailing)
            for key, a, b, dtype, trailing in layout}


def _fetch_packed(buf, layout, rows=None) -> dict:
    """:func:`_fetch` for a packed solve: wait, then ONE copy back, cut
    into the same dict of host arrays (keys, dtypes, shapes, values).
    ``rows``, where given, reorders the fetched rows before the cut."""
    with span("planner.device_wait"):
        buf.block_until_ready()
    with span("planner.fetch"):
        host = np.asarray(buf)
        count("d2h_arrays")
        if rows is not None:
            host = host[rows]
        res = _unpack(host, layout)
    return res


def _fleet_sharding() -> NamedSharding:
    """The scenario axis laid over every local device."""
    mesh = Mesh(np.asarray(jax.local_devices()), ("fleet",))
    return NamedSharding(mesh, P("fleet"))


def _maybe_shard(arrays: dict, S: int) -> dict:
    """Lay the batch out across local devices over the scenario axis."""
    n_dev = len(jax.local_devices())
    if n_dev <= 1 or S % n_dev != 0:
        return arrays
    sharding = _fleet_sharding()
    return {k: jax.device_put(v, sharding) for k, v in arrays.items()}


def _switch_p_err(branches, link_model_id, link_params, rates):
    """Per-scenario link dispatch: lax.switch over the registered p_err
    kernels, vmapped over the batch (under vmap every branch runs and the
    result is selected — fine: p_err is O(R), the objective is O(R G))."""

    def p_err_one(mid, params, rate_row):
        return jax.lax.switch(mid, branches, params, rate_row)

    return jax.vmap(p_err_one)(link_model_id, link_params, rates)  # (S, R)


def _reduce_joint_argmin(vals, n_o_eff, p, N, T, rates, rate_mask, grid):
    """Two-stage argmin == flat rate-major argmin (ties: first grid point
    within a rate, then first rate), matching the scalar
    ``repro.core.scenario._finish_plan`` exactly.  Shared by every
    objective kernel so tie-breaking can never drift between objectives.

    ``grid`` is the per-scenario ``(S, G)`` grid shared across rates, or
    a per-rate ``(S, R, G)`` window grid — the fine pass of the
    coarse->fine solve hands every rate its own bracket, whose ascending
    dense-index order keeps the within-rate "first grid point" tie-break
    identical to the single-pass dense reduction.  Per-rate grids
    additionally return the chosen rate's window row (``sel_grid``), and
    every reduction reports the per-rate argmin lanes (``gi_per_rate``) —
    the coarse pass's output that the fine pass brackets around.
    """
    S = rates.shape[0]
    masked = jnp.where(rate_mask[:, :, None], vals, jnp.inf)
    gi_per_rate = jnp.argmin(masked, axis=2)                   # (S, R)
    ri = jnp.argmin(jnp.min(masked, axis=2), axis=1)           # (S,)
    s = jnp.arange(S)
    gi = gi_per_rate[s, ri]

    n_c = grid[s, gi] if grid.ndim == 2 else grid[s, ri, gi]
    best_no = n_o_eff[s, ri, gi]
    best_dur = n_c.astype(T.dtype) + best_no
    delivered = jnp.minimum(jnp.floor(T / best_dur) * n_c, N)
    out = {
        "n_c": n_c,
        "rate": rates[s, ri],
        "bound_value": vals[s, ri, gi],
        "p_err": p[s, ri],
        "n_o_eff": best_no,
        "full_transfer": delivered >= N,
        "bound_grid": vals[s, ri],
        "gi_per_rate": gi_per_rate,
        # per-rate minima: what the coarse pass ranks rates by when the
        # fine pass prunes to the top-K rates (RefineHints.refine_rates)
        "val_per_rate": jnp.min(masked, axis=2),
    }
    if grid.ndim == 3:
        out["sel_grid"] = grid[s, ri]
    return out


# ---------------------------------------------------------------------------
# grid objectives: any value function of the (S, R, G) effective overhead
# ---------------------------------------------------------------------------


_GE_MODEL_ID = GilbertElliottLink.model_id


def _ge_exact_arq_inflation(link_params, rates):
    """(S, R) exact burst-aware ARQ inflation from packed GE parameters —
    the jax mirror of ``GilbertElliottLink.exact_arq_inflation`` (same op
    order).  Rows of other models produce garbage here; callers mask."""
    beta, p_good, p_bad, p_gb, p_bg = (
        link_params[:, k:k + 1] for k in range(5))            # (S, 1)
    decay = jnp.exp(-beta * jnp.maximum(rates - 1.0, 0.0))
    p_g = jnp.minimum(1.0 - (1.0 - p_good) * decay, P_ERR_MAX)
    p_b = jnp.minimum(1.0 - (1.0 - p_bad) * decay, P_ERR_MAX)
    den_g = 1.0 - p_g * (1.0 - p_gb)
    den_b = 1.0 - p_b * (1.0 - p_bg)
    det = den_g * den_b - p_g * p_gb * p_b * p_bg
    t_g = (den_b + p_g * p_gb) / det
    t_b = (den_g + p_b * p_bg) / det
    pi_b = p_gb / (p_gb + p_bg)
    return t_g + pi_b * (t_b - t_g)


def _corollary1_values(g, N, T, n_o_eff, tau_p, sigma, e0, contraction):
    """The Corollary-1 bound as a grid-objective value function."""
    return corollary1_bound_jax(g, N=N, T=T, n_o=n_o_eff, tau_p=tau_p,
                                sigma=sigma, e0=e0, contraction=contraction)


def _layout_key(rates, grid, width=None) -> tuple:
    """What fixes a grid solve's packed layout: the rate count, the
    grid's trailing shape and dtype, and the fine pass's window width.
    The same key from the traced arguments and from the host's."""
    return (rates.shape[1], tuple(grid.shape[1:]), np.dtype(grid.dtype),
            width)


def _build_grid_solve(branches, value_fn, exact_arq: bool):
    """Jit a grid-objective solve closed over a link-kernel branch table.

    Shapes: per-scenario vectors (S,), rate matrix (S, R), grid (S, G);
    output per-scenario reductions.  ``exact_arq`` swaps the stationary
    ARQ inflation for the exact Markov-reward block time on
    non-degenerate Gilbert-Elliott rows.

    Returns ``(grid_solve, grid_solve_fine, layout)``: the single-pass
    solve over a ``(S, G)`` / per-rate ``(S, R, G)`` grid (the dense
    solve and the coarse pass), the FUSED fine pass of the coarse->fine
    solve, which builds the per-rate bracket+tail windows ON DEVICE from
    ``(centers, tail_start)`` — mirroring
    :func:`repro.core.planner.refine_window_bounds` op-for-op — so the
    serving hot path never materialises or transfers ``(S, R, W)``
    window arrays from the host, and ``layout(rates, grid, width=None)``.

    Both jitted functions end in :func:`_pack`: they return ONE float64
    ``(S, K)`` device buffer holding every output of
    :func:`_reduce_joint_argmin` as column blocks in its key order —
    ``n_c``, ``rate``, ``bound_value``, ``p_err``, ``n_o_eff``,
    ``full_transfer`` one column each, then ``bound_grid`` (G or W
    columns), ``gi_per_rate`` and ``val_per_rate`` (R each) and, on a
    per-rate grid, ``sel_grid`` — so the host pays one transfer per solve
    instead of nine.  The layout is recorded while tracing, keyed by
    :func:`_layout_key`; ``layout`` looks it up for a call's host
    arguments (its trace has run by the time the call returns).  The
    functions' names are what the profiler shows:
    ``PjitFunction(grid_solve)`` on the host and the ``jit_grid_solve``
    module on the device.
    """
    layouts = {}

    def packed(out, rates, grid, width=None):
        buf, layouts[_layout_key(rates, grid, width)] = _pack(out)
        return buf

    def solve_grid(N, T, union_no, tau_p, rates, rate_mask, grid,
                   link_model_id, link_params, sigma, e0, contraction):
        # runs once per TRACE (both jitted solves funnel through this
        # body) — the serving layer's retrace audit
        record_trace(("grid", int(exact_arq)) + tuple(grid.shape))
        rate = rates[:, :, None]                                   # (S, R, 1)
        # (S, G) shared grid broadcasts over rates; a (S, R, G) window
        # grid (the coarse->fine pass) evaluates per-rate points
        g = (grid[:, None, :] if grid.ndim == 2 else grid).astype(T.dtype)

        p = _switch_p_err(branches, link_model_id, link_params, rates)
        p3 = p[:, :, None]

        # expected_block_time under stop-and-wait ARQ, batched
        raw = g / rate + union_no[:, None, None]                   # (S, R, G)
        dur = raw / (1.0 - p3)
        if exact_arq:
            infl = _ge_exact_arq_inflation(link_params, rates)     # (S, R)
            exact = ((link_model_id == _GE_MODEL_ID)
                     & (link_params[:, 1] != link_params[:, 2]))
            dur = jnp.where(exact[:, None, None],
                            raw * infl[:, :, None], dur)
        n_o_eff = dur - g

        vals = value_fn(
            g, N[:, None, None].astype(T.dtype), T[:, None, None],
            n_o_eff, tau_p[:, None, None], sigma, e0, contraction)

        return _reduce_joint_argmin(vals, n_o_eff, p, N, T, rates,
                                    rate_mask, grid)

    @jax.jit
    def grid_solve(N, T, union_no, tau_p, rates, rate_mask, grid,
                   link_model_id, link_params, sigma, e0, contraction):
        return packed(solve_grid(N, T, union_no, tau_p, rates, rate_mask,
                                 grid, link_model_id, link_params, sigma,
                                 e0, contraction), rates, grid)

    @partial(jax.jit, static_argnames=("stride", "width"))
    def grid_solve_fine(N, T, union_no, tau_p, rates, rate_mask, grid,
                        link_model_id, link_params, sigma, e0, contraction,
                        centers, tail_start, *, stride, width):
        S, G = grid.shape
        # jnp mirror of repro.core.planner.refine_window_bounds (+ the
        # refine_grid padding rule): integer ops, so both paths agree
        # exactly and refine_grid stays the testable numpy reference
        lo = jnp.maximum(centers - stride, 0)                      # (S, R)
        hi = jnp.minimum(centers + stride, G - 1)
        t = jnp.clip(tail_start, 0, G)[:, None]
        t = jnp.broadcast_to(t, centers.shape)
        single = t <= hi + 1
        lo = jnp.where(single, jnp.minimum(lo, t), lo)
        hi2 = jnp.where(single, G - 1, hi)
        t2 = jnp.where(single, G, t)
        len1 = hi2 - lo + 1
        j = jnp.arange(width)
        pad = jnp.where(t2 < G, G - 1, hi2)
        win = lo[..., None] + j
        win = win + (t2 - lo - len1)[..., None] * (j >= len1[..., None])
        win = jnp.minimum(win, pad[..., None])                     # (S, R, W)
        win_grid = grid[jnp.arange(S)[:, None, None], win]
        return packed(solve_grid(N, T, union_no, tau_p, rates, rate_mask,
                                 win_grid, link_model_id, link_params,
                                 sigma, e0, contraction),
                      rates, grid, width)

    def layout(rates, grid, width=None):
        return layouts[_layout_key(rates, grid, width)]

    return grid_solve, grid_solve_fine, layout


@lru_cache(maxsize=16)
def _grid_solve_for(link_version: int, value_fn, exact_arq: bool):
    """Jitted grid solves for the CURRENT link-kernel table; keyed on the
    registry version so later link plugins get their own trace.  Bounded:
    stale versions' compiled programs are evicted rather than retained
    for the life of a long-running server."""
    del link_version  # cache key only
    return _build_grid_solve(kernel_table(), value_fn, exact_arq)


def grid_objective_builder(value_fn, exact_arq: bool = False) -> Callable:
    """Kernel builder for any objective of the form ``vals = f(grid,
    scenario params, effective overhead)`` — enough for most plugins.

    ``value_fn(g, N, T, n_o_eff, tau_p, sigma, e0, contraction)`` receives
    ``(S, R, G)``-broadcast jnp arrays (plus the three bound-constant
    scalars) and returns the ``(S, R, G)`` objective values to minimise.

    The built solve advertises ``supports_refine_windows``: the planner's
    coarse->fine fine pass then ships only ``(centers, tail_start)`` plus
    the static ``(refine_stride, refine_width)`` and the windows are
    gathered on device.

    Each call copies its results back ONCE: the jitted solve returns
    every output packed into one float64 ``(S, K)`` device buffer (layout
    in :func:`_build_grid_solve`), which :func:`_fetch_packed` pulls back
    and cuts into the dict of host arrays that :func:`_fetch` would give
    (same keys, dtypes, shapes and values), counting one ``d2h_arrays``.
    """

    def build(objective):
        def solve(arrays, consts, shard, batch):
            # three leaves: the call until it returns (argument
            # conversion, host-to-device copies, launch), the wait on
            # the device, the copies back
            with span("planner.dispatch"):
                dense_fn, win_fn, layout = _grid_solve_for(
                    kernel_table_version(), value_fn, exact_arq)
                arrays = dict(arrays)
                stride = arrays.pop("refine_stride", None)
                width = arrays.pop("refine_width", None)
                _count_in(len(arrays) + 3)
                with jax.enable_x64(True):
                    if shard:
                        arrays = _maybe_shard(arrays, arrays["N"].shape[0])
                    if stride is None:
                        buf = dense_fn(sigma=consts.variance_floor,
                                       e0=consts.init_gap,
                                       contraction=consts.contraction,
                                       **arrays)
                    else:
                        buf = win_fn(sigma=consts.variance_floor,
                                     e0=consts.init_gap,
                                     contraction=consts.contraction,
                                     stride=stride, width=width, **arrays)
            return _fetch_packed(buf, layout(arrays["rates"], arrays["grid"],
                                             width))
        solve.supports_refine_windows = True
        return solve

    return build


# ---------------------------------------------------------------------------
# Monte-Carlo objective: the empirical ridge loss, simulated in-batch
# ---------------------------------------------------------------------------


def pow2ceil(n: int) -> int:
    """Smallest power of two >= n — the shared padding rule that bounds
    how many compiled shapes (batch lengths, scan lengths) can exist."""
    p = 1
    while p < n:
        p *= 2
    return p


#: slab length of the table-driven Monte-Carlo engines (CRN scan and
#: pallas): the update timeline is processed in slabs of this many slots,
#: each slab's (slab, L) index/mask tables computed in one vectorised
#: shot so the inner per-slot loop is pure f32 training math.  A power of
#: two, so it always divides the pow2-padded ``max_updates``.  256 keeps
#: a slab's (slab, L) tables inside L2 at serving lane counts and
#: benches a few percent faster than 512/1024 on one CPU core; the slab
#: size only partitions the timeline, so plans are bitwise-invariant
#: to it.
MC_SLAB = 256


@lru_cache(maxsize=8)
def _mc_solve_for(objective, link_version: int, interpret: bool):
    """Jitted Monte-Carlo solve for one objective instance (its data and
    hyperparameters — including ``crn`` and ``seed_stream`` — are
    compile-time constants) and link-table version.  ``interpret`` runs
    the pallas engine through the Pallas interpreter (the CPU path).

    Returns ``(montecarlo_solve, layout)``.  The solve takes its nine
    arguments as one float64 ``(S, K)`` buffer and the static layout
    :func:`_mc_args` packed it with, and returns its outputs as one
    float64 buffer, as the grid solves do (:func:`_build_grid_solve`);
    ``layout(args_layout)`` gives that buffer's layout, recorded while
    tracing.  The device module is ``jit_montecarlo_solve``."""
    del link_version  # cache key only
    branches = kernel_table()
    # float32 mirrors the scalar path, which runs OUTSIDE enable_x64 and
    # downcasts the host float64 data on jnp.asarray
    X = jnp.asarray(np.asarray(objective.X, np.float32))
    y = jnp.asarray(np.asarray(objective.y, np.float32))
    n, d = X.shape
    lam = float(objective.lam)
    alpha = float(objective.alpha)
    n_runs = int(objective.n_runs)
    seed0 = int(objective.seed)
    crn = bool(getattr(objective, "crn", False))
    seed_stream = str(getattr(objective, "seed_stream", "legacy"))
    # one lane-dot form for every engine of the solve, so they agree
    # bitwise: the einsum under the interpreter (XLA:CPU fuses an ordered
    # chain differently in each engine), else the ordered sum, the only
    # form Mosaic lowers
    ordered = not interpret

    def run_key(r):
        # per-run key derivation, mirroring repro.core.pipeline.mc_run_key
        # (inlined: this runs under jit/vmap with a traced r)
        if seed_stream == "legacy":
            return jax.random.PRNGKey(seed0 + 97 * r)
        return jax.random.fold_in(jax.random.PRNGKey(seed0), r)

    layouts = {}

    @partial(jax.jit, static_argnames=("layout", "max_updates",
                                       "shard_lanes", "mc_impl", "mc_seeds"))
    def montecarlo_solve(buf, *, layout, max_updates, shard_lanes=False,
                         mc_impl="scan", mc_seeds=None):
        out, layouts[layout] = _pack(solve_arrays(
            **_unpack(buf, layout), max_updates=max_updates,
            shard_lanes=shard_lanes, mc_impl=mc_impl, mc_seeds=mc_seeds))
        return out

    def solve_arrays(N, T, union_no, tau_p, rates, rate_mask, grid,
                     link_model_id, link_params, *, max_updates,
                     shard_lanes, mc_impl, mc_seeds):
        runs = int(mc_seeds) if mc_seeds else n_runs
        record_trace(("montecarlo", mc_impl, crn, runs)
                     + tuple(grid.shape) + (max_updates,))
        S, R = rates.shape
        G = grid.shape[-1]
        rate = rates[:, :, None]
        gi = grid[:, None, :] if grid.ndim == 2 else grid      # (S, R?, G)
        g = gi.astype(T.dtype)

        p = _switch_p_err(branches, link_model_id, link_params, rates)
        raw = g / rate + union_no[:, None, None]
        dur = raw / (1.0 - p[:, :, None])                      # (S, R, G) f64
        n_o_eff = dur - g
        # the scalar path rebuilds the block duration as n_c + n_o_eff
        # (NOT the raw dur) — replicate so the f64 timeline is bitwise
        dur_sched = g + n_o_eff

        # one simulation lane per (scenario, rate, grid point); the lane
        # axis is scenario-major, so laying it out over the "fleet" mesh
        # agrees with _maybe_shard's scenario-axis placement of the inputs
        lane_nc = jnp.broadcast_to(gi, (S, R, G)).reshape(-1)
        lane_dur = dur_sched.reshape(-1)
        lane_tau = jnp.broadcast_to(tau_p[:, None, None], (S, R, G)).reshape(-1)
        lane_total = jnp.broadcast_to(
            jnp.floor(T / tau_p)[:, None, None], (S, R, G)).reshape(-1)
        L = lane_nc.shape[0]
        if shard_lanes:
            mesh = Mesh(np.asarray(jax.local_devices()), ("fleet",))
            lanes = NamedSharding(mesh, P("fleet"))
            constrain = partial(jax.lax.with_sharding_constraint,
                                shardings=lanes)
            lane_nc, lane_dur, lane_tau, lane_total = (
                constrain(lane_nc), constrain(lane_dur),
                constrain(lane_tau), constrain(lane_total))

        slab = min(MC_SLAB, max_updates)
        nslab = max_updates // slab
        # each lane's deadline as a slot count: no slot from it on
        # updates, so the pallas engine stops each lane block there and
        # the whole pass after the batch's last live slab
        lane_hi = jnp.minimum(lane_total, max_updates).astype(jnp.int32)
        live_slabs = (jnp.max(lane_hi) + (slab - 1)) // slab
        c_reg = jnp.float32(2.0 * alpha * lam / n)
        c_2a = jnp.float32(-2.0 * alpha)

        def avail_at(j):
            # samples available at update slot j (f64, mirrors the
            # host-side BlockSchedule.updates_timeline bit-for-bit);
            # j may be a scalar slot or a (slab,) slot vector
            jf = j.astype(lane_dur.dtype)
            t = (jf[:, None] * lane_tau[None, :] if j.ndim
                 else jf * lane_tau)
            blocks = jnp.floor(t / lane_dur).astype(jnp.int64)
            a = jnp.minimum(blocks * lane_nc, n)
            live = (jf[:, None] if j.ndim else jf) < lane_total
            return jnp.where(live, a, 0).astype(jnp.int32)

        def crn_tables(j0, u_s):
            # the whole slab's timeline in one vectorised shot: the f64
            # slot->availability map needs no carried state, so it runs
            # OUTSIDE the per-slot loop and the loop body stays pure f32.
            a = avail_at(j0 + jnp.arange(slab))                # (slab, L)
            af = a.astype(jnp.float32)
            # common random numbers: ONE shared uniform per slot across
            # every lane; the comonotone floor(u * a) sample index keeps
            # neighbouring grid points on maximally-correlated paths
            ix = jnp.minimum((u_s[:, None] * af).astype(jnp.int32),
                             jnp.maximum(a - 1, 0))
            return ix, (a > 0).astype(jnp.float32)

        def exact_tables(k, j0):
            # exact per-slot RNG: one split + one vmapped randint per
            # slot, consuming the key stream exactly like the reference
            # scan engine (and the scalar planner) do
            def tstep(k, j):
                k, sub = jax.random.split(k)
                a = avail_at(j)
                idx = jax.vmap(
                    lambda b: jax.random.randint(sub, (), 0, b,
                                                 dtype=jnp.int32)
                )(jnp.maximum(a, 1))
                return k, (idx, (a > 0).astype(jnp.float32))
            return jax.lax.scan(tstep, k, j0 + jnp.arange(slab))

        def scan_slab(W, Xs, ys, ix, m):
            # the CRN scan engine's inner loop: affine-fused update, no
            # RNG, no f64 — ridge_dot keeps the lane dot bitwise-equal
            # to the exact engine's and to the pallas kernel's
            def inner(W, row):
                ixr, mr = row
                xr = Xs[ixr]
                yr = ys[ixr]
                dot = ridge_dot(W, xr, ordered=ordered)
                c1 = 1.0 - mr * c_reg
                c2 = mr * c_2a * (dot - yr)
                return W * c1[:, None] + xr * c2[:, None], None
            W, _ = jax.lax.scan(inner, W, (ix, m), unroll=2)
            return W

        def pallas_slab(W, Xs, ys, ix, m, j0):
            from repro.kernels.mc_ridge import mc_ridge_slab
            slab_fn = partial(mc_ridge_slab, alpha=alpha, lam=lam,
                              fused=crn, interpret=interpret,
                              ordered=ordered)
            if shard_lanes:
                # XLA cannot partition a Mosaic kernel: each device runs
                # it on its own lanes (lanes are independent)
                slab_fn = jax.shard_map(
                    slab_fn, mesh=mesh,
                    in_specs=(P("fleet"), P(), P(), P(None, "fleet"),
                              P(None, "fleet"), P("fleet"), P()),
                    out_specs=P("fleet"), check_vma=False)
            return slab_fn(W, Xs, ys, ix, m, lane_hi, j0)

        def per_run_exact_scan(r):
            # the reference engine: per-slot split + randint INSIDE the
            # scan, vmapped ridge_grad_sample update — op-for-op the
            # scalar planner's stream, kept as the pinned escape hatch
            key = run_key(r)
            kp, kw, ks = jax.random.split(key, 3)
            perm = jax.random.permutation(kp, n)
            Xs, ys = X[perm], y[perm]
            w0 = jax.random.normal(kw, (d,), jnp.float32)
            W0 = jnp.broadcast_to(w0, (L, d))

            def step(carry, j):
                W, k = carry
                k, sub = jax.random.split(k)
                a = avail_at(j)
                # same key for every lane: the scalar path consumes ONE
                # split per update slot whatever the grid point
                idx = jax.vmap(
                    lambda b: jax.random.randint(sub, (), 0, b,
                                                 dtype=jnp.int32)
                )(jnp.maximum(a, 1))
                grads = jax.vmap(
                    partial(ridge_grad_sample, ordered=ordered),
                    (0, 0, 0, None, None))(W, Xs[idx], ys[idx], lam, n)
                W_new = W - alpha * grads
                W = jnp.where((a > 0)[:, None], W_new, W)
                return (W, k), None

            (W_fin, _), _ = jax.lax.scan(step, (W0, ks),
                                         jnp.arange(max_updates))
            return W_fin

        def per_run_pallas(r):
            # the pallas engine, one run: a loop over the slabs up to the
            # batch's last live one; each slab's (slab, L) tables, the
            # same the scan engines build, feed one pallas_call.  Slabs
            # past every deadline would only mask, so neither their
            # tables nor their calls are made; the uniforms are still
            # drawn for the whole horizon, so the stream is unchanged
            key = run_key(r)
            kp, kw, ks = jax.random.split(key, 3)
            perm = jax.random.permutation(kp, n)
            Xs, ys = X[perm], y[perm]
            w0 = jax.random.normal(kw, (d,), jnp.float32)
            W0 = jnp.broadcast_to(w0, (L, d))

            if crn:
                u = jax.random.uniform(ks, (max_updates,),
                                       jnp.float32).reshape(nslab, slab)

                def outer(s, W):
                    ix, m = crn_tables(s * slab, u[s])
                    return pallas_slab(W, Xs, ys, ix, m, s * slab)

                return jax.lax.fori_loop(np.int32(0), live_slabs, outer,
                                         W0)

            def outer(s, carry):
                W, k = carry
                k, (ix, m) = exact_tables(k, s * slab)
                return pallas_slab(W, Xs, ys, ix, m, s * slab), k

            W_fin, _ = jax.lax.fori_loop(np.int32(0), live_slabs, outer,
                                         (W0, ks))
            return W_fin

        def crn_scan_all_runs():
            # CRN scan engine, all runs in ONE pass over slabs: the f64
            # slot->availability tables depend only on the timeline (not
            # the run), so they are computed ONCE per slab and shared by
            # every run — the per-run work is just the f32 sample-index
            # map and the training scan.  Values are bitwise those of
            # the run-at-a-time form: same tables, same per-run streams,
            # same vmapped scan body.
            def prep(r):
                key = run_key(r)
                kp, kw, ks = jax.random.split(key, 3)
                perm = jax.random.permutation(kp, n)
                w0 = jax.random.normal(kw, (d,), jnp.float32)
                u = jax.random.uniform(ks, (max_updates,), jnp.float32)
                return (X[perm], y[perm], jnp.broadcast_to(w0, (L, d)),
                        u.reshape(nslab, slab))

            Xs_a, ys_a, W0_a, u_a = jax.vmap(prep)(jnp.arange(runs))

            def outer(W_a, inp):
                s, u_s = inp                           # u_s: (runs, slab)
                a = avail_at(s * slab + jnp.arange(slab))    # (slab, L)
                af = a.astype(jnp.float32)
                hi = jnp.maximum(a - 1, 0)
                m = (a > 0).astype(jnp.float32)

                def one(W, Xs, ys, u_r):
                    ix = jnp.minimum((u_r[:, None] * af).astype(jnp.int32),
                                     hi)
                    return scan_slab(W, Xs, ys, ix, m)

                return jax.vmap(one)(W_a, Xs_a, ys_a, u_s), None

            W_fin, _ = jax.lax.scan(outer, W0_a,
                                    (jnp.arange(nslab),
                                     jnp.moveaxis(u_a, 1, 0)))
            return W_fin

        if mc_impl == "pallas":
            # python loop over runs: vmapping a pallas_call would batch
            # the kernel grid; runs are few, so unrolled calls are fine
            W_fin = jnp.stack([per_run_pallas(r) for r in range(runs)])
        elif crn:
            W_fin = crn_scan_all_runs()
        else:
            W_fin = jax.vmap(per_run_exact_scan)(jnp.arange(runs))
        # one (runs, L) loss evaluation shared by every engine
        losses = ridge_losses(W_fin, X, y, lam)
        vals = jnp.mean(losses, axis=0).astype(T.dtype).reshape(S, R, G)

        return _reduce_joint_argmin(vals, n_o_eff, p, N, T, rates,
                                    rate_mask, grid)

    return montecarlo_solve, layouts.__getitem__


def _mc_args(arrays: dict):
    """The Monte-Carlo solve's arguments as one float64 ``(S, K)`` host
    matrix and its static layout (:func:`_pack`): one column block per
    key in the batch's key order (``N``, ``T``, ``union_no``, ``tau_p``,
    ``rates`` (R), ``rate_mask`` (R), ``grid`` (G, or R x W on a per-rate
    grid), ``link_model_id``, ``link_params`` (P)), so the layout is fixed
    by (R, G, P) and the dtypes.  The float64 columns are the arrays'
    own values and every integer and bool is below 2**24, so the solve
    cuts back bitwise the arrays it would be given one by one."""
    return _pack({k: np.asarray(v) for k, v in arrays.items()}, np)


def _mc_horizons(arrays: dict, max_updates: int) -> np.ndarray:
    """Each scenario's deadline in update slots, ``floor(T / tau_p)``,
    capped by the pass's horizon: every lane of the scenario updates only
    at slots below it."""
    return np.minimum(np.floor(np.asarray(arrays["T"])
                               / np.asarray(arrays["tau_p"])),
                      max_updates).astype(np.int64)


def _mc_order(horizon: np.ndarray, n_dev: int) -> np.ndarray:
    """The scenario order the pallas engine is fed in: by deadline, dealt
    over ``n_dev`` devices (device ``c`` holds the sorted positions ``c,
    c + n_dev, ...`` in ascending order), so each lane block's longest
    lane is close to its shortest and every device gets a like share."""
    by_deadline = np.argsort(horizon, kind="stable")
    return by_deadline.reshape(-1, n_dev).T.reshape(-1)


def _mc_run_slots(horizon: np.ndarray, per_scenario: int,
                  n_dev: int) -> int:
    """Lane-slots the pallas kernel steps through in one run: each
    device's lanes in ``BLOCK_L``-lane blocks, every lane stepping to its
    block's longest deadline (``block_steps`` summed over the slabs)."""
    from repro.kernels.mc_ridge import BLOCK_L
    total = 0
    for part in np.split(np.repeat(horizon, per_scenario), n_dev):
        pad = (-part.size) % BLOCK_L
        steps = np.pad(part, (0, pad)).reshape(-1, BLOCK_L).max(axis=1)
        real = np.full(steps.size, BLOCK_L)
        real[-1] -= pad
        total += int(steps @ real)
    return total


def _count_mc(arrays: dict, runs: int, max_updates: int, sharded: bool,
              kernel_devices: int = 0, order=None) -> None:
    """One Monte-Carlo pass on the open chunk record, reckoned on the host
    from the batch's arrays.  A lane is one simulated trajectory (run x
    scenario x rate x grid point, bucket padding included):
    ``mc_lane_slots`` counts every lane's padded timeline,
    ``mc_live_slots`` each lane's slots before its deadline
    ``floor(T / tau_p)``, capped by the pass's horizon (slots before a
    lane's first block arrives are live but masked), and
    ``mc_run_slots`` the slots the pallas kernel steps each lane through
    (its block's longest deadline), over ``kernel_devices`` devices in
    the arrays' order, or in ``order`` where the kernel is fed the rows
    so; the scan engines run no kernel and count none.
    ``mc_sharded_dispatches`` counts a pass laid over every local
    device."""
    per_scenario = arrays["rates"].shape[1] * arrays["grid"].shape[-1]
    horizon = _mc_horizons(arrays, max_updates)
    count("mc_lane_slots",
          runs * per_scenario * horizon.shape[0] * max_updates)
    count("mc_live_slots", runs * per_scenario * int(horizon.sum()))
    if kernel_devices:
        fed = horizon if order is None else horizon[order]
        count("mc_run_slots", runs * _mc_run_slots(fed, per_scenario,
                                                   kernel_devices))
    if sharded:
        count("mc_sharded_dispatches")


def montecarlo_builder(objective) -> Callable:
    """Kernel builder for ``MonteCarloObjective``: pads the shared update
    timeline to the next power of two over the batch (masked slots no-op,
    so plans are unaffected) to bound how many scan lengths can compile.

    Each solve crosses the host-device boundary once each way: its nine
    argument arrays travel as one float64 ``(S, K)`` matrix
    (:func:`_mc_args`), one ``device_put``, and its outputs come back as
    one buffer (:func:`_fetch_packed`), cut into the dict of host arrays
    the per-array path gave (same keys, dtypes, shapes and bits).  The
    chunk record counts one ``h2d_arrays`` and one ``d2h_arrays``.

    Sharded like the grid solves: the matrix is laid out over the local
    devices' "fleet" mesh on the scenario axis, and the kernel constrains
    its flattened scenario-major ``(S * R * G)`` simulation-lane axis to
    the same mesh, so every device simulates its own scenarios' lanes.
    Requires both ``S`` and the lane count to divide the device count;
    otherwise the solve runs unsharded (single device is the common case
    and is bitwise-unchanged by this path).

    The pallas engine gets the scenarios in deadline order
    (:func:`_mc_order`), one row index of the packed matrix, so its lane
    blocks stop early together; one row index of the fetched matrix puts
    the outputs back in the caller's order.  Lanes are independent (one
    shared uniform per slot, lane-wise losses), so the order changes no
    plan.
    """

    def solve(arrays, consts, shard, batch):
        del consts  # empirical objective
        arrays = dict(arrays)
        # host-side planner hints, popped before the arrays ship to the
        # device: the simulation engine, the coarse-pass seed count and
        # the coarse-pass horizon cap
        mc_impl = arrays.pop("mc_impl", "scan")
        mc_seeds = arrays.pop("mc_seeds", None)
        mc_updates = arrays.pop("mc_updates", None)
        # the pallas engine runs interpreted on the CPU (the test suite)
        # and compiled everywhere else
        fn, out_layout = _mc_solve_for(objective, kernel_table_version(),
                                       jax.default_backend() == "cpu")
        # the objective's min_updates floor pins the padded scan length
        # for serving: every batch below the floor shares ONE shape
        # (padded slots no-op, so plans are unaffected)
        max_updates = pow2ceil(max(1, batch.max_updates,
                                   int(getattr(objective, "min_updates",
                                               0) or 0)))
        if mc_updates:
            # truncated horizon (coarse-pass hint): train each lane for
            # at most this many update slots.  The CRN slot stream is
            # counter-based, so the truncated timeline is a bitwise
            # PREFIX of the full-horizon simulation.
            max_updates = min(max_updates,
                              pow2ceil(max(1, int(mc_updates))))
        S = arrays["N"].shape[0]
        n_dev = len(jax.local_devices())
        lanes = S * arrays["rates"].shape[1] * arrays["grid"].shape[-1]
        shard = bool(shard) and n_dev > 1 and S % n_dev == 0 \
            and lanes % n_dev == 0
        kernel_devices = 0
        order = back = None
        with span("planner.dispatch"):
            host, layout = _mc_args(arrays)
            if mc_impl == "pallas":
                kernel_devices = n_dev if shard else 1
                order = _mc_order(_mc_horizons(arrays, max_updates),
                                  kernel_devices)
                back = np.argsort(order)
                host = host[order]
            _count_in(1)
            _count_mc(arrays, int(mc_seeds or objective.n_runs),
                      max_updates, shard, kernel_devices, order)
            with jax.enable_x64(True):
                buf = jax.device_put(host, _fleet_sharding() if shard
                                     else None)
                out = fn(buf, layout=layout, max_updates=max_updates,
                         shard_lanes=shard, mc_impl=str(mc_impl),
                         mc_seeds=None if mc_seeds is None
                         else int(mc_seeds))
        return _fetch_packed(out, out_layout(layout), back)

    solve.supports_mc_impl = True
    return solve


register_objective_kernel("corollary1",
                          grid_objective_builder(_corollary1_values))
register_objective_kernel("markov_arq",
                          grid_objective_builder(_corollary1_values,
                                                 exact_arq=True))
register_objective_kernel("montecarlo", montecarlo_builder)
