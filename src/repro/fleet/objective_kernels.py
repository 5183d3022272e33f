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

import time
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
from repro.obs.runtime import record_solve

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


def _maybe_shard(arrays: dict, S: int) -> dict:
    """Lay the batch out across local devices over the scenario axis."""
    devices = jax.local_devices()
    if len(devices) <= 1 or S % len(devices) != 0:
        return arrays
    mesh = Mesh(np.asarray(devices), ("fleet",))
    sharding = NamedSharding(mesh, P("fleet"))
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


def _build_grid_solve(branches, value_fn, exact_arq: bool):
    """Jit a grid-objective solve closed over a link-kernel branch table.

    Shapes: per-scenario vectors (S,), rate matrix (S, R), grid (S, G);
    output per-scenario reductions.  ``exact_arq`` swaps the stationary
    ARQ inflation for the exact Markov-reward block time on
    non-degenerate Gilbert-Elliott rows.

    Returns ``(solve, solve_windows)``: the single-pass solve over a
    ``(S, G)`` / per-rate ``(S, R, G)`` grid, and the FUSED fine pass of
    the coarse->fine solve, which builds the per-rate bracket+tail
    windows ON DEVICE from ``(centers, tail_start)`` — mirroring
    :func:`repro.core.planner.refine_window_bounds` op-for-op — so the
    serving hot path never materialises or transfers ``(S, R, W)``
    window arrays from the host.
    """

    def _core(N, T, union_no, tau_p, rates, rate_mask, grid,
              link_model_id, link_params, sigma, e0, contraction):
        # runs once per TRACE (both the dense jit and _solve_windows
        # funnel through this body) — the serving layer's retrace audit
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

    @partial(jax.jit, static_argnames=("stride", "width"))
    def _solve_windows(N, T, union_no, tau_p, rates, rate_mask, grid,
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
        return _core(N, T, union_no, tau_p, rates, rate_mask, win_grid,
                     link_model_id, link_params, sigma, e0, contraction)

    return jax.jit(_core), _solve_windows


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
    """

    def build(objective):
        def solve(arrays, consts, shard, batch):
            dense_fn, win_fn = _grid_solve_for(kernel_table_version(),
                                               value_fn, exact_arq)
            arrays = dict(arrays)
            stride = arrays.pop("refine_stride", None)
            width = arrays.pop("refine_width", None)
            S = arrays["N"].shape[0]
            with jax.enable_x64(True):
                if shard:
                    arrays = _maybe_shard(arrays, S)
                # device/host attribution: the fence makes the jitted
                # call's duration the device portion, asarray the host's
                t0 = time.perf_counter()
                if stride is None:
                    out = dense_fn(sigma=consts.variance_floor,
                                   e0=consts.init_gap,
                                   contraction=consts.contraction, **arrays)
                else:
                    out = win_fn(sigma=consts.variance_floor,
                                 e0=consts.init_gap,
                                 contraction=consts.contraction,
                                 stride=stride, width=width, **arrays)
                jax.block_until_ready(out)
                t1 = time.perf_counter()
                res = {k: np.asarray(v) for k, v in out.items()}
                record_solve(t1 - t0, time.perf_counter() - t1)
                return res
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
    the pallas engine through the Pallas interpreter (the CPU path)."""
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

    @partial(jax.jit, static_argnames=("max_updates", "shard_lanes",
                                       "mc_impl", "mc_seeds"))
    def _solve(N, T, union_no, tau_p, rates, rate_mask, grid,
               link_model_id, link_params, *, max_updates,
               shard_lanes=False, mc_impl="scan", mc_seeds=None):
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

        def pallas_slab(W, Xs, ys, ix, m):
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
                              P(None, "fleet")),
                    out_specs=P("fleet"), check_vma=False)
            return slab_fn(W, Xs, ys, ix, m)

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

        def per_run_slabbed(r):
            # table-driven engines: outer scan over slabs; each slab's
            # (slab, L) tables feed either the lean jnp inner scan or
            # one pallas_call — both consume IDENTICAL tables, so the
            # two engines agree bitwise
            key = run_key(r)
            kp, kw, ks = jax.random.split(key, 3)
            perm = jax.random.permutation(kp, n)
            Xs, ys = X[perm], y[perm]
            w0 = jax.random.normal(kw, (d,), jnp.float32)
            W0 = jnp.broadcast_to(w0, (L, d))
            run_slab = pallas_slab if mc_impl == "pallas" else scan_slab

            if crn:
                u = jax.random.uniform(ks, (max_updates,),
                                       jnp.float32).reshape(nslab, slab)

                def outer(W, inp):
                    s, u_s = inp
                    ix, m = crn_tables(s * slab, u_s)
                    return run_slab(W, Xs, ys, ix, m), None

                W_fin, _ = jax.lax.scan(outer, W0,
                                        (jnp.arange(nslab), u))
            else:
                def outer(carry, s):
                    W, k = carry
                    k, (ix, m) = exact_tables(k, s * slab)
                    return (run_slab(W, Xs, ys, ix, m), k), None

                (W_fin, _), _ = jax.lax.scan(outer, (W0, ks),
                                             jnp.arange(nslab))
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
            W_fin = jnp.stack([per_run_slabbed(r) for r in range(runs)])
        elif crn:
            W_fin = crn_scan_all_runs()
        else:
            W_fin = jax.vmap(per_run_exact_scan)(jnp.arange(runs))
        # one (runs, L) loss evaluation shared by every engine
        losses = ridge_losses(W_fin, X, y, lam)
        vals = jnp.mean(losses, axis=0).astype(T.dtype).reshape(S, R, G)

        return _reduce_joint_argmin(vals, n_o_eff, p, N, T, rates,
                                    rate_mask, grid)

    return _solve


def montecarlo_builder(objective) -> Callable:
    """Kernel builder for ``MonteCarloObjective``: pads the shared update
    timeline to the next power of two over the batch (masked slots no-op,
    so plans are unaffected) to bound how many scan lengths can compile.

    Sharded like the grid solves: the batch arrays are laid out over the
    local devices' "fleet" mesh on the scenario axis via ``_maybe_shard``,
    and the kernel constrains its flattened scenario-major ``(S * R * G)``
    simulation-lane axis to the same mesh, so every device simulates its
    own scenarios' lanes.  Requires both ``S`` and the lane count to
    divide the device count; otherwise the solve runs unsharded (single
    device is the common case and is bitwise-unchanged by this path).
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
        fn = _mc_solve_for(objective, kernel_table_version(),
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
        with jax.enable_x64(True):
            if shard:
                arrays = _maybe_shard(arrays, S)
            t0 = time.perf_counter()
            out = fn(max_updates=max_updates, shard_lanes=shard,
                     mc_impl=str(mc_impl),
                     mc_seeds=None if mc_seeds is None else int(mc_seeds),
                     **arrays)
            jax.block_until_ready(out)
            t1 = time.perf_counter()
            res = {k: np.asarray(v) for k, v in out.items()}
            record_solve(t1 - t0, time.perf_counter() - t1)
            return res

    solve.supports_mc_impl = True
    return solve


register_objective_kernel("corollary1",
                          grid_objective_builder(_corollary1_values))
register_objective_kernel("markov_arq",
                          grid_objective_builder(_corollary1_values,
                                                 exact_arq=True))
register_objective_kernel("montecarlo", montecarlo_builder)
