"""The jitted federated-round kernel: per-device grids + participation scan.

One ``jax.jit`` call solves the WHOLE round: it reuses the fleet
engine's per-scenario joint ``(rate, n_c)`` grid evaluation (the same
link dispatch and Corollary-1 value function the registered
``corollary1`` objective kernel runs — see
:mod:`repro.fleet.objective_kernels`), masks the grid to the
DEADLINE-FEASIBLE points, reduces each device to its best feasible
operating point, and then solves the participation axis with a
sort-and-prefix-scan:

  1. **Inner sweep** — for every candidate device, every ``(rate, n_c)``
     point gets its Corollary-1 bound at the round deadline ``T`` and
     its completion time ``ceil(N / n_c) * (n_c + n_o_eff)`` (the time
     the device's last block lands; ``completion <= T`` is exactly the
     "full transfer by the deadline" regime boundary of Corollary 1).
     Infeasible points are masked to ``+inf`` and each device keeps its
     rate-major argmin — the same tie-breaking contract as
     ``_reduce_joint_argmin``.
  2. **Participation scan** — devices sort ascending by best-feasible
     bound (stable: ties keep population order), and one sequential
     scan gives the prefix sum — the aggregated bound ``F(K)`` for EVERY
     participant count ``K`` — and the prefix max, each prefix's
     straggler-bounded round time.  ``argmin F(K)`` (first minimum, i.e. the smallest
     optimal ``K``) picks the round.

The aggregated objective is

    ``F(K) = (1/K) sum_{i in topK} b_i  -  sigma * (1 - 1/K)``

i.e. ``sigma / K + mean(b_i - sigma)``: the ``K`` participants train
independently on DISJOINT shards, so averaging their models keeps the
mean of the per-device bias terms (each bound's excess over the SGD
noise floor ``sigma = consts.variance_floor``) while the independent
gradient-noise floors average down as ``sigma / K``.  More devices
always shrink the noise term but drag the mean toward worse devices —
participation count is a real axis, not a monotone knob.

Every prefix over eligible devices already satisfies the deadline
(each member's best-feasible completion is ``<= T``), so the straggler
max is a REPORT (the realised round length), not a second constraint.

``valid`` masks the batch-padding lanes out of eligibility — a padded
copy of a real device must never join the round (the fleet planner can
discard pad results; a prefix scan cannot).

Like every fleet kernel, the body's first statement is
:func:`repro.fleet.tracing.record_trace` — the serving layer's
zero-post-warmup-traces audit counts this kernel too.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp

from repro.fleet.link_kernels import kernel_table, kernel_table_version
from repro.fleet.objective_kernels import _corollary1_values, _switch_p_err
from repro.fleet.tracing import record_trace


def _build_round_solve(branches):
    """Jit the round solve closed over the link-kernel branch table."""

    @jax.jit
    def _solve(N, T, union_no, tau_p, rates, rate_mask, grid,
               link_model_id, link_params, valid, sigma, e0, contraction):
        # runs once per TRACE — the serving retrace audit
        record_trace(("federated",) + tuple(grid.shape))
        S = rates.shape[0]
        rate = rates[:, :, None]                               # (S, R, 1)
        g = grid[:, None, :].astype(jnp.float64)               # (S, 1, G)

        # ---- inner sweep: the fleet engine's joint-grid evaluation ----
        p = _switch_p_err(branches, link_model_id, link_params, rates)
        raw = g / rate + union_no[:, None, None]               # (S, R, G)
        dur = raw / (1.0 - p[:, :, None])
        n_o_eff = dur - g
        vals = _corollary1_values(
            g, N[:, None, None].astype(jnp.float64), T, n_o_eff,
            tau_p[:, None, None], sigma, e0, contraction)

        # completion = ceil(N / n_c) blocks at the REBUILT duration
        # g + n_o_eff (the scalar schedule's op order, not the raw dur),
        # so the numpy reference reproduces the comparison bit-for-bit;
        # completion <= T  <=>  Corollary 1's full-transfer regime
        blocks = jnp.ceil(N[:, None, None].astype(jnp.float64) / g)
        completion = blocks * (g + n_o_eff)
        feasible = (completion <= T) & rate_mask[:, :, None]
        masked = jnp.where(feasible, vals, jnp.inf)

        # per-device best feasible point, rate-major tie-breaking (the
        # _reduce_joint_argmin contract: first grid point within a rate,
        # then first rate) — which is the first minimum of the flattened
        # (R, G) block, as the numpy reference takes it.  One flat argmin
        # also keeps the TPU compile short: every further consumer of the
        # float64 grid makes XLA duplicate its emulated arithmetic.
        G = grid.shape[1]
        flat = jnp.argmin(masked.reshape(S, -1), axis=1)       # (S,)
        ri, gi = flat // G, flat % G
        s = jnp.arange(S)
        best = masked[s, ri, gi]                               # +inf if none
        best_t = completion[s, ri, gi]

        # ---- participation axis: sort + prefix scans over devices ----
        eligible = jnp.isfinite(best) & valid
        sort_key = jnp.where(eligible, best, jnp.inf)
        order = jnp.argsort(sort_key)          # stable: ties keep index order
        b_sorted = sort_key[order]
        t_sorted = jnp.where(eligible, best_t, -jnp.inf)[order]

        # one sequential pass for both prefix scans: it adds left to
        # right like np.cumsum (so the sums stay bitwise), and the TPU
        # compiler takes seconds for it where jnp.cumsum / lax.cummax in
        # float64 take minutes
        def prefix(carry, bt):
            total, latest = carry
            total = total + bt[0]
            latest = jnp.maximum(latest, bt[1])
            return (total, latest), (total, latest)

        _, (b_cum, t_cmax) = jax.lax.scan(
            prefix, (jnp.zeros((), b_sorted.dtype),
                     jnp.full((), -jnp.inf, t_sorted.dtype)),
            (b_sorted, t_sorted))
        K = jnp.arange(1, S + 1, dtype=jnp.float64)
        curve = b_cum / K - sigma * (1.0 - 1.0 / K)
        n_eligible = jnp.sum(eligible)
        curve = jnp.where(jnp.arange(1, S + 1) <= n_eligible,
                          curve, jnp.inf)
        k_best = jnp.argmin(curve) + 1         # ties -> smallest K
        round_time = t_cmax[k_best - 1]

        return {
            "order": order, "k_best": k_best,
            "objective_value": curve[k_best - 1],
            "objective_curve": curve,
            "round_time": round_time, "n_eligible": n_eligible,
            "n_c": grid[s, gi], "rate": rates[s, ri],
            "bound_value": best, "p_err": p[s, ri],
            "n_o_eff": n_o_eff[s, ri, gi], "completion_time": best_t,
            "eligible": eligible,
        }

    return _solve


@lru_cache(maxsize=4)
def _round_solve_for(link_version: int):
    """The jitted round solve for the CURRENT link-kernel table; keyed on
    the registry version so late link plugins retrace instead of
    stale-dispatching (same scheme as ``_grid_solve_for``)."""
    del link_version  # cache key only
    return _build_round_solve(kernel_table())


def round_solve():
    """The jitted federated-round solve for the current link registry."""
    return _round_solve_for(kernel_table_version())
