"""Plain reference for Monte-Carlo planning (arXiv:1906.04488 Sec. V: the
best block size found by simulating pipelined SGD on the ridge task).

For each request, every point of its ``grid_points``-wide log grid at
every candidate rate is one simulation lane: the update timeline of the
pipelined protocol (blocks of ``n_c`` samples, each delivered after its
expected block time over the link, one SGD update every ``tau_p`` up to
the deadline ``T``) drives ``n_runs`` runs of single-sample ridge SGD on
a small data set, and the lane's value is the runs' mean final ridge
loss.  The plan is the lane of least value.  The objective's settings
(data set, runs, step, regulariser, seed) are read from the
configuration's ``objective`` section.

The estimator is the common-random-numbers one the configuration serves
(``mc_crn``): per run ``r`` the key ``fold_in(PRNGKey(seed), r)`` is split
into a row permutation, the initial weights and ONE uniform ``u_j`` per
update slot shared by every lane; at slot ``j`` a lane with ``a`` samples
available trains on row ``min(floor(u_j a), a - 1)`` of the permuted
set.  The keys, permutations, initial weights and uniforms are drawn with
``jax.random`` on the host's CPU; everything else is numpy:

- the timeline in float64: ``a_j = min(floor(j tau_p / dur) n_c, rows)``
  for ``j < floor(T / tau_p)``, else 0, with ``dur = n_c + n_o_eff`` and
  ``n_o_eff`` the link's expected block time less ``n_c``;
- the sample index ``floor(u_j a_j)`` in float32, as the objective
  computes it, so that rounding never picks another row;
- the SGD in float32 (the controls: bfloat16, and float32 SGD on a
  float32 timeline), in the update's affine form
  ``w <- w (1 - m c_reg) + x (m c_2a (w.x - y))`` with ``m`` the slot's
  live mask, ``c_reg = 2 alpha lam / rows``, ``c_2a = -2 alpha`` and
  ``w.x`` added left to right; the final loss likewise.

Departures from ``repro.core.montecarlo.montecarlo_objective_grid`` (the
program's scalar reference, which runs the exact per-slot RNG stream):
the shared-uniform sample index above in place of a per-slot
``randint``; the slots are not padded to the batch's power of two (a
masked slot leaves the weights unchanged, so a lane's value does not
depend on the padding); the data set is regenerated here from its
generator's recipe.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from reference.common import block_time, log_grid, pick, served_gaps

#: slots whose sample-index and mask tables are built in one shot
SLAB = 256


def dtypes(precision: str):
    """The numpy dtypes ``(sgd, timeline)`` of a precision the
    configuration names: ``"<sgd> SGD on a <timeline> timeline"``, or the
    SGD's alone (the timeline then float64)."""
    words = precision.split()
    timeline = words[-2] if words[-1] == "timeline" else "float64"
    if timeline not in ("float64", "float32"):
        raise ValueError(f"unknown precision {precision!r}")
    if words[0] == "float32":
        sgd = np.float32
    elif words[0] == "bfloat16":
        import ml_dtypes
        sgd = ml_dtypes.bfloat16
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return sgd, np.dtype(timeline).type


def make_dataset(rows: int, features: int, seed: int, *, l_max=1.908,
                 l_min=0.061, noise=0.3):
    """The canonical synthetic ridge data set (``make_regression_dataset``):
    a Gramian ``X^T X / rows`` whose spectrum spans ``[l_min, l_max]``
    with both extremes hit exactly, and noisy linear targets.  Returns
    float32 ``(X, y)``."""
    rng = np.random.default_rng(seed)
    Q, _ = np.linalg.qr(rng.standard_normal((features, features)))
    eigs = np.concatenate([[l_min], np.exp(
        rng.uniform(np.log(l_min), np.log(l_max), features - 2)), [l_max]])
    Z = rng.standard_normal((rows, features))
    Z = (Z - Z.mean(0)) / Z.std(0)
    U, _, Vt = np.linalg.svd(Z, full_matrices=False)
    X = U @ np.diag(np.sqrt(rows * eigs)) @ Vt @ Q.T
    w_true = rng.standard_normal(features)
    y = X @ w_true + noise * rng.standard_normal(rows)
    return X.astype(np.float32), y.astype(np.float32)


def run_streams(objective: Dict, slots: int):
    """Per run: the permuted rows' indices, the initial weights and the
    first ``slots`` shared uniforms, drawn as the objective draws them."""
    import jax

    if objective.get("seed_stream", "fold_in") != "fold_in":
        raise ValueError("the reference draws fold_in run keys only")
    rows = int(objective["dataset"]["rows"])
    d = int(objective["dataset"]["features"])
    cpu = jax.devices("cpu")[0]
    perms, w0s, us = [], [], []
    with jax.default_device(cpu), jax.enable_x64(True):
        base = jax.random.PRNGKey(int(objective["seed"]))
        for r in range(int(objective["n_runs"])):
            kp, kw, ks = jax.random.split(jax.random.fold_in(base, r), 3)
            perms.append(np.asarray(jax.random.permutation(kp, rows)))
            w0s.append(np.asarray(jax.random.normal(kw, (d,),
                                                    np.float32)))
            us.append(np.asarray(jax.random.uniform(ks, (slots,),
                                                    np.float32)))
    return np.stack(perms), np.stack(w0s), np.stack(us)


def _ordered_dot(a, b):
    """``sum_k a[k] b[k]`` over the leading axis, added left to right."""
    prods = a * b
    acc = prods[0]
    for k in range(1, prods.shape[0]):
        acc = acc + prods[k]
    return acc


def evaluate(requests: Sequence[Dict], config: Dict,
             precision: str) -> List:
    """``(grid, rates, values)`` of every request, values ``(R, G)`` in
    float64, every request's lanes simulated together."""
    obj = config["objective"]
    dt, tl = dtypes(precision)
    rows = int(obj["dataset"]["rows"])
    X, y = make_dataset(rows, int(obj["dataset"]["features"]),
                        int(obj["dataset"]["seed"]))
    alpha, lam = float(obj["alpha"]), float(obj["lam"])
    size = int(obj["grid_points"])

    # -- the timeline of every lane (request x rate x grid point), in
    # float64 (``tl``)
    grids, rate_rows, durs, taus, totals = [], [], [], [], []
    for req in requests:
        grid = log_grid(req["N"], size)
        rates = np.asarray(req["rates"], np.float64)
        g = np.broadcast_to(grid.astype(tl)[None, :],
                            (rates.size, grid.size))
        dur = block_time(req, grid, rates, tl, burst_exact=False)
        grids.append(grid)
        rate_rows.append(rates)
        durs.append((g + (dur - g)).reshape(-1))
        taus.append(np.full(g.size, tl(req["tau_p"])))
        totals.append(np.full(g.size, np.floor(tl(req["T"])
                                               / tl(req["tau_p"]))))
    lane_nc = np.concatenate([np.broadcast_to(gr[None, :], (r.size, gr.size))
                              .reshape(-1) for gr, r in zip(grids,
                                                            rate_rows)])
    lane_dur = np.concatenate(durs)
    lane_tau = np.concatenate(taus)
    lane_total = np.concatenate(totals)
    L = lane_nc.size
    horizon = int(lane_total.max())

    perms, w0s, us = run_streams(obj, -(-horizon // SLAB) * SLAB)
    runs, d = w0s.shape
    # the runs' permuted rows side by side: lane k of run r reads row
    # r * rows + ix of the stacked (runs * rows) table
    Xs = np.concatenate([X[p] for p in perms]).T.astype(dt)    # (d, R n)
    ys = np.concatenate([y[p] for p in perms]).astype(dt)
    offset = (np.arange(runs) * rows)[:, None]
    W = np.repeat(w0s.astype(dt)[:, :, None], L, axis=2)       # (runs,d,L)
    W = np.moveaxis(W, 1, 0).reshape(d, runs * L)
    c_reg = dt(np.float32(2.0 * alpha * lam / rows))
    c_2a = dt(np.float32(-2.0 * alpha))
    one = dt(1.0)

    for j0 in range(0, horizon, SLAB):
        j = np.arange(j0, min(j0 + SLAB, horizon), dtype=tl)
        t = j[:, None] * lane_tau[None, :]
        a = np.minimum(np.floor(t / lane_dur).astype(np.int64) * lane_nc,
                       rows)
        a = np.where(j[:, None] < lane_total, a, 0).astype(np.int32)
        af = a.astype(np.float32)
        hi = np.maximum(a - 1, 0)
        m = (a > 0).astype(dt)
        u = us[:, j0:j0 + j.size]                               # (runs, s)
        ix = np.minimum((u[:, :, None] * af[None]).astype(np.int32),
                        hi[None])                               # (runs,s,L)
        flat = (ix + offset[:, :, None]).transpose(1, 0, 2).reshape(
            j.size, runs * L)
        mm = np.tile(m, (1, runs))
        # a lane masked over the whole slab keeps its weights exactly
        # (w * 1 + x * 0), so only the lanes with a live slot are stepped
        act = np.flatnonzero(mm.any(axis=0))
        if act.size == 0:
            continue
        Wa, flat, mm = W[:, act], flat[:, act], mm[:, act]
        c1, c2a, yr = one - mm * c_reg, mm * c_2a, ys[flat]
        for k in range(j.size):
            xr = Xs[:, flat[k]]
            c2 = c2a[k] * (_ordered_dot(Wa, xr) - yr[k])
            Wa = Wa * c1[k] + xr * c2
        W[:, act] = Wa

    # the final ridge loss of every run's lanes, then the runs' mean
    Xd, yd = X.astype(dt), y.astype(dt)
    sq = np.zeros(runs * L, dt)
    for i in range(rows):
        r = _ordered_dot(W, Xd[i][:, None]) - yd[i]
        sq = sq + r * r
    loss = sq / dt(rows) + dt(lam / rows) * _ordered_dot(W, W)
    loss = loss.reshape(runs, L)
    mean = loss[0]
    for r in range(1, runs):
        mean = mean + loss[r]
    vals = (mean / dt(runs)).astype(np.float64)

    out, start = [], 0
    for grid, rates in zip(grids, rate_rows):
        n = rates.size * grid.size
        out.append((grid, rates, vals[start:start + n].reshape(
            rates.size, grid.size)))
        start += n
    return out


def compare(requests: Sequence[Dict], records: Sequence[Dict],
            config: Dict) -> Dict[str, float]:
    """The numbers ``correct`` is decided by (see ``served_gaps``): the
    served value against the reference's at the served point, and the
    reference's value there against its least over the request's whole
    grid and every rate."""
    return served_gaps(records, evaluate(requests, config,
                                         config["precision"]))


def control(requests: Sequence[Dict], config: Dict) -> List[Dict]:
    """The reference's own plans with the SGD in the configuration's
    ``control_precision``: what must come out as not correct."""
    return [pick(*ev) for ev in evaluate(requests, config,
                                         config["control_precision"])]
