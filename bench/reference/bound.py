"""Plain reference for bound planning (arXiv:1906.04488, Corollary 1).

For each request, the Corollary-1 bound on the optimality gap of
pipelined SGD (eqs. 14-15) is evaluated at every block size of its
``grid_size``-point log grid and every candidate rate, with the link's
expected block time folded into an effective per-block overhead, and the
plan is the point that minimises it.  ``corollary1`` takes the
stationary loss rate of a Gilbert-Elliott link; ``markov_arq`` its exact
burst-aware attempts per block.  The grid mode a request names changes
how the program searches, not the plan it must find, so the reference
always searches the whole grid.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from reference.common import block_time, log_grid, pick, served_gaps

#: numpy dtype of the precision the configuration states, and of the
#: control one step below it.
DTYPES = {"float64": np.float64, "float32": np.float32}


def corollary1(n_c, n_o, *, N, T, tau_p, consts: Dict, dt) -> np.ndarray:
    """Eq. (14) where the whole set cannot arrive by ``T``, else eq. (15)."""
    one, zero = dt(1.0), dt(0.0)
    alpha, L, c = dt(consts["alpha"]), dt(consts["L"]), dt(consts["c"])
    gamma = alpha * (one - dt(0.5) * alpha * L * dt(consts["M_G"]))
    sigma = alpha * alpha * L * dt(consts["M"]) / (dt(2.0) * gamma * c)
    e0 = L * dt(consts["D"]) ** 2 / dt(2.0)
    r = np.clip(one - gamma * c, zero, one)
    N, T, tau_p = dt(N), dt(T), dt(tau_p)

    dur = n_c + n_o
    B_d = N / n_c
    B = np.floor(T / dur)
    n_p = np.floor(dur / tau_p)
    full = T > B_d * dur
    rp = r ** n_p

    def geom(k):  # sum_{l=1}^{k} rp^l
        out = np.where(np.abs(one - rp) < dt(1e-15), k,
                       rp * (one - rp ** k) / (one - rp))
        return np.where(k <= zero, zero, out)

    frac = np.clip((B - one) / B_d, zero, one)
    bound_a = (sigma * frac + (one - frac) * e0
               + (e0 - sigma) * geom(np.maximum(B - one, zero)) / B_d)
    n_l = np.floor(np.maximum(T - B_d * dur, zero) / tau_p)
    s_b = one + geom(np.maximum(np.ceil(B_d) - one, zero))
    bound_b = sigma + r ** n_l * (e0 - sigma) * s_b / B_d
    return np.where(full, bound_b, bound_a)


def evaluate(requests: Sequence[Dict], config: Dict,
             precision: str) -> List:
    """``(grid, rates, values)`` of every request, values ``(R, G)``."""
    dt = DTYPES[precision]
    size = int(config["service"]["grid_size"])
    consts = config["bound_constants"]
    out = []
    for req in requests:
        grid = log_grid(req["N"], size)
        rates = np.asarray(req["rates"], np.float64)
        g = np.broadcast_to(np.asarray(grid, dt)[None, :],
                            (rates.size, grid.size))
        dur = block_time(req, grid, rates, dt,
                         burst_exact=req["objective"] == "markov_arq")
        vals = corollary1(g, dur - g, N=req["N"], T=req["T"],
                          tau_p=req["tau_p"], consts=consts, dt=dt)
        out.append((grid, rates, vals))
    return out


def compare(requests: Sequence[Dict], records: Sequence[Dict],
            config: Dict) -> Dict[str, float]:
    """The numbers ``correct`` is decided by (see ``served_gaps``)."""
    return served_gaps(records, evaluate(requests, config,
                                         config["precision"]))


def control(requests: Sequence[Dict], config: Dict) -> List[Dict]:
    """The reference's own plans, computed one precision step below the
    configuration's: what must come out as not correct."""
    return [pick(*ev) for ev in evaluate(requests, config,
                                         config["control_precision"])]
