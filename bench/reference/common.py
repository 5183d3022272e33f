"""Plain numpy pieces shared by the planning references.

A request is a plain dict drawn by the traffic generator::

    {"N": 5000, "T": 9000.0, "n_o": 40.0, "tau_p": 1.0, "D": 2,
     "link": {"family": "erasure", "beta": 0.3, "p_base": 0.1},
     "rates": [1.0, 1.25, 1.5, 2.0, 3.0],
     "objective": "corollary1", "grid_mode": "dense"}

and a served plan a dict ``{"n_c", "rate", "value", "fallback"}``.  The
link physics follow arXiv:1906.04488 Sec. VI (stop-and-wait ARQ over an
erasure, Rayleigh-fading or Gilbert-Elliott link): the expected block time
is the lossless time ``n_c / rate + D n_o`` inflated by ``1 / (1 - p)``.
Every function takes the dtype it computes in, so one code path gives the
reference and its lower-precision control.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

#: Cap on a packet's loss probability, so that ARQ inflation stays finite.
P_ERR_MAX = 0.999


def log_grid(N: int, size: int) -> np.ndarray:
    """``size`` log-spaced integer block sizes from 1 to ``N`` (not
    deduplicated: every request is searched over the same width)."""
    expo = np.linspace(0.0, 1.0, size) * np.log10(float(N))
    return np.maximum(np.round(10.0 ** expo).astype(np.int64), 1)


def p_err(link: Dict, rate: np.ndarray, dt) -> np.ndarray:
    """Loss probability of one packet at each candidate ``rate``."""
    rate = np.asarray(rate, dt)
    one = dt(1.0)
    fam = link["family"]
    if fam == "ideal":
        return np.zeros_like(rate)
    if fam == "erasure":
        p = one - (one - dt(link["p_base"])) * np.exp(
            -dt(link["beta"]) * np.maximum(rate - one, dt(0.0)))
    elif fam == "fading":
        p = one - np.exp(-(np.exp2(rate) - one) / dt(link["snr"]))
    elif fam == "gilbert_elliott":
        p_g, p_b = _ge_state_p(link, rate, dt)
        pi_bad = dt(link["p_gb"]) / (dt(link["p_gb"]) + dt(link["p_bg"]))
        p = p_g + pi_bad * (p_b - p_g)
    else:
        raise ValueError(f"unknown link family {fam!r}")
    return np.minimum(p, dt(P_ERR_MAX))


def _ge_state_p(link: Dict, rate: np.ndarray, dt):
    one = dt(1.0)
    decay = np.exp(-dt(link["beta"]) * np.maximum(rate - one, dt(0.0)))
    return (one - (one - dt(link["p_good"])) * decay,
            one - (one - dt(link["p_bad"])) * decay)


def block_time(req: Dict, grid: np.ndarray, rates: np.ndarray, dt, *,
               burst_exact: bool) -> np.ndarray:
    """Expected delivery time of one block, ``(R, G)``.

    ``burst_exact`` takes a sticky Gilbert-Elliott link's attempts per
    block from the Markov-reward solve of its two-state chain (a failure
    is evidence of the bad state) instead of the stationary loss rate."""
    g = np.asarray(grid, dt)[None, :]
    r = np.asarray(rates, dt)[:, None]
    raw = g / r + dt(req["D"]) * dt(req["n_o"])
    link = req["link"]
    if burst_exact and link["family"] == "gilbert_elliott" \
            and link["p_good"] != link["p_bad"]:
        return raw * _ge_attempts(link, r, dt)
    return raw / (dt(1.0) - p_err(link, r, dt))


def _ge_attempts(link: Dict, rate: np.ndarray, dt) -> np.ndarray:
    """Expected transmission attempts per delivered block on a
    Gilbert-Elliott link, started from the stationary state:
    ``T_g = 1 + p_g[(1 - p_gb) T_g + p_gb T_b]``,
    ``T_b = 1 + p_b[p_bg T_g + (1 - p_bg) T_b]``."""
    one = dt(1.0)
    p_g, p_b = (np.minimum(p, dt(P_ERR_MAX))
                for p in _ge_state_p(link, rate, dt))
    p_gb, p_bg = dt(link["p_gb"]), dt(link["p_bg"])
    den_g = one - p_g * (one - p_gb)
    den_b = one - p_b * (one - p_bg)
    det = den_g * den_b - p_g * p_gb * p_b * p_bg
    t_g = (den_b + p_g * p_gb) / det
    t_b = (den_g + p_b * p_bg) / det
    return t_g + p_gb / (p_gb + p_bg) * (t_b - t_g)


def pick(grid: np.ndarray, rates: np.ndarray, vals: np.ndarray) -> Dict:
    """The plan minimising ``vals`` (R, G): the first rate, then the
    first block size, among equal minima."""
    flat = int(np.argmin(vals))
    ri, gi = divmod(flat, vals.shape[1])
    return {"n_c": int(grid[gi]), "rate": float(rates[ri]),
            "value": float(vals[ri, gi]), "fallback": "full"}


def served_gaps(records: Sequence[Dict], evaluated: List) -> Dict[str, float]:
    """The two numbers a plan is judged by, each the worst over the sample.

    ``value_rel_gap``: how far the served value lies from the reference's
    value at the served ``(n_c, rate)``, relative.  ``regret_rel``: how far
    the reference's value there lies above the reference's best plan,
    relative.  A plan whose point is not on the request's grid reads
    infinity in both."""
    gap = regret = 0.0
    for rec, (grid, rates, vals) in zip(records, evaluated):
        ri = np.flatnonzero(np.asarray(rates, np.float64) == rec["rate"])
        gi = np.flatnonzero(grid == rec["n_c"])
        if ri.size == 0 or gi.size == 0:
            return {"value_rel_gap": float("inf"), "regret_rel": float("inf")}
        ref = float(vals[ri[0], gi[0]])
        best = float(np.min(vals))
        gap = max(gap, abs(rec["value"] - ref) / abs(ref))
        regret = max(regret, (ref - best) / abs(best))
    return {"value_rel_gap": gap, "regret_rel": regret}
