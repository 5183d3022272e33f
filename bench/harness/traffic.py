"""The one general traffic generator: every mix is a JSON file of
parameters under ``bench/traffic/`` that this module reads.

Requests follow the planning service's own synthetic catalogue (the
paper's ranges, arXiv:1906.04488 Sec. V-VI): ``N`` uniform in
``[N_lo, N_hi)``, ``T = U(T_over_N) * N``, ``n_o`` uniform, ``tau_p`` and
the device count ``D`` from lists, and a link family drawn uniformly
from ``links`` with its parameters uniform in their ranges (a range
bound given as a name is the value already drawn for that parameter).
No request repeats another, so every one is solved.

The (objective, grid mode) pairs of ``mix`` come in equal numbers, in
an order drawn from the seed.  An open loop's arrival gaps are one fixed
set of exponential gaps, drawn from ``arrivals.gap_seed`` and scaled to
the window, permuted by the run's seed: every seed sends the same number
of requests at the same mean rate, in another order.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def _draw_link(rng, family: str, ranges: Dict) -> Dict:
    link = {"family": family}
    for name, (lo, hi) in ranges.items():
        lo = link[lo] if isinstance(lo, str) else lo
        link[name] = float(rng.uniform(lo, hi))
    return link


def draw_request(rng, p: Dict) -> Dict:
    N = int(rng.integers(p["N"][0], p["N"][1]))
    req = {"N": N,
           "T": float(rng.uniform(*p["T_over_N"])) * N,
           "n_o": float(rng.uniform(*p["n_o"])),
           "tau_p": float(rng.choice(p["tau_p"])),
           "D": int(rng.choice(p["D"]))}
    families = list(p["links"])
    family = families[int(rng.integers(len(families)))]
    req["link"] = _draw_link(rng, family, p["links"][family])
    req["rates"] = [float(r) for r in p["rates"]]
    return req


def _block(rng, traffic: Dict, count: int) -> List[Dict]:
    mix = traffic["mix"]
    pairs = rng.permutation(np.arange(count) % len(mix))
    out = []
    for k in range(count):
        req = draw_request(rng, traffic["requests"])
        req["objective"], req["grid_mode"] = mix[int(pairs[k])]
        out.append(req)
    return out


def requests(traffic: Dict, seed: int, count: int,
             stream: int = 1) -> List[Dict]:
    """``count`` requests of the mix, each naming its objective and mode;
    ``stream`` keeps the draws of one run's phases apart."""
    return _block(np.random.default_rng([int(seed), stream]), traffic, count)


def open_schedule(traffic: Dict, seed: int, seconds: float) -> np.ndarray:
    """Send times (s from the window's start) of an open loop."""
    arr = traffic["arrivals"]
    n = int(round(float(arr["rate_per_s"]) * seconds))
    gaps = np.random.default_rng(int(arr["gap_seed"])).exponential(1.0, n)
    gaps *= seconds / gaps.sum()
    gaps = np.random.default_rng([int(seed), 2]).permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
