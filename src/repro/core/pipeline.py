"""The pipelined streaming-SGD trainer (paper Secs. 2 & 5).

``run_pipelined_sgd`` simulates the exact protocol on the ridge-regression
task: blocks of ``n_c`` samples arrive every ``n_c + n_o`` time units while
SGD updates run every ``tau_p`` units on the prefix received so far.  The
whole timeline executes as one ``jax.lax.scan`` over update slots — fully
jitted, so the Fig. 3/4 sweeps run in seconds on CPU.

``n_c = N`` recovers the sequential transmit-everything-first baseline the
paper argues against (single block, single overhead, no pipelining).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.protocol import BlockSchedule


# ---------------------------------------------------------------------------
# Ridge-regression objective (paper Sec. 5)
# ---------------------------------------------------------------------------


def ridge_loss_full(w, X, y, lam):
    """L(w) = (1/N) sum (w^T x - y)^2 + (lam/N)||w||^2   (paper's ell summed).

    The matvec runs at HIGHEST precision: the TPU's default f32 matmul
    rounds its operands to bfloat16, which would change the objective."""
    r = jnp.matmul(X, w, precision=jax.lax.Precision.HIGHEST) - y
    n = X.shape[0]
    return jnp.mean(r ** 2) + lam / n * jnp.sum(w ** 2)


def ridge_losses(W, X, y, lam):
    """:func:`ridge_loss_full` of every weight vector in ``W`` (..., d).

    The squared residuals are summed one sample at a time, elementwise
    over the batch: a vmapped reduction adds in an order that changes with
    the batch shape (on the CPU), which would let a Monte-Carlo lane's
    loss depend on how many lanes were solved beside it."""
    n = X.shape[0]

    def add_sample(total, xy):
        x, yv = xy
        r = ridge_dot(W, x) - yv
        return total + r * r, None

    sq, _ = jax.lax.scan(add_sample, jnp.zeros(W.shape[:-1], W.dtype),
                         (X, y))
    return sq / n + lam / n * ridge_dot(W, W)


def ridge_dot(a, b, axis: int = -1, keepdims: bool = False,
              ordered: bool = True):
    """``sum_k a_k b_k`` over ``axis``: the ``w^T x`` of every ridge SGD
    step.

    ``ordered`` adds the products left to right, an order XLA's dot and a
    Mosaic reduction would each choose differently; a compiled Pallas
    kernel and the ``lax.scan`` engines agree bitwise on it.  Otherwise it
    is one ``einsum``: XLA:CPU contracts a chain of adds into FMAs
    differently in each fusion, but evaluates the ``einsum`` alike in
    every engine.  A Monte-Carlo solve picks one form for all its
    engines (:func:`repro.fleet.objective_kernels._mc_solve_for`)."""
    if not ordered:
        axis = axis % a.ndim
        sub = "abcdefgh"[:a.ndim]
        out = jnp.einsum(f"{sub},{sub}->{sub.replace(sub[axis], '')}", a, b,
                         precision=jax.lax.Precision.HIGHEST)
        return jnp.expand_dims(out, axis) if keepdims else out
    prods = a * b

    def term(k):
        return jax.lax.index_in_dim(prods, k, axis, keepdims=keepdims)

    acc = term(0)
    for k in range(1, prods.shape[axis]):
        acc = acc + term(k)
    return acc


def ridge_grad_sample(w, x, yv, lam, n, ordered: bool = True):
    """grad of ell(w, (x,y)) = (w^T x - y)^2 + (lam/N)||w||^2."""
    return (2.0 * (ridge_dot(w, x, ordered=ordered) - yv) * x
            + 2.0 * lam / n * w)


# ---------------------------------------------------------------------------
# Pipelined trainer
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class StreamResult:
    w_final: np.ndarray
    final_loss: float
    loss_trace: np.ndarray    # loss every `record_every` updates
    trace_times: np.ndarray   # normalised times of the trace entries
    delivered: int


def _scan_core(X, y, perm, w0, alpha, lam, key, *, n_c: int, n_o: float,
               T: float, tau_p: float, record_every: int):
    n, d = X.shape
    plan = BlockSchedule(N=n, n_c=n_c, n_o=n_o, T=T, tau_p=tau_p)
    total = plan.total_updates
    # samples available at each update slot (host-computed static timeline)
    avail = jnp.asarray(plan.updates_timeline(), jnp.int32)

    Xs = X[perm]  # streaming order: uniform w/o replacement == random perm
    ys = y[perm]

    def step(carry, inp):
        w, k = carry
        a_t = inp
        k, sub = jax.random.split(k)
        idx = jax.random.randint(sub, (), 0, jnp.maximum(a_t, 1))
        g = ridge_grad_sample(w, Xs[idx], ys[idx], lam, n)
        w_new = w - alpha * g
        w = jnp.where(a_t > 0, w_new, w)  # no data yet -> no update
        return (w, k), ridge_loss_full(w, X, y, lam)

    (w_fin, _), losses = jax.lax.scan(step, (w0, key), avail)
    # subsample the trace
    rec = losses[record_every - 1::record_every]
    return w_fin, ridge_loss_full(w_fin, X, y, lam), rec


_run_scan = partial(jax.jit, static_argnames=(
    "n_c", "n_o", "T", "tau_p", "record_every"))(_scan_core)


def mc_run_key(seed0, r, seed_stream: str = "fold_in"):
    """Per-run PRNG key of the Monte-Carlo seed loop.

    ``"fold_in"`` (default) derives run ``r`` as ``fold_in(PRNGKey(seed0),
    r)`` — distinct (seed0, r) pairs can never share a key.  ``"legacy"``
    reproduces the historical ``PRNGKey(seed0 + 97 r)`` streams, which
    alias across nearby base seeds (seed0=0 run 1 == seed0=97 run 0); it
    exists only to pin old results, e.g. the fleet parity suite.
    """
    if seed_stream == "legacy":
        return jax.random.PRNGKey(seed0 + 97 * r)
    if seed_stream != "fold_in":
        raise ValueError(f"unknown seed_stream {seed_stream!r}")
    return jax.random.fold_in(jax.random.PRNGKey(seed0), r)


@partial(jax.jit,
         static_argnames=("n_c", "n_o", "T", "tau_p", "n_runs",
                          "seed_stream"))
def _mc_final_losses(X, y, alpha, lam, seed0, *, n_c: int, n_o: float,
                     T: float, tau_p: float, n_runs: int,
                     seed_stream: str = "fold_in"):
    """Final loss for ``n_runs`` independent seeds as ONE vmapped scan —
    the Monte-Carlo seed loop of the experimental-optimum search runs
    batched instead of one jitted call per seed."""
    n, d = X.shape

    def one(r):
        key = mc_run_key(seed0, r, seed_stream)
        kp, kw, ks = jax.random.split(key, 3)
        perm = jax.random.permutation(kp, n)
        w0 = jax.random.normal(kw, (d,))
        _, floss, _ = _scan_core(X, y, perm, w0, alpha, lam, ks, n_c=n_c,
                                 n_o=n_o, T=T, tau_p=tau_p,
                                 record_every=1_000_000_000)
        return floss

    return jax.vmap(one)(jnp.arange(n_runs))


def run_pipelined_sgd(X, y, *, n_c: int, n_o: float, T: float,
                      tau_p: float = 1.0, alpha: float = 1e-4,
                      lam: float = 0.05, seed: int = 0,
                      w0: Optional[np.ndarray] = None,
                      record_every: int = 256,
                      key=None) -> StreamResult:
    n, d = X.shape
    if key is None:
        key = jax.random.PRNGKey(seed)
    kp, kw, ks = jax.random.split(key, 3)
    perm = jax.random.permutation(kp, n)
    if w0 is None:
        w0 = jax.random.normal(kw, (d,))  # paper: i.i.d. N(0, 1) init
    plan = BlockSchedule(N=n, n_c=n_c, n_o=n_o, T=T, tau_p=tau_p)
    w_fin, floss, rec = _run_scan(
        jnp.asarray(X), jnp.asarray(y), perm, jnp.asarray(w0),
        alpha, lam, ks, n_c=int(n_c), n_o=float(n_o), T=float(T),
        tau_p=float(tau_p), record_every=int(record_every))
    times = (np.arange(len(rec)) + 1) * record_every * tau_p
    return StreamResult(
        w_final=np.asarray(w_fin), final_loss=float(floss),
        loss_trace=np.asarray(rec), trace_times=times,
        delivered=plan.available_at(T))


def average_final_loss(X, y, *, n_c: int, n_o: float, T: float,
                       n_runs: int = 5, **kw) -> float:
    """Monte-Carlo average of the final training loss (paper's experimental
    optimum search computes this per candidate n_c).

    The seeds run as a single ``jax.vmap``-batched scan rather than a
    Python loop of jitted calls.  Per-run keys come from
    :func:`mc_run_key` — collision-free ``fold_in`` streams by default,
    ``seed_stream="legacy"`` for the historical ``seed0 + 97 r`` keys.
    Passing ``w0`` falls back to the sequential path, which the batched
    kernel does not support.
    """
    seed0 = kw.pop("seed", 0)
    seed_stream = kw.pop("seed_stream", "fold_in")
    if kw.get("w0") is not None:
        losses = [run_pipelined_sgd(
            X, y, n_c=n_c, n_o=n_o, T=T,
            key=mc_run_key(seed0, r, seed_stream), **kw).final_loss
                  for r in range(n_runs)]
        return float(np.mean(losses))
    kw.pop("w0", None)
    kw.pop("record_every", None)  # only affects the (unused) trace
    losses = _mc_final_losses(
        jnp.asarray(X), jnp.asarray(y), kw.pop("alpha", 1e-4),
        kw.pop("lam", 0.05), seed0, n_c=int(n_c), n_o=float(n_o),
        T=float(T), tau_p=float(kw.pop("tau_p", 1.0)), n_runs=int(n_runs),
        seed_stream=str(seed_stream))
    if kw:
        raise TypeError(f"unexpected keyword arguments: {sorted(kw)}")
    return float(np.mean(np.asarray(losses)))
