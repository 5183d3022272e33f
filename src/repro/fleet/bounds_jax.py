"""``jax.numpy`` port of the Corollary-1 evaluator (eqs. 14-15).

Algebraically identical to the reference
:func:`repro.core.bounds.corollary1_bound` — the numpy implementation stays
the REFERENCE semantics; this port exists so the fleet planner can evaluate
the bound for thousands of scenarios in one jitted, device-sharded call.
Any change to the numpy math must land here too (the fleet property tests
enforce agreement to ~1e-12 relative).

Two deliberate restructurings for CPU throughput (the kernel is
transcendental-bound; these roughly halve its cost at identical results up
to float64 rounding):

  * powers become single exponentials of precomputed log-contractions:
    ``r ** n_p == exp(n_p log r)`` — and ``log r`` is clamped at the
    smallest normal so ``r == 0`` still yields ``rp == 0`` for ``n_p >= 1``
    and ``1`` for ``n_p == 0``, matching numpy's ``0 ** k``;
  * ONE geometric sum serves both regimes: each grid point only ever reads
    the sum with its own regime's term count (``B - 1`` in regime (a),
    ``ceil(B_d) - 1`` in regime (b)), so the two reference ``_geom_sum``
    calls collapse into one via a ``where`` on the count.

Quotients feeding ``floor``/``ceil`` (block counts, update counts) keep the
exact division of the reference — regime boundaries are knife-edges where
an ulp flips a whole block, so those must round identically to numpy.
"""
from __future__ import annotations

import jax.numpy as jnp


def corollary1_bound_jax(n_c, *, N, T, n_o, tau_p, sigma, e0, contraction):
    """Eq. (14)/(15) on broadcastable jnp arrays.

    ``n_c``/``n_o`` carry the grid axes, ``N``/``T``/``tau_p`` the
    per-scenario axes; ``sigma``/``e0``/``contraction`` are the three
    scalars from :class:`~repro.core.bounds.BoundConstants`
    (``variance_floor``, ``init_gap``, ``contraction``), passed as plain
    arguments so a jitted caller never retraces on new constants.  Call
    under ``jax.enable_x64(True)`` for float64 agreement with
    the reference.
    """
    n_c = jnp.asarray(n_c)
    n_o = jnp.asarray(n_o)
    dur = n_c + n_o
    B_d = N / n_c
    invB_d = n_c / N                      # bound terms only ever DIVIDE by B_d
    B = jnp.floor(T / dur)                # whole blocks that fit
    n_p = jnp.floor(dur / tau_p)          # SGD updates per block
    full = T > B_d * dur                  # regime (b)

    lr = jnp.log(jnp.maximum(contraction, jnp.finfo(dur.dtype).tiny))
    a = n_p * lr                          # log of the per-block contraction
    rp = jnp.exp(a)                       # r ** n_p
    tie = jnp.abs(1.0 - rp) < 1e-15
    inv_1mrp = 1.0 / jnp.where(tie, 1.0, 1.0 - rp)

    # sum_{l=1}^{k} rp^l with the regime's own term count k (eq. 14 wants
    # B - 1 terms, eq. 15 wants ceil(B_d) - 1): closed form
    # rp (1 - rp^k) / (1 - rp), degenerating to k when rp == 1, 0 when k <= 0
    k = jnp.where(full,
                  jnp.maximum(jnp.ceil(B_d) - 1.0, 0.0),
                  jnp.maximum(B - 1.0, 0.0))
    s_g = jnp.where(k <= 0, 0.0,
                    jnp.where(tie, k,
                              rp * (1.0 - jnp.exp(a * k)) * inv_1mrp))

    # ---- regime (a): T <= B_d (n_c + n_o)   (eq. 14) ----------------------
    frac = jnp.clip((B - 1.0) * invB_d, 0.0, 1.0)
    bound_a = sigma * frac + (1.0 - frac) * e0 + (e0 - sigma) * s_g * invB_d

    # ---- regime (b): T > B_d (n_c + n_o)    (eq. 15) ----------------------
    tau_l = jnp.maximum(T - B_d * dur, 0.0)
    n_l = jnp.floor(tau_l / tau_p)
    bound_b = sigma + jnp.exp(lr * n_l) * (e0 - sigma) * (1.0 + s_g) * invB_d

    return jnp.where(full, bound_b, bound_a)
