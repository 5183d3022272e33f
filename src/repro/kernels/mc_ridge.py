"""Pallas kernel for the Monte-Carlo pipelined-SGD ridge simulation.

One call advances EVERY simulation lane (one lane per scenario x rate x
grid point, as laid out by the fleet Monte-Carlo solve in
:mod:`repro.fleet.objective_kernels`) through one SLAB of update slots.
The host precomputes, per slab, the two (slab, L) tables the timeline
fully determines — the sampled training-row index ``ix`` and the
update-live mask ``m`` — so the kernel body is pure f32 training math
with no RNG and no f64: the f64 timeline / f32 training split stays on
the host side of the call.

Layout: lanes-LAST.  The weight block is ``(d, block_l) = (8, 128)`` —
exactly one float32 TPU tile — and every per-lane scalar is a
``(1, block_l)`` row, so all elementwise work is lane-aligned.  The
training-row gather runs as a one-hot matmul on the MXU
(``Xs^T @ onehot``): for a 0/1 f32 one-hot this is BITWISE equal to the
``Xs[ix]`` gather (each output element is one exact product plus exact
zeros), which is what lets interpret-mode tests pin the kernel against
the ``lax.scan`` reference bit-for-bit.

Grid: one program per 128-lane block; lanes are padded to a block
multiple with ``m = 0`` rows (a dead lane's weights pass through both
update forms unchanged).  Each block steps through only as many slots
as its lanes can use: given every lane's deadline ``hi``, the wrapper
reduces the slab's slots before it to one count per block and hands the
counts to the kernel as a scalar prefetch, so a block whose lanes are
all past their deadline runs no slot at all.  A masked slot leaves the
weights unchanged, so stopping early changes no bit.

``fused=True`` applies the update in the algebraically-rearranged
affine form ``W <- c1 * W + c2 * xr`` used by the common-random-numbers
engine; ``fused=False`` replicates
:func:`repro.core.pipeline.ridge_grad_sample`'s op order exactly
(gradient, step, ``where``-mask), matching the exact-RNG scan engine.

The lane dot ``w^T x`` is :func:`repro.core.pipeline.ridge_dot` in the
form the calling solve chose for all its engines.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.pipeline import ridge_dot


#: The one-hot gather must reproduce ``Xs[ix]`` exactly.  The TPU's
#: default f32 matmul rounds its operands to bfloat16; HIGHEST keeps
#: every f32 bit of the gathered rows.
_EXACT = jax.lax.Precision.HIGHEST

#: Block index 0 as int32: the serving solve calls the kernel under
#: ``jax.enable_x64``, where a bare ``0`` would be int64, which Mosaic
#: cannot lower.
_I0 = np.int32(0)

#: Lanes per program: one (d, 128) float32 weight tile.
BLOCK_L = 128


def _mc_ridge_kernel(steps_ref, xs_ref, ys_ref, ix_ref, m_ref, w_ref,
                     o_ref, *, n: int, alpha: float, lam: float,
                     fused: bool, ordered: bool):
    Xs = xs_ref[...]                                   # (d, n) f32
    ys = ys_ref[...]                                   # (1, n) f32
    c_reg = np.float32(2.0 * alpha * lam / n)
    c_2a = np.float32(-2.0 * alpha)

    def body(j, W):
        bl = W.shape[1]
        ixr = ix_ref[pl.ds(j, 1), :]                        # (1, bl) i32
        mr = m_ref[pl.ds(j, 1), :]                          # (1, bl) f32
        iota = jax.lax.broadcasted_iota(jnp.int32, (n, bl), 0)
        oh = (iota == ixr).astype(jnp.float32)              # (n, bl)
        xr = jnp.dot(Xs, oh, precision=_EXACT,
                     preferred_element_type=jnp.float32)    # (d, bl)
        yr = jnp.dot(ys, oh, precision=_EXACT,
                     preferred_element_type=jnp.float32)    # (1, bl)
        dot = ridge_dot(W, xr, axis=0, keepdims=True,
                        ordered=ordered)                    # (1, bl)
        if fused:
            c1 = 1.0 - mr * c_reg
            c2 = mr * c_2a * (dot - yr)
            return W * c1 + xr * c2
        g = 2.0 * (dot - yr) * xr + 2.0 * lam / n * W
        return jnp.where(mr > 0.0, W - alpha * g, W)

    steps = steps_ref[pl.program_id(0)]
    o_ref[...] = jax.lax.fori_loop(np.int32(0), steps, body, w_ref[...])


def block_steps(hi, j0, slab: int, block_l: int = BLOCK_L):
    """Slots each ``block_l``-lane block of one slab runs: the most any of
    its lanes has before its deadline, ``clip(hi - j0, 0, slab)``.

    ``hi``: (L,) int32 per-lane deadline (a lane updates only at slots
    below it); ``j0``: the slab's first slot.  Lanes past ``L`` up to the
    block multiple count as deadline 0.  Returns (blocks,) int32."""
    hi = jnp.asarray(hi, jnp.int32)
    left = jnp.clip(hi - jnp.asarray(j0, jnp.int32), np.int32(0),
                    np.int32(slab))
    left = jnp.pad(left, (0, (-left.shape[0]) % block_l))
    return jnp.max(left.reshape(-1, block_l), axis=1)


@functools.partial(jax.jit, static_argnames=("alpha", "lam", "fused",
                                             "interpret", "ordered",
                                             "block_l"))
def mc_ridge_slab(W, Xs, ys, ix, m, hi=None, j0=0, *, alpha: float,
                  lam: float, fused: bool, interpret: bool = False,
                  ordered: bool = True, block_l: int = BLOCK_L):
    """Advance all lanes through one slab of update slots.

    ``W``: (L, d) f32 per-lane weights; ``Xs``: (n, d) f32 permuted
    training rows; ``ys``: (n,) f32 targets; ``ix``: (slab, L) int32
    sampled row per (slot, lane); ``m``: (slab, L) f32, 1.0 where the
    lane updates at that slot.  ``hi``: optional (L,) int32 per-lane
    deadline and ``j0`` the slab's first slot: each block then stops at
    its lanes' last slot before their deadline (:func:`block_steps`);
    ``m`` must be 0 from each lane's deadline on.  Without ``hi`` every
    block runs the whole slab.  Returns the updated (L, d) weights.
    ``ordered`` is :func:`~repro.core.pipeline.ridge_dot`'s lane-dot form;
    Mosaic lowers only the ordered one.
    """
    L, d = W.shape
    n = Xs.shape[0]
    slab = ix.shape[0]
    pad = (-L) % block_l
    Wt = W.T                                           # (d, L) lanes-last
    if pad:
        Wt = jnp.pad(Wt, ((0, 0), (0, pad)))
        ix = jnp.pad(ix, ((0, 0), (0, pad)))
        m = jnp.pad(m, ((0, 0), (0, pad)))             # dead lanes: m = 0
    lp = L + pad
    if hi is None:
        steps = jnp.full((lp // block_l,), slab, jnp.int32)
    else:
        steps = block_steps(hi, j0, slab, block_l)

    kernel = functools.partial(
        _mc_ridge_kernel, n=n, alpha=float(alpha), lam=float(lam),
        fused=fused, ordered=ordered)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(lp // block_l,),
            in_specs=[
                pl.BlockSpec((d, n), lambda i, s: (_I0, _I0)),
                pl.BlockSpec((1, n), lambda i, s: (_I0, _I0)),
                pl.BlockSpec((slab, block_l), lambda i, s: (_I0, i)),
                pl.BlockSpec((slab, block_l), lambda i, s: (_I0, i)),
                pl.BlockSpec((d, block_l), lambda i, s: (_I0, i)),
            ],
            out_specs=pl.BlockSpec((d, block_l), lambda i, s: (_I0, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((d, lp), jnp.float32),
        interpret=interpret,
    )(steps, Xs.T.astype(jnp.float32), ys[None, :].astype(jnp.float32),
      ix.astype(jnp.int32), m.astype(jnp.float32), Wt)
    return out[:, :L].T
