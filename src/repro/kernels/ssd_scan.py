"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Grid: (batch, heads, num_chunks) — the chunk dimension is innermost and
sequential, so the inter-chunk SSM state (head_dim x state_dim) is carried
in VMEM scratch across chunk steps.  Each step computes the within-chunk
quadratic (attention-like) term on the MXU plus the state contribution, and
updates the running state — one HBM pass over x/B/C/dt.

Layout: heads before sequence, so every block ends in ``(chunk, feature)``
(or ``(1, chunk)`` for ``dt``), which the TPU's (8, 128) tiling accepts;
``chunk`` should be a multiple of 128 on the chip.  The per-head decay
``a`` lives in SMEM.  The kernel reads ``dt`` as a row and derives the
column form and both prefix sums with masked reductions over the
(chunk, chunk) tile, so no vector transpose or scan is lowered.

VMEM working set per step (chunk=256, P=64, N=128, f32):
  x (256x64) + B,C (2x256x128) + M (256x256) + state (64x128) ~ 0.6 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref, state_scr,
                *, chunk: int, num_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)           # (chunk, P)
    dt_row = dt_ref[0, 0].astype(jnp.float32)     # (1, chunk)
    a = a_ref[pl.program_id(1)]                   # decay rate (negative)
    bm = b_ref[0, 0].astype(jnp.float32)          # (chunk, N)
    cm = c_ref[0, 0].astype(jnp.float32)          # (chunk, N)

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = row >= col
    dt = jnp.sum(jnp.where(row == col, dt_row, 0.0), axis=1,
                 keepdims=True)                   # (chunk, 1)
    da = dt * a                                   # log-decay per step
    da_row = dt_row * a                           # (1, chunk)
    cum = jnp.sum(jnp.where(causal, da_row, 0.0), axis=1,
                  keepdims=True)                  # (chunk, 1) inclusive
    cum_row = jnp.sum(jnp.where(row <= col, da, 0.0), axis=0,
                      keepdims=True)              # (1, chunk)
    total = jnp.sum(da_row, axis=1, keepdims=True)  # (1, 1)
    xbar = x * dt

    # intra-chunk: M[t, s] = exp(cum_t - cum_s) * (C_t . B_s) for s <= t
    decay = cum - cum_row
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())))   # (chunk, chunk)
    m = jnp.where(causal, cb * jnp.exp(decay), 0.0)
    y_intra = jax.lax.dot(m, xbar)                                # (chunk, P)

    # inter-chunk: y_inter[t] = exp(cum_t) * C_t . state^T
    state = state_scr[...]                                        # (P, N)
    y_inter = jnp.exp(cum) * jax.lax.dot_general(
        cm, state, (((1,), (1,)), ((), ())))                      # (chunk, P)

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: S <- exp(total) * S + sum_s exp(total - cum_s) B_s (x) xbar_s
    w = jnp.exp(total - cum)                                      # (chunk, 1)
    state_new = jnp.exp(total) * state + jax.lax.dot_general(
        xbar, w * bm, (((0,), (0,)), ((), ())))                   # (P, N)
    state_scr[...] = state_new

    @pl.when(ic == num_chunks - 1)
    def _final():
        st_ref[0, 0] = state_new.astype(st_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_fwd(x, dt, a, b, c, *, chunk: int = 256, interpret: bool = False):
    """Heads-first layout: x (B, H, L, P); dt (B, H, 1, L); a (H,);
    b, c (B, H, L, N) (groups pre-broadcast to heads) -> y (B, H, L, P),
    state (B, H, P, N).
    """
    bsz, h, l, p = x.shape
    n = b.shape[-1]
    chunk = min(chunk, l)
    assert l % chunk == 0, (l, chunk)
    nc = l // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk, num_chunks=nc)
    seq_block = lambda width: pl.BlockSpec(
        (1, 1, chunk, width), lambda b_, h_, ic: (b_, h_, ic, 0))
    y, state = pl.pallas_call(
        kernel,
        grid=(bsz, h, nc),
        in_specs=[
            seq_block(p),
            pl.BlockSpec((1, 1, 1, chunk), lambda b_, h_, ic: (b_, h_, 0, ic)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            seq_block(n),
            seq_block(n),
        ],
        out_specs=[
            seq_block(p),
            pl.BlockSpec((1, 1, p, n), lambda b_, h_, ic: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, l, p), jnp.float32),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x, dt, a.astype(jnp.float32), b, c)
    return y, state
