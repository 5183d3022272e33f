"""Jit'd wrappers exposing the Pallas kernels in model-layout form.

``interpret`` defaults to the backend: on the CPU (the test suite) the
kernel bodies run under the Pallas interpreter; on any other backend they
compile with Mosaic for the TPU.  Nothing falls back to the interpreter
on an accelerator unasked — a backend Mosaic cannot target fails.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.ssd_scan import ssd_scan_fwd


def _default_interpret() -> bool:
    return jax.default_backend() == "cpu"


def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    scale=None, q_block=512, kv_block=512, interpret=None):
    """Model layout: q (B, S, H, D); k, v (B, S, Hkv, D) -> (B, S, H, D)."""
    interpret = _default_interpret() if interpret is None else interpret
    out = flash_attention_fwd(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=causal, window=window, softcap=softcap, scale=scale,
        q_block=q_block, kv_block=kv_block, interpret=interpret)
    return out.transpose(0, 2, 1, 3)


def ssd_scan(x, dt, a, b, c, *, chunk=256, interpret=None):
    """Model layout: x (B, L, H, P); b, c (B, L, G, N) (groups broadcast)."""
    interpret = _default_interpret() if interpret is None else interpret
    h = x.shape[2]
    g = b.shape[2]
    if g != h:
        b = jnp.repeat(b, h // g, axis=2)
        c = jnp.repeat(c, h // g, axis=2)
    heads_first = lambda t: jnp.moveaxis(t, 2, 1)
    y, state = ssd_scan_fwd(heads_first(x), jnp.moveaxis(dt, 1, 2)[:, :, None],
                            a, heads_first(b), heads_first(c), chunk=chunk,
                            interpret=interpret)
    return jnp.moveaxis(y, 1, 2).astype(x.dtype), state
