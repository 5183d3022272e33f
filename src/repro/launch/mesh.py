"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init,
and smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the model code places activations
    with ``with_sharding_constraint`` hints, which Explicit axes (the
    default) reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 2, pod: int = 1):
    """Small mesh over however many (host) devices exist — for tests."""
    if pod > 1:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


@dataclass(frozen=True)
class ChipPeaks:
    """Per-chip roofline denominators."""

    flops_bf16: float       # FLOP/s
    hbm_bandwidth: float    # bytes/s
    ici_bandwidth: float    # bytes/s per link, one direction


#: Peaks by ``jax.Device.device_kind``.  TPU v5e: Google Cloud
#: documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM,
#: 1,600 Gbit/s of interconnect per chip over four links, ~50 GB/s each).
PEAKS = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bandwidth=819e9,
                             ici_bandwidth=50e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The peaks of one chip of this kind; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
