"""Production mesh builders.

Functions, not module-level constants — importing this module never touches
jax device state.  The production target is TPU v5e: 256 chips per pod in a
16x16 mesh; the multi-pod configuration is 2 pods = 512 chips.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import math
    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"production mesh needs {need} devices, found {len(devs)}; "
            "the dry-run entrypoint sets "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512")
    return jax.make_mesh(shape, axes, devices=devs[:need],
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever devices exist (CPU tests / examples)."""
    n = jax.device_count()
    assert n % model_parallel == 0
    return jax.make_mesh((n // model_parallel, model_parallel),
                         ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


# Hardware constants for the roofline analysis (TPU v5e).
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link
CHIP_HBM_BYTES = 16 * 1024 ** 3
