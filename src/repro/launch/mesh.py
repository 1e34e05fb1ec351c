"""Production meshes.

Single pod:  (16, 16) over ("data", "model")   = 256 chips (TPU v5e pod)
Multi-pod :  (2, 16, 16) over ("pod", "data", "model") = 512 chips.

The "pod" axis composes with "data" for batch/FSDP sharding so only
gradient/weight-gather traffic crosses the (slower) DCN between pods;
all TP collectives stay on intra-pod ICI.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(num_devices: int = 8):
    """Small mesh over however many (host) devices exist — for tests."""
    n = min(num_devices, len(jax.devices()))
    model = 1
    for m in (4, 2, 1):
        if n % m == 0:
            model = m
            break
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
