"""Production mesh construction.

A *function*, not a module-level constant, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before first init).
"""
from __future__ import annotations

import math

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (256 chips / pod) single-pod, or 2x16x16 (512 chips) multi-pod.

    Every axis is ``Auto``: the model code places activations with
    ``with_sharding_constraint``, which only names Auto axes (``make_mesh``
    defaults to Explicit).

    Axes: ('pod', 'data', 'model') multi-pod / ('data', 'model') single-pod.
    The 'pod' axis carries pure DP (or pipeline stages with --pp); 'model'
    is the fast intra-pod TP/EP/SP axis.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devs)} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "(launch/dryrun.py does this)")
    return jax.make_mesh(shape, axes, devices=devs[:n],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for tests (8 fake devices)."""
    n = math.prod(shape)
    return jax.make_mesh(shape, axes, devices=jax.devices()[:n],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
