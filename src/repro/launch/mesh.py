"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state (smoke tests must see 1 device; only dryrun.py sets
the 512-placeholder-device XLA flag).
"""
from __future__ import annotations

import math

import jax


def _auto(n: int):
    """Auto axis types: GSPMD propagates shardings from the jit boundaries
    and ``with_sharding_constraint`` pins (``jax.make_mesh`` defaults to
    Explicit axes, which every program here would then have to annotate)."""
    return (jax.sharding.AxisType.Auto,) * n


def _validate_shape(shape, devices, *, what):
    """Raise a readable error before jax.make_mesh fails opaquely."""
    n = len(devices)
    want = math.prod(shape)
    if any(s <= 0 for s in shape):
        raise ValueError(f"{what}: mesh shape {shape} has a non-positive axis")
    if want != n:
        raise ValueError(
            f"{what}: mesh shape {shape} needs {want} devices but "
            f"{n} are available; pick (dp, tp) with dp*tp == {n}"
        )


def make_production_mesh(shape=(16, 16), *, multi_pod: bool = False):
    """Data x model mesh; default 16x16 = 256 chips/pod.

    ``shape`` is the explicit ``(dp, tp)`` pair (or ``(pods, dp, tp)`` when
    ``multi_pod``); it is validated against the visible device count so a
    mismatch raises a clear error instead of an opaque jax.make_mesh failure.
    """
    if multi_pod:
        shape = (2, *shape) if len(shape) == 2 else tuple(shape)
        axes = ("pod", "data", "model")
    else:
        shape = tuple(shape)
        axes = ("data", "model")
    if len(shape) != len(axes):
        raise ValueError(
            f"make_production_mesh: shape {shape} must have {len(axes)} axes {axes}"
        )
    _validate_shape(shape, jax.devices(), what="make_production_mesh")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_test_mesh(devices=None, shape=None):
    """Small ("data", "model") mesh over ``devices`` (CPU tests).

    Default shape is ``(1, n)`` — all devices on the model (tensor-parallel)
    axis. Pass an explicit ``(dp, tp)`` to split them; the product must match
    the device count.
    """
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if shape is None:
        shape = (1, n)
    shape = tuple(shape)
    if len(shape) != 2:
        raise ValueError(f"make_test_mesh: shape {shape} must be (dp, tp)")
    _validate_shape(shape, devices, what="make_test_mesh")
    return jax.make_mesh(shape, ("data", "model"), axis_types=_auto(2),
                         devices=devices)
