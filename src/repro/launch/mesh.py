"""Mesh construction — the one place the repo builds a device mesh.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods x 256 chips as (pod=2, data=16, model=16).

Every axis is ``AxisType.Auto``. ``jax.make_mesh`` defaults to
``Explicit`` axes, under which ``with_sharding_constraint`` becomes an
assert and gathers over a sharded axis raise ``ShardingTypeError``; the
activation constraints (``repro.dist.act``) and the GSPMD paths are
written for auto sharding.

Functions (not module-level constants) so importing never touches jax
device state.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(num_devices: int | None = None, axes=("data",)):
    """Small mesh over the first ``num_devices`` devices (paper-scale: 8
    workers); two axes split the devices roughly evenly."""
    n = num_devices or len(jax.devices())
    devices = jax.devices()[:n]
    if len(axes) == 1:
        return make_mesh((n,), axes, devices=devices)
    a = int(math.sqrt(n))
    while n % a:
        a -= 1
    return make_mesh((n // a, a), axes, devices=devices)
