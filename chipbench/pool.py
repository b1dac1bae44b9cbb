"""Batches made on the device from the seed.

Batch ``b`` of a cell is drawn from ``fold_in(key, b)`` alone, so the
program's sharded pool and the reference's copy of one batch hold the
same rows whatever their placement.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def batch(key, b: int, rows: int, side: int, classes: int) -> dict:
    kb = jax.random.fold_in(key, b)
    return {"images": jax.random.normal(jax.random.fold_in(kb, 0),
                                        (rows, side, side, 3), jnp.float32),
            "labels": jax.random.randint(jax.random.fold_in(kb, 1), (rows,),
                                         0, classes, jnp.int32)}


def make_pool(key, count: int, rows: int, side: int, classes: int,
              sharding=None) -> list:
    """``count`` distinct batches of ``rows`` images in one jitted call."""
    def make(key):
        return [batch(key, b, rows, side, classes) for b in range(count)]

    out = None
    if sharding is not None:
        out = [{"images": sharding, "labels": sharding}] * count
    return jax.jit(make, out_shardings=out)(key)
