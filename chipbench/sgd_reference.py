"""The plain reference of a BSP training step with momentum SGD.

It follows the first steps of a cell from the seed alone: the weights from
the configuration's ``init_params``, the batches from ``pool.batch``, the
dropout keys as ``train()`` derives them (``fold_in(key(seed + 1), step)``,
then ``fold_in(., worker)``). Each worker's rows are cut into the plan's
microbatches, and the gradient is the mean over all of them, as
synchronous data parallelism defines it. The update is momentum SGD with
weight decay on leaves of two or more dimensions, in float32. It imports
nothing of the program.

``compute`` below the reference's float32 gives a control: the same
steps one precision below the configuration's (``refops.Ops``). ``fault`` plants a fault of the program in the
reference, to read what it does to the compared numbers:
``"half_batch"`` (half of each microbatch left out, the mean over the
rest) or ``"no_exchange"`` (each worker's shard of the update from its own
gradient alone).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import pool
from refops import Ops

FAULTS = ("half_batch", "no_exchange")


def follow(ref, conf: dict, cell: dict, seed: int, *, steps: int = 3,
           compute: str = "float32", fault: str | None = None,
           device=None) -> dict:
    """Host copies of what the compared numbers need: ``losses`` (one per
    step), ``grad0`` (the first mean gradient), ``m1`` (the momentum after
    one step), ``p0`` and ``p_last`` (parameters before and after
    ``steps`` steps), each a list of leaves in tree order."""
    if fault not in (None,) + FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    device = device or jax.devices()[0]
    k = cell["chips"]
    rows = cell["images_per_chip"]
    mbs = cell["plan"].get("microbatches", 1)
    mb = rows // mbs
    opt = cell["optimizer"]
    side, classes = conf["image_size"], conf["num_classes"]
    key = jax.random.key(seed)

    with jax.default_device(device):
        params = jax.jit(lambda kk: ref.init_params(kk, conf))(key)
        leaves0, treedef = jax.tree.flatten(params)
        p0 = [np.asarray(l) for l in leaves0]
        ops = Ops(compute)
        grad_fn = jax.jit(jax.value_and_grad(
            lambda p, x, y, kk: ref.loss(p, x, y, ops, conf, kk)))
        make_batch = jax.jit(lambda kk, b: pool.batch(kk, b, rows * k, side,
                                                     classes))
        decay = [opt["weight_decay"] if l.ndim > 1 else 0.0 for l in p0]
        mom = [jnp.zeros(l.shape, jnp.float32) for l in leaves0]
        out = {"losses": [], "p0": p0}
        run_key = jax.random.key(seed + 1)
        for step in range(steps):
            b = make_batch(key, step % cell["pool_batches"])
            step_key = jax.random.fold_in(run_key, step)
            per_worker, losses = [], []
            for w in range(k):
                wkey = jax.random.fold_in(step_key, w)
                acc = None
                for j in range(mbs):
                    lo = w * rows + j * mb
                    hi = lo + (mb // 2 if fault == "half_batch" else mb)
                    loss, g = grad_fn(params, b["images"][lo:hi],
                                      b["labels"][lo:hi], wkey)
                    losses.append(float(loss))
                    g = jax.tree.leaves(g)
                    acc = g if acc is None else [a + x for a, x in zip(acc, g)]
                per_worker.append([a / mbs for a in acc])
            if fault == "no_exchange":
                grads = [_own_shards([g[i] for g in per_worker])
                         for i in range(len(leaves0))]
            else:
                grads = [sum(g[i] for g in per_worker) / k
                         for i in range(len(leaves0))]
            if step == 0:
                out["grad0"] = [np.asarray(g) for g in grads]
            leaves = jax.tree.leaves(params)
            mom = [opt["momentum"] * m + g + wd * p
                   for m, g, p, wd in zip(mom, grads, leaves, decay)]
            leaves = [p - opt["lr"] * m for p, m in zip(leaves, mom)]
            params = jax.tree.unflatten(treedef, leaves)
            out["losses"].append(float(np.mean(losses)))
            if step == 0:
                out["m1"] = [np.asarray(m) for m in mom]
        out["p_last"] = [np.asarray(p) for p in jax.tree.leaves(params)]
    return out


def _own_shards(grads: list):
    """Worker r's own gradient in the r-th of k equal flat shards of the
    leaf: what each chip updates when the exchange is left out."""
    k = len(grads)
    n = grads[0].size
    shard = -(-n // k)
    flat = [g.reshape(-1) for g in grads]
    parts = [flat[r][r * shard:(r + 1) * shard] for r in range(k)]
    return jnp.concatenate(parts).reshape(grads[0].shape)
