"""Async training plans: EASGD (paper §4; Zhang et al. 2015) and ASGD.

Theano-MPI re-implements Platoon's EASGD over CUDA-aware MPI SendRecv. The
TPU/SPMD adaptation keeps per-worker parameter *and optimizer-state*
replicas as a leading worker axis sharded over the data axes; the elastic
attraction to the replicated center runs every ``tau`` steps (the
averaging period) — a synchronous clock emulation of bounded-staleness
asynchrony (the paper itself equates larger tau with larger effective
batch).

Promoted to first-class (engine) status:

- the per-worker descent goes through the shared :class:`Optimizer`
  interface (momentum-SGD *and* AdamW), not an inline update;
- the center traffic routes through :class:`Exchanger` — the ASA
  decomposition and fp16/int8 wire precision apply to the elastic
  exchange exactly as they do to BSP gradients;
- the state uses the engine's canonical layout (``params/opt/step`` +
  the ``center`` extra), so checkpoint save/resume is shared.

Sync-step semantics (server-style ordering: the center absorbs the worker
deltas first, workers then attract to the *updated* center — what a
Platoon worker observes after its round trip):

    delta_i = x_i - c
    c'      = c + alpha * sum_i delta_i     (exchanger: mean * k)
    x_i'    = x_i - alpha * (x_i - c')

``algo="asgd"`` is the ``alpha = 1`` point of the same scaffolding: the
center applies the full sum of worker deltas (each worker's accumulated
local updates since its last sync — staleness bounded by tau) and the
workers re-fetch the center. At ``tau = 1`` that collapses to synchronous
model averaging, which from a synced start equals BSP gradient averaging
with the learning rate scaled by ``k`` (momentum/Adam states stay local
but their mean tracks the BSP state by linearity) — the parity tested in
``tests/test_engine.py``.

The local (non-averaging) step is a *separate* function with no
param-sized collective at all — the engine dispatches sync vs local by
``step_idx % tau``, so at tau > 1 the wire really is idle between
averaging rounds.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.exchanger import Exchanger, default_chunk_sum, norm_axes
from repro.models.registry import Model
from repro.optim.optimizers import Optimizer


def init_async_state(model: Model, optimizer: Optimizer, key,
                     num_workers: int, *, mesh=None, data_axes=("data",)):
    """Canonical engine layout + the async extras.

    ``params``/``opt`` are per-worker replica stacks (leading worker dim of
    extent ``num_workers``, sharded over the data axes when ``mesh`` is
    given); ``center`` is the replicated center replica."""
    params = model.init(key)
    stack = lambda p: jnp.broadcast_to(p[None], (num_workers, *p.shape))
    state = {"params": jax.tree.map(stack, params),
             "opt": jax.tree.map(stack, optimizer.init(params)),
             "center": params,
             "step": jnp.zeros((), jnp.int32)}
    if mesh is not None:
        worker = NamedSharding(mesh, P(norm_axes(data_axes)))
        rep = NamedSharding(mesh, P())
        put = lambda sh: (lambda l: jax.device_put(l, sh))
        state = {"params": jax.tree.map(put(worker), state["params"]),
                 "opt": jax.tree.map(put(worker), state["opt"]),
                 "center": jax.tree.map(put(rep), state["center"]),
                 "step": jax.device_put(state["step"], rep)}
    return state


def make_async_step(model: Model, optimizer: Optimizer, exchanger: Exchanger,
                    lr_fn: Callable, mesh, *, algo: str = "easgd",
                    alpha: float = 0.5, data_axes=("data",),
                    sum_fn=default_chunk_sum, bucket_bytes: int = 0,
                    unroll: bool = False, quorum: bool = False):
    """Returns ``(local_step, sync_step)``, both un-jitted.

    Each is ``step(state, batch, rng) -> (state, metrics)``. ``local_step``
    is the pure per-worker descent (no param-sized collective);
    ``sync_step`` additionally runs the elastic exchange. The engine
    dispatches ``sync_step`` on every tau-th step.

    With ``quorum=True`` the sync step instead takes per-worker weight
    vectors — ``sync(state, batch, rng, absorb, attract)`` with ``absorb``
    and ``attract`` of shape (k,) fp32, sharded like the replica stacks —
    the elastic-fleet variant (see ``repro.fault``):

        c'   = c + sum_i absorb_i * (x_i - c)
        x_i' = x_i - attract_i * (x_i - c')

    ``absorb_i = alpha / (1 + staleness_i)`` for reporting workers (the
    staleness-scaled late-absorption rule) and 0 for non-reporting rows,
    whose params ignore the center this round. alpha is folded into the
    weights by the membership controller, so full participation at
    staleness 0 (``absorb = attract = alpha``) reproduces the fixed sync
    step exactly; ``attract_i == 1`` snaps to the center (the asgd
    re-fetch, special-cased against fp rounding)."""
    if algo not in ("easgd", "asgd"):
        raise ValueError(f"unknown async algo {algo!r}")
    if exchanger.kind == "none":
        raise ValueError("async plans need a real exchanger for the center "
                         "traffic (got 'none')")
    # asgd = the alpha=1 point: center applies the full delta sum, workers
    # re-fetch the center (tau-bounded staleness)
    a = float(alpha) if algo == "easgd" else 1.0
    axes = tuple(data_axes)
    entry = norm_axes(axes)

    def _worker_rng(rng):
        idx = jax.lax.axis_index(axes[0])
        for ax in axes[1:]:
            idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
        return jax.random.fold_in(rng, idx)

    def local_update(state, batch, rng):
        rng = _worker_rng(rng)
        w = jax.tree.map(lambda v: v[0], state["params"])
        opt = jax.tree.map(lambda v: v[0], state["opt"])
        (_, metrics), grads = jax.value_and_grad(
            model.loss_fn, has_aux=True)(w, batch, rng, unroll=unroll)
        w, opt = optimizer.update(w, grads, opt, lr_fn(state["step"]))
        metrics = jax.tree.map(lambda v: jax.lax.pmean(v, entry), metrics)
        return w, opt, metrics

    def restack(w, opt, center, step):
        return {"params": jax.tree.map(lambda v: v[None], w),
                "opt": jax.tree.map(lambda v: v[None], opt),
                "center": center, "step": step}

    def per_shard_local(state, batch, rng):
        w, opt, metrics = local_update(state, batch, rng)
        return restack(w, opt, state["center"], state["step"] + 1), metrics

    def per_shard_sync(state, batch, rng):
        w, opt, metrics = local_update(state, batch, rng)
        k = 1
        for ax in axes:
            k *= jax.lax.axis_size(ax)
        delta = jax.tree.map(
            lambda wi, c: wi.astype(jnp.float32) - c.astype(jnp.float32),
            w, state["center"])
        # the elastic exchange IS an exchanger round: ASA decomposition,
        # bucketing and fp16/int8 wire precision apply to the center traffic
        dmean = exchanger.exchange(delta, entry, sum_fn=sum_fn,
                                   bucket_bytes=bucket_bytes)
        c_new = jax.tree.map(
            lambda c, d: (c.astype(jnp.float32) + a * k * d).astype(c.dtype),
            state["center"], dmean)
        if a == 1.0:
            # exact re-fetch (w - (w - c) would round): workers snap to the
            # updated center — the asgd/model-averaging point
            w_new = jax.tree.map(lambda wi, c: c.astype(wi.dtype), w, c_new)
        else:
            w_new = jax.tree.map(
                lambda wi, c: (wi.astype(jnp.float32)
                               - a * (wi.astype(jnp.float32)
                                      - c.astype(jnp.float32))
                               ).astype(wi.dtype), w, c_new)
        return restack(w_new, opt, c_new, state["step"] + 1), metrics

    def per_shard_sync_quorum(state, batch, rng, absorb, attract):
        w, opt, metrics = local_update(state, batch, rng)
        k = 1
        for ax in axes:
            k *= jax.lax.axis_size(ax)
        wa = absorb[0].astype(jnp.float32)    # this worker's absorb weight
        at = attract[0].astype(jnp.float32)
        # weighted delta: alpha (staleness-scaled) is already folded into
        # wa, so the center update is c + sum_i wa_i * delta_i
        delta = jax.tree.map(
            lambda wi, c: wa * (wi.astype(jnp.float32)
                                - c.astype(jnp.float32)),
            w, state["center"])
        dmean = exchanger.exchange(delta, entry, sum_fn=sum_fn,
                                   bucket_bytes=bucket_bytes)
        c_new = jax.tree.map(
            lambda c, d: (c.astype(jnp.float32) + k * d).astype(c.dtype),
            state["center"], dmean)
        # attract==1 must snap exactly (w - (w - c) would round); non-
        # reporting rows (attract==0) keep their params bit-identical
        w_new = jax.tree.map(
            lambda wi, c: jnp.where(
                at == 1.0, c.astype(wi.dtype),
                jnp.where(at == 0.0, wi,
                          (wi.astype(jnp.float32)
                           - at * (wi.astype(jnp.float32)
                                   - c.astype(jnp.float32))
                           ).astype(wi.dtype))),
            w, c_new)
        return restack(w_new, opt, c_new, state["step"] + 1), metrics

    state_spec = {"params": P(entry), "opt": P(entry),
                  "center": P(), "step": P()}

    def wrap(fn, extra_in=()):
        return jax.shard_map(fn, mesh=mesh,
                             in_specs=(state_spec, P(axes), P(), *extra_in),
                             out_specs=(state_spec, P()),
                             axis_names=frozenset(axes),
                             check_vma=False)

    if quorum:
        return (wrap(per_shard_local),
                wrap(per_shard_sync_quorum, extra_in=(P(entry), P(entry))))
    return wrap(per_shard_local), wrap(per_shard_sync)


def reshard_async_state(state, old_workers, new_workers,
                        optimizer: Optimizer, *, mesh,
                        data_axes=("data",)):
    """Migrate an async state between memberships (elastic join/leave).

    ``old_workers``/``new_workers`` are ordered worker-id tuples defining
    the replica-stack row order before and after the change. Survivor rows
    carry over by id (params *and* optimizer state — a surviving worker
    keeps its momentum); joiners start at the center with a fresh
    ``optimizer.init`` row (their delta is zero, their staleness 0).
    ``center`` and ``step`` pass through unchanged.

    Host-side by design: membership changes happen at round boundaries
    (rare), and the gather/restack is O(state size) — the same cost class
    as the checkpoint save that production systems do at the same place.
    The result lands on ``mesh`` with the canonical async placement
    (stacks sharded over the data axes, center/step replicated).
    """
    import numpy as np

    k_new = len(new_workers)
    mesh_k = 1
    for a in data_axes:
        mesh_k *= int(mesh.shape[a])
    if k_new != mesh_k:
        raise ValueError(f"{k_new} workers but the new mesh has {mesh_k} "
                         f"devices over {data_axes}")
    old_index = {w: i for i, w in enumerate(old_workers)}

    center_host = jax.tree.map(np.asarray, state["center"])
    fresh_opt = jax.tree.map(np.asarray,
                             optimizer.init(state["center"]))

    def rows(stack_leaf, fill_leaf):
        host = np.asarray(stack_leaf)
        return np.stack([host[old_index[w]] if w in old_index
                         else np.asarray(fill_leaf)
                         for w in new_workers])

    new_params = jax.tree.map(rows, state["params"], center_host)
    new_opt = jax.tree.map(rows, state["opt"], fresh_opt)

    worker = NamedSharding(mesh, P(norm_axes(tuple(data_axes))))
    rep = NamedSharding(mesh, P())
    put = lambda sh: (lambda l: jax.device_put(l, sh))
    return {"params": jax.tree.map(put(worker), new_params),
            "opt": jax.tree.map(put(worker), new_opt),
            "center": jax.tree.map(put(rep), center_host),
            "step": jax.device_put(np.asarray(state["step"]), rep)}
