"""BSP synchronous data-parallel training (paper §3.1, §4).

Builds a jitted train step that runs under ``jax.shard_map`` with the data
(and pod) axes *manual* — so the configured Exchanger's collectives are the
literal HLO collectives — and any model-parallel axes left to GSPMD.

Both of the paper's parallel-SGD schemes are supported:

- ``subgd``: sum/mean gradients across workers BEFORE the descent step
  (the paper notes this needs no LR rescaling);
- ``awagd``: each worker descends on its local gradient, then weights AND
  momentum are averaged (Krizhevsky's scheme; LR scales with k).

Beyond the paper, ``subgd`` has a ZeRO-1-style **sharded fused update**
path (``sharded_update=True``): the exchange is split into its
reduce-scatter / all-gather halves and the optimizer updates only the
local 1/k shard between them (RS -> update -> AG). The full reduced
gradient is never materialized, optimizer state lives sharded over the
data axis (1/k memory), and the wire precision applies to both directions
— gradients in, updated parameters out. With ``overlap="buckets"`` the
microbatch ``lax.scan`` double-buffers: microbatch *i-1*'s bucket
reduce-scatters are issued while microbatch *i*'s backprop runs, so the
latency-hiding scheduler can overlap exchange with compute (the paper's
§3.2 remark); each bucket's sharded update is dispatched independently so
updates and parameter all-gathers interleave too. Note the tradeoff:
overlap exchanges every microbatch's gradient separately (m× wire volume,
hidden behind backprop) while the serialized path exchanges the
accumulated gradient once.

Each microbatch's receive is accumulated in the shape the reduce-scatter
returns it (``Exchanger.reduce_scatter``): a **shaped** bucket — one leaf
whose leading dimension k divides, every bucket of a ``bucket_bytes=0``
plan at k = 1 — stays in its leaf's own layout through the scan, so no
relayout of the gradient runs per microbatch. Each bucket is flattened
once per step, after the scan (or right after the all-to-all on the
serialized path), into the flat shard the update and the optimizer state
use; the state's layout does not depend on it.
"""
from __future__ import annotations

from math import prod
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import telemetry
from repro.core.exchanger import (Exchanger, RSPlan, default_chunk_sum,
                                  make_rs_plan, norm_axes, param_wire_dtype)
from repro.models.registry import Model
from repro.optim.optimizers import Optimizer


def init_train_state(model: Model, optimizer: Optimizer, key):
    params = model.init(key)
    return {"params": params, "opt": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32)}


def _model_plan(model: Model, mesh, data_axes, bucket_bytes: int) -> RSPlan:
    """The (deterministic) bucket plan shared by init and the step."""
    params_abs = jax.eval_shape(model.init, jax.random.key(0))
    k = int(mesh.shape[data_axes[-1]])
    return make_rs_plan(params_abs, k, bucket_bytes)


def init_sharded_train_state(model: Model, optimizer: Optimizer, key, mesh,
                             data_axes=("data",), bucket_bytes: int = 0):
    """Train state for the RS->update->AG path.

    Optimizer state lives as flat per-bucket arrays sharded over the last
    data axis (global extent ``k * shard_len``; each rank materializes
    1/k), alongside the fp32 **master** parameter shard (``"master"``).
    Updates accumulate in the master — ``state["params"]`` is the compute
    copy rebuilt from the wire-dtype all-gather each step, so fp16/int8
    gather rounding never feeds back into the update (sub-ulp updates
    still accumulate, the standard ZeRO-1 master-weights discipline).
    Small psum'd leaves keep replicated flat state and update
    ``params`` directly at fp32."""
    if optimizer.flat_init is None:
        raise ValueError(f"optimizer {optimizer.name!r} has no flat/sharded "
                         "update support (flat_init/flat_update)")
    params = model.init(key)
    plan = _model_plan(model, mesh, data_axes, bucket_bytes)
    ax = data_axes[-1]
    shard = NamedSharding(mesh, P(ax))

    def bucket_state(b):
        # jit with out_shardings so each rank only ever allocates its own
        # 1/k shard — a host-side flat_init would materialize the full
        # (k*shard_len,) state exactly where the ZeRO-1 memory matters
        abs_st = jax.eval_shape(lambda: optimizer.flat_init(b.padded))
        sh = jax.tree.map(
            lambda l: shard if (len(l.shape) == 1 and l.shape[0] == b.padded)
            else NamedSharding(mesh, P()), abs_st)
        return jax.jit(lambda: optimizer.flat_init(b.padded),
                       out_shardings=sh)()

    master = ([] if not plan.buckets else
              jax.jit(lambda ps: Exchanger.pack(ps, plan)[0],
                      out_shardings=[shard] * plan.num_buckets)(params))
    opt = {"buckets": [bucket_state(b) for b in plan.buckets],
           "small": [optimizer.flat_init(prod(plan.shapes[i]))
                     for i in plan.small],
           "master": master}
    return {"params": params, "opt": opt, "step": jnp.zeros((), jnp.int32)}


def _sharded_state_specs(optimizer: Optimizer, plan: RSPlan, ax: str):
    """in/out spec tree: params/step/small-leaf state replicated, per-bucket
    flat state and fp32 master shards split over the rs axis (the (k*s,)
    arrays; scalars like adamw's ``t`` stay replicated)."""
    def bucket_spec(b):
        st = jax.eval_shape(lambda: optimizer.flat_init(b.padded))
        return jax.tree.map(
            lambda l: P(ax) if (len(l.shape) == 1 and l.shape[0] == b.padded)
            else P(), st)

    return {"params": P(),
            "opt": {"buckets": [bucket_spec(b) for b in plan.buckets],
                    "small": P(),
                    "master": [P(ax) for _ in plan.buckets]},
            "step": P()}


def _loss_and_grad(model: Model, params, batch, rng, unroll: bool):
    """``value_and_grad`` of the model's loss, taken through ``jax.vjp`` so
    that the forward ops run under the name scope ``forward`` and their
    transposes under ``backward``: the device trace's ``tf_op`` metadata
    then groups a step's ops by phase. Metadata only: the compiled program
    is the one ``value_and_grad`` gives."""
    with jax.named_scope("forward"):
        loss, vjp_fn, metrics = jax.vjp(
            lambda p: model.loss_fn(p, batch, rng, unroll=unroll), params,
            has_aux=True)
    with jax.named_scope("backward"):
        (grads,) = vjp_fn(jnp.ones_like(loss))
    return (loss, metrics), grads


def make_bsp_step(model: Model, optimizer: Optimizer, exchanger: Exchanger,
                  lr_fn: Callable, mesh, data_axes=("data",),
                  scheme: str = "subgd", sum_fn=default_chunk_sum,
                  unroll: bool = False, microbatches: int = 1,
                  bucket_bytes: int = 0, sharded_update: bool = False,
                  overlap: str | None = None, fuse_rs_update=None,
                  grad_norm: bool = False):
    """Returns ``step(state, batch, rng) -> (state, metrics)`` (un-jitted).

    ``microbatches`` > 1 splits the local batch and accumulates gradients
    over a ``lax.scan`` (activation-memory reduction; the exchange then
    amortizes over the whole accumulated gradient — the regime the paper's
    §3.2 'overlap with backprop' remark targets).

    ``sharded_update=True`` (subgd only) takes the RS->update->AG path;
    the state must come from :func:`init_sharded_train_state` with the
    same ``bucket_bytes``. ``overlap="buckets"`` additionally
    double-buffers the microbatch scan (see module docstring); it implies
    ``sharded_update`` and needs ``microbatches >= 2`` to overlap
    anything; its accumulators hold each bucket's receive in its
    ``reduce_scatter`` shape (a shaped bucket's in its leaf's layout) and
    are flattened once, after the scan. ``fuse_rs_update`` selects the
    Pallas fused dequant+sum+update kernel on the raw alltoall receives
    (needs a single-axis asa-family strategy and an optimizer with
    ``rs_fused_update``; None = auto: on when kernels run compiled — TPU —
    off in interpreter mode where the jnp flat update is faster). The
    gauges ``exchange/shaped_buckets`` and ``exchange/flat_buckets`` count
    the plan's buckets of each kind when the step is built.

    ``grad_norm=True`` adds the post-exchange global gradient norm to the
    step metrics — the telemetry layer's single *in-graph* opt-in (it adds
    reductions to the compiled step, so it is off by default and gated by
    ``REPRO_TELEMETRY_GRADNORM``; non-sharded paths only, where the full
    reduced gradient exists to be normed).

    The step's ops run under the name scopes ``forward``, ``backward``,
    ``exchange`` and ``update`` (metadata for the profiler's view)."""
    if overlap not in (None, "buckets"):
        raise ValueError(f"unknown overlap mode {overlap!r}")
    if overlap:
        sharded_update = True
    if sharded_update and scheme != "subgd":
        raise ValueError("sharded_update requires scheme='subgd' "
                         "(awagd updates on the local gradient)")
    axes = norm_axes(data_axes)
    ax_rs = data_axes[-1]

    def grad_of(params, batch, rng):
        if microbatches <= 1:
            return _loss_and_grad(model, params, batch, rng, unroll)

        def split(v):
            return v.reshape(microbatches, v.shape[0] // microbatches,
                             *v.shape[1:])
        mb = jax.tree.map(split, batch)

        def body(carry, mbatch):
            acc, loss_sum, aux_sum = carry
            (loss, metrics), g = _loss_and_grad(model, params, mbatch, rng,
                                                unroll)
            acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                               acc, g)
            return (acc, loss_sum + loss, aux_sum + metrics["aux"]), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)
        (acc, loss_sum, aux_sum), _ = jax.lax.scan(
            body, (zeros, jnp.zeros((), jnp.float32),
                   jnp.zeros((), jnp.float32)), mb)
        m = float(microbatches)
        grads = jax.tree.map(lambda a: a / m, acc)
        return (loss_sum / m, {"loss": loss_sum / m, "aux": aux_sum / m}), grads

    if not sharded_update:
        def per_shard(state, batch, rng):
            rng = jax.random.fold_in(rng, jax.lax.axis_index(data_axes[0]))
            (loss, metrics), grads = grad_of(state["params"], batch, rng)
            lr = lr_fn(state["step"])
            if scheme == "subgd":
                with jax.named_scope("exchange"):
                    grads = exchanger.exchange(grads, axes, sum_fn=sum_fn,
                                               bucket_bytes=bucket_bytes)
                with jax.named_scope("update"):
                    new_params, new_opt = optimizer.update(
                        state["params"], grads, state["opt"], lr)
            elif scheme == "awagd":
                with jax.named_scope("update"):
                    new_params, new_opt = optimizer.update(
                        state["params"], grads, state["opt"], lr)
                # average weights AND momentum after the descent step
                # ([7], [15]) — with the same bucketing as the gradients
                with jax.named_scope("exchange"):
                    new_params = exchanger.exchange(
                        new_params, axes, sum_fn=sum_fn,
                        bucket_bytes=bucket_bytes)
                    new_opt = exchanger.exchange(new_opt, axes,
                                                 sum_fn=sum_fn,
                                                 bucket_bytes=bucket_bytes)
            else:
                raise ValueError(f"unknown scheme {scheme!r}")
            metrics = jax.tree.map(lambda v: jax.lax.pmean(v, axes), metrics)
            if grad_norm:
                # subgd: grads here are the post-exchange global mean
                # (identical on every rank); awagd: the local gradient —
                # the pmean reports the worker average
                sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads))
                metrics["grad_norm"] = jnp.sqrt(
                    jax.lax.pmean(sq, axes))
            new_state = {"params": new_params, "opt": new_opt,
                         "step": state["step"] + 1}
            return new_state, metrics

        state_specs = P()
    else:
        if optimizer.flat_update is None or optimizer.flat_init is None:
            raise ValueError(f"optimizer {optimizer.name!r} has no "
                             "flat_init/flat_update; cannot shard the "
                             "update")
        plan = _model_plan(model, mesh, data_axes, bucket_bytes)
        raw_ok = (exchanger.supports_raw and not isinstance(axes, tuple)
                  and optimizer.rs_fused_update is not None)
        if fuse_rs_update is None:
            # auto: the fused kernel only pays off compiled; in Pallas
            # interpreter mode (CPU hosts) the jnp flat_update path wins
            from repro.kernels import default_interpret
            use_raw = raw_ok and not default_interpret()
        else:
            use_raw = bool(fuse_rs_update)
        if use_raw and not raw_ok:
            raise ValueError(
                f"fuse_rs_update needs a single-axis alltoall strategy and "
                f"an optimizer with rs_fused_update (got {exchanger.name!r}"
                f" / {optimizer.name!r})")
        nb = plan.num_buckets
        n_shaped = sum(b.shaped for b in plan.buckets)
        telemetry.metrics.gauge("exchange/shaped_buckets").set(n_shaped)
        telemetry.metrics.gauge("exchange/flat_buckets").set(nb - n_shaped)

        def shard_wd_mask(b, start):
            # 1.0 where the element's original leaf is >=2-D (weight decay
            # applies). Built O(shard_len) from the static leaf boundaries
            # — materializing the full bucket mask just to slice 1/k of it
            # would add O(model) traffic to the memory-saving path.
            pos = start + jnp.arange(b.shard_len)
            mask = jnp.zeros((b.shard_len,), jnp.float32)
            off = 0
            for i, n in zip(b.leaves, b.sizes):
                if len(plan.shapes[i]) > 1:
                    mask = mask + ((pos >= off) & (pos < off + n)).astype(
                        jnp.float32)
                off += n
            return mask

        @jax.named_scope("exchange")
        def rs_accum(grads):
            """RS one microbatch's grads to fp32 accumulables, each in the
            shape ``reduce_scatter`` returns (a shaped bucket's in its
            leaf's layout, so adding it up moves no data around)."""
            res, _ = exchanger.reduce_scatter(grads, axes, sum_fn=sum_fn,
                                              plan=plan, raw=use_raw)
            if use_raw:
                ch, sc = res["chunks"], res["scales"]
                if sc:   # int8 wire: dequant before accumulating
                    ch = [c.astype(jnp.float32) * s for c, s in zip(ch, sc)]
                else:
                    ch = [c.astype(jnp.float32) for c in ch]
                return ch, res["full"]
            return res["shards"], res["full"]

        def per_shard(state, batch, rng):
            rng = jax.random.fold_in(rng, jax.lax.axis_index(data_axes[0]))
            params = state["params"]
            lr = lr_fn(state["step"])
            idx = jax.lax.axis_index(ax_rs)

            if overlap == "buckets" and microbatches > 1:
                def split(v):
                    return v.reshape(microbatches,
                                     v.shape[0] // microbatches, *v.shape[1:])
                mb = jax.tree.map(split, batch)
                mb0 = jax.tree.map(lambda v: v[0], mb)
                rest = jax.tree.map(lambda v: v[1:], mb)

                def one_grad(mbatch):
                    return _loss_and_grad(model, params, mbatch, rng, unroll)

                (l0, met0), g0 = one_grad(mb0)
                # each accumulator takes its receive's shape: a shaped
                # bucket's stays in its leaf's layout through the scan
                acc0, accf0 = jax.tree.map(
                    lambda r: jnp.zeros(r.shape, r.dtype),
                    jax.eval_shape(rs_accum, g0))

                def body(carry, mbatch):
                    acc, accf, pending, loss_s, aux_s = carry
                    # the RS of the PREVIOUS microbatch is issued first and
                    # is data-independent of THIS microbatch's grads: the
                    # scheduler overlaps the collective with the backward
                    # dots that follow it in the loop body
                    sh, fl = rs_accum(pending)
                    (l, met), g = one_grad(mbatch)
                    acc = [a + s for a, s in zip(acc, sh)]
                    accf = [a + f for a, f in zip(accf, fl)]
                    return (acc, accf, g, loss_s + l,
                            aux_s + met["aux"]), None

                carry, _ = jax.lax.scan(
                    body, (acc0, accf0, g0, l0, met0["aux"]), rest)
                acc, accf, pending, loss_s, aux_s = carry
                sh, fl = rs_accum(pending)         # last microbatch: exposed
                acc = [a + s for a, s in zip(acc, sh)]
                accf = [a + f for a, f in zip(accf, fl)]
                m = float(microbatches)
                loss = loss_s / m
                metrics = {"loss": loss, "aux": aux_s / m}
                fulls = [a / m for a in accf]
                # flattened once per step, after the scan
                if use_raw:
                    chunks = [a.reshape(plan.k, -1) for a in acc]
                    scales = [None] * nb
                    scale = 1.0 / (plan.k * m)
                else:
                    shards = [a.reshape(-1) / m for a in acc]
            else:
                (loss, metrics), grads = grad_of(params, batch, rng)
                with jax.named_scope("exchange"):
                    res, _ = exchanger.reduce_scatter(
                        grads, axes, sum_fn=sum_fn, plan=plan, raw=use_raw)
                fulls = res["full"]
                if use_raw:
                    chunks = [c.reshape(plan.k, -1) for c in res["chunks"]]
                    scales = res["scales"] or [None] * nb
                    scale = 1.0 / plan.k
                else:
                    shards = [s.reshape(-1) for s in res["shards"]]

            p_leaves = jax.tree.flatten(params)[0]
            p_smalls = [p_leaves[i] for i in plan.small]
            wire = param_wire_dtype(exchanger)
            new_flats, new_bstates, new_master = [], [], []
            for bi, b in enumerate(plan.buckets):
                # the fp32 master shard is persistent state: updates
                # accumulate there, and only the compute copy goes through
                # the (possibly lossy) wire-dtype all-gather
                p_sh = state["opt"]["master"][bi]
                st = state["opt"]["buckets"][bi]
                with jax.named_scope("update"):
                    mask_sh = shard_wd_mask(b, idx * b.shard_len)
                    if use_raw:
                        p_new, st_new = optimizer.rs_fused_update(
                            chunks[bi], p_sh, st, lr, mask_sh, scale,
                            scales[bi])
                    else:
                        p_new, st_new = optimizer.flat_update(
                            p_sh, shards[bi], st, lr, mask_sh)
                new_bstates.append(st_new)
                new_master.append(p_new)
                # per-bucket dispatch: each AG depends only on its bucket's
                # update, so gathers and updates interleave
                with jax.named_scope("exchange"):
                    new_flats.append(exchanger.all_gather(
                        [p_new], plan, axes, wire_dtype=wire)[0])
            new_smalls, new_sstates = [], []
            for si, i in enumerate(plan.small):
                p_fl = p_smalls[si].reshape(-1).astype(jnp.float32)
                mask = (jnp.ones_like(p_fl) if len(plan.shapes[i]) > 1
                        else None)
                with jax.named_scope("update"):
                    p_new, st_new = optimizer.flat_update(
                        p_fl, fulls[si].reshape(-1),
                        state["opt"]["small"][si], lr, mask)
                new_smalls.append(p_new)
                new_sstates.append(st_new)
            new_params = Exchanger.unpack(new_flats, new_smalls, plan)
            metrics = jax.tree.map(lambda v: jax.lax.pmean(v, axes), metrics)
            new_state = {"params": new_params,
                         "opt": {"buckets": new_bstates,
                                 "small": new_sstates,
                                 "master": new_master},
                         "step": state["step"] + 1}
            return new_state, metrics

        state_specs = _sharded_state_specs(optimizer, plan, ax_rs)

    batch_spec = P(data_axes)
    step = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(state_specs, batch_spec, P()),
        out_specs=(state_specs, P()),
        axis_names=frozenset(data_axes),
        check_vma=False)
    return step
