"""Parameter-exchange strategies — the paper's core contribution (§3.2).

Theano-MPI exchanges gradients/parameters between data-parallel workers with
one of several strategies; this module reimplements them as explicit JAX
collectives that run inside ``jax.shard_map`` over the *data* (and *pod*)
mesh axes, leaving any model-parallel axes to GSPMD ("auto" axes):

- ``ar``    : MPI_Allreduce analogue            -> ``lax.psum``
- ``asa``   : Alltoall-sum-Allgather (Fig 2)    -> ``lax.all_to_all`` +
              local fp32 sum + ``lax.all_gather``  (== reduce-scatter + AG,
              transfer separated from arithmetic exactly as in the paper)
- ``asa16`` : ASA with half-precision transfer, fp32 summation (§3.2)
- ``asa8``  : beyond-paper int8 + per-shard scale transfer
- ``ring``  : beyond-paper ring reduce-scatter/all-gather via
              ``lax.ppermute`` (bandwidth-optimal on a torus link)
- ``hier``  : beyond-paper pod-hierarchical exchange — intra-pod
              reduce-scatter, cross-pod (DCN) allreduce of the 1/k shard,
              intra-pod all-gather. The TPU analogue of the paper's
              "QPI-aware" staging concern.
- ``none``  : identity (benchmark baseline: isolates compute from exchange)

Every strategy is split into composable **halves**:

    reduce_scatter(grads) -> 1/k shard     all_gather(shard) -> full tree

and ``exchange`` is their composition (``ar`` keeps the single fused
``psum`` so the MPI_Allreduce baseline of the paper's Table 3 stays one
collective; its halves are ``psum_scatter``/``all_gather``). The split is
what lets the optimizer update only the local shard between the halves
(ZeRO-1-style RS -> update -> AG, see ``core/bsp.py``): the full reduced
gradient is never materialized and the fp16/int8 wire precision applies to
both directions (gradients in, updated parameters out).

Leaves are packed into flat fp32 **buckets** (``make_rs_plan``): one bucket
per leaf by default, or DDP-style multi-leaf buckets of up to
``bucket_bytes``. Leaves smaller than ``_SMALL_LEAF`` elements are psum'd
whole and updated replicated — chunking overhead dominates there.

The reduce-scatter works on each bucket's ``(k, ...)`` view, row i being
rank i's chunk (``Exchanger.split``). A **shaped** bucket — one leaf whose
leading dimension ``k`` divides — is its leaf split on that dimension,
``(k, d0 // k, *rest)``, so its shard comes back in the leaf's own shape
and no relayout runs before the wire; every other bucket is the flat
bucket as ``(k, shard_len)``. Row-major, row i holds the same elements in
the same order either way, so the flat shard is the shaped one reshaped:
callers flatten once, where the update needs it.

NOTE: flattening assumes gradient leaves are *replicated* over any
model-parallel mesh axes inside the shard_map body — the invariant the
BSP path maintains (``repro.dist.state_shardings`` replicates train
state). With model-sharded gradient leaves under the partial-auto
shard_map, the reshape/concat would force GSPMD to regather each leaf —
the GSPMD/ZeRO-1 path (``core/gspmd.py``) is the right tool there, not
this module.

Every strategy computes the *mean* over the data axes and is numerically
interchangeable (up to its transfer precision) — property-tested in
``tests/test_exchangers.py`` / ``tests/test_rs_update.py``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


# leaves smaller than this are psum'd directly (chunking overhead dominates)
_SMALL_LEAF = 1024


def norm_axes(data_axes):
    """Collapse a data-axes tuple to the form the collectives take: the
    bare name for a single axis, the tuple itself otherwise."""
    axes = tuple(data_axes)
    return axes[0] if len(axes) == 1 else axes


def _axis_size(axis) -> int:
    if isinstance(axis, (tuple, list)):
        return int(np.prod([jax.lax.axis_size(a) for a in axis]))
    return jax.lax.axis_size(axis)


def _split_axes(axis):
    """(lead_axes, rs_axis): the reduce-scatter/all-gather legs run over the
    *last* axis (intra-pod ICI); any leading axes (cross-pod DCN) see only a
    psum of the 1/k shard."""
    if isinstance(axis, (tuple, list)):
        axes = tuple(axis)
        return axes[:-1], axes[-1]
    return (), axis


def _pad_to(g, k: int):
    n = g.shape[0]
    pad = (-n) % k
    if pad:
        g = jnp.pad(g, ((0, pad),) + ((0, 0),) * (g.ndim - 1))
    return g, n


def default_chunk_sum(chunks):
    """fp32-accumulating sum over the leading (worker) axis.

    The Pallas `chunk_sum` kernel implements the same contract on TPU; the
    exchanger takes it as a plug-in (see ``ops.chunk_sum``)."""
    return jnp.sum(chunks.astype(jnp.float32), axis=0)


# ---------------------------------------------------------------------------
# bucket plan: the static layout shared by RS, update, and AG
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class BucketSpec:
    """One flat fp32 bucket: which leaves it packs and its padded extent."""
    leaves: tuple[int, ...]      # leaf indices (tree.flatten order)
    sizes: tuple[int, ...]       # flat element counts, same order
    shard_len: int               # per-rank shard extent
    padded: int                  # k * shard_len
    shaped: bool                 # one leaf, leading dim divisible by k:
                                 # exchanged in the leaf's own shape


@dataclass(frozen=True)
class RSPlan:
    """Static reduce-scatter plan for one gradient/parameter pytree.

    Derived deterministically from (leaf shapes, k, bucket_bytes) so the
    optimizer-state layout built at init time and the step built at trace
    time always agree."""
    k: int                       # rs-axis worker count (shard denominator)
    buckets: tuple[BucketSpec, ...]
    small: tuple[int, ...]       # leaf indices exchanged whole (psum)
    treedef: Any
    shapes: tuple
    dtypes: tuple

    @property
    def num_buckets(self) -> int:
        return len(self.buckets)


def _leaf_size(leaf) -> int:
    return int(np.prod(leaf.shape)) if leaf.shape else 1


def make_rs_plan(tree, k: int, bucket_bytes: int = 0,
                 small_leaf: int = _SMALL_LEAF) -> RSPlan:
    """Pack a pytree's leaves into reduce-scatter buckets.

    ``tree`` may hold arrays or ``ShapeDtypeStruct``s (the plan only reads
    shapes/dtypes). ``bucket_bytes=0`` gives one bucket per big leaf;
    ``bucket_bytes>0`` greedily packs consecutive big leaves into flat fp32
    buckets of up to that size (fewer, larger collectives)."""
    leaves, treedef = jax.tree.flatten(tree)
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
    small, groups, cur, cur_b = [], [], [], 0
    for i, l in enumerate(leaves):
        n = _leaf_size(l)
        if n <= small_leaf:
            small.append(i)
            continue
        if bucket_bytes and cur and cur_b + n * 4 > bucket_bytes:
            groups.append(cur)
            cur, cur_b = [], 0
        cur.append(i)
        cur_b += n * 4
        if not bucket_bytes:
            groups.append(cur)
            cur, cur_b = [], 0
    if cur:
        groups.append(cur)
    buckets = []
    for g in groups:
        sizes = tuple(_leaf_size(leaves[i]) for i in g)
        total = sum(sizes)
        shard_len = -(-total // k)
        shaped = len(g) == 1 and shapes[g[0]][0] % k == 0
        buckets.append(BucketSpec(tuple(g), sizes, shard_len, shard_len * k,
                                  shaped))
    return RSPlan(k, tuple(buckets), tuple(small), treedef, shapes, dtypes)


# ---------------------------------------------------------------------------
# per-bucket halves (inside shard_map): the RS takes a bucket's (k, ...)
# fp32 view and returns rank i's shard, row i's shape; the AG takes and
# returns flat fp32 arrays
# ---------------------------------------------------------------------------

def _quant_rows(cf):
    """Per-row absmax int8 quantization: (k, ...) fp32 -> (q int8, scale
    (k, 1, ...)) with one scale per row."""
    scale = jnp.max(jnp.abs(cf), axis=tuple(range(1, cf.ndim)),
                    keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(cf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _rs_ar(chunks, axes, inv_k, sum_fn, transfer_dtype):
    """psum_scatter over the rs axis (+ psum over lead axes): true HLO
    reduce-scatter, fp32 on the wire."""
    lead, ax = _split_axes(axes)
    s = jax.lax.psum_scatter(chunks, ax, scatter_dimension=0)
    if lead:
        s = jax.lax.psum(s, tuple(lead))
    return s * inv_k


def _rs_asa(chunks, axes, inv_k, sum_fn, transfer_dtype):
    """Alltoall -> local fp32 sum (paper Fig 2), optional lead-axes psum of
    the 1/k shard (the hierarchical/DCN leg)."""
    lead, ax = _split_axes(axes)
    if transfer_dtype == jnp.int8 and lead:
        transfer_dtype = jnp.float16   # int8 scaling not plumbed across pods
    if transfer_dtype == jnp.int8:
        q, scale = _quant_rows(chunks)
        recv = jax.lax.all_to_all(q, ax, split_axis=0, concat_axis=0)
        rscale = jax.lax.all_to_all(scale, ax, split_axis=0, concat_axis=0)
        s = jnp.sum(recv.astype(jnp.float32) * rscale, axis=0)
    else:
        if transfer_dtype is not None:
            chunks = chunks.astype(transfer_dtype)
        recv = jax.lax.all_to_all(chunks, ax, split_axis=0, concat_axis=0)
        s = sum_fn(recv)
    if lead:
        s = jax.lax.psum(s, tuple(lead))
    return s * inv_k


def _rs_asa_raw(chunks, axes, sum_fn, transfer_dtype):
    """Transfer-only RS half: the received per-rank chunks BEFORE summation,
    so a fused kernel can do dequant + fp32 sum + update in one VMEM pass.

    Returns ``(recv (k, ...) wire-dtype, scales (k, 1, ...) | None)`` in
    the shape of ``chunks``; the caller owns the mean divisor. Single-axis
    only."""
    lead, ax = _split_axes(axes)
    assert not lead, "raw reduce-scatter is single-axis (intra-pod) only"
    if transfer_dtype == jnp.int8:
        q, scale = _quant_rows(chunks)
        recv = jax.lax.all_to_all(q, ax, split_axis=0, concat_axis=0)
        rscale = jax.lax.all_to_all(scale, ax, split_axis=0, concat_axis=0)
        return recv, rscale
    if transfer_dtype is not None:
        chunks = chunks.astype(transfer_dtype)
    recv = jax.lax.all_to_all(chunks, ax, split_axis=0, concat_axis=0)
    return recv, None


def _rs_ring(chunks, axes, inv_k, sum_fn, transfer_dtype):
    """Ring reduce-scatter via collective_permute; rank i ends holding
    chunk i fully reduced (aligned with the AG/update shard layout)."""
    lead, ax = _split_axes(axes)
    if lead:   # cross-pod: stage hierarchically like asa/hier
        return _rs_asa(chunks, axes, inv_k, sum_fn, transfer_dtype)
    k = jax.lax.axis_size(ax)
    if k == 1:
        return chunks[0] * inv_k
    idx = jax.lax.axis_index(ax)
    fwd = [(i, (i + 1) % k) for i in range(k)]
    # at step s rank i sends its partial of chunk (i-s-1)%k and receives
    # chunk (i-s-2)%k, adding its local copy; after k-1 steps rank i holds
    # chunk i fully reduced.
    acc = jnp.take(chunks, (idx - 1) % k, axis=0)
    for s in range(k - 1):
        acc_t = acc.astype(transfer_dtype) if transfer_dtype is not None else acc
        recv = jax.lax.ppermute(acc_t, ax, fwd).astype(jnp.float32)
        acc = recv + jnp.take(chunks, (idx - s - 2) % k, axis=0)
    return acc * inv_k


def _ag_ring(shard, axes, transfer_dtype):
    """Ring all-gather: after s permutes rank i holds rank (i-s)'s chunk."""
    lead, ax = _split_axes(axes)
    if lead:
        return _ag_flat(shard, axes, transfer_dtype)
    k = jax.lax.axis_size(ax)
    if k == 1:
        return shard
    idx = jax.lax.axis_index(ax)
    fwd = [(i, (i + 1) % k) for i in range(k)]
    buf = jnp.zeros((k, shard.shape[0]), jnp.float32)
    cur = shard
    buf = jax.lax.dynamic_update_index_in_dim(buf, cur, idx, axis=0)
    for s in range(1, k):
        cur_t = cur.astype(transfer_dtype) if transfer_dtype is not None else cur
        cur = jax.lax.ppermute(cur_t, ax, fwd).astype(jnp.float32)
        buf = jax.lax.dynamic_update_index_in_dim(buf, cur, (idx - s) % k,
                                                  axis=0)
    return buf.reshape(-1)


def _ag_flat(shard, axes, transfer_dtype):
    """All-gather the (s,) fp32 shard back to (k*s,) over the rs axis, at
    the wire dtype (int8 requantizes with one fp32 scale per shard)."""
    lead, ax = _split_axes(axes)
    del lead   # lead axes already hold identical shards (post cross-pod psum)
    if transfer_dtype == jnp.int8:
        scale = jnp.max(jnp.abs(shard)) / 127.0 + 1e-12
        q = jnp.clip(jnp.round(shard / scale), -127, 127).astype(jnp.int8)
        out_q = jax.lax.all_gather(q, ax, axis=0, tiled=True)
        out_s = jax.lax.all_gather(scale[None], ax, axis=0, tiled=True)
        s_len = shard.shape[0]
        return out_q.astype(jnp.float32) * jnp.repeat(out_s, s_len, axis=0)
    if transfer_dtype is not None:
        shard = shard.astype(transfer_dtype)
    return jax.lax.all_gather(shard, ax, axis=0, tiled=True).astype(
        jnp.float32)


_RS_FNS = {"ar": _rs_ar, "asa": _rs_asa, "ring": _rs_ring}
_AG_FNS = {"ar": _ag_flat, "asa": _ag_flat, "ring": _ag_ring}


def _flat_bucket(leaves, b: BucketSpec):
    """Bucket ``b``'s leaves, flattened, cast to fp32, concatenated and
    zero-padded to ``b.padded``."""
    f = jnp.concatenate(
        [leaves[i].reshape(-1).astype(jnp.float32) for i in b.leaves])
    pad = b.padded - f.shape[0]
    return jnp.pad(f, (0, pad)) if pad else f


# ---------------------------------------------------------------------------
# pytree-level exchanger
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Exchanger:
    """Named strategy applied bucket-wise to a gradient pytree.

    ``kind`` picks the collective family (``ar`` | ``asa`` | ``ring`` |
    ``none``); ``hier`` is the ``asa`` family over a ('pod', 'data') axis
    tuple. ``transfer_dtype`` is the wire format of both halves."""
    name: str
    kind: str
    transfer_dtype: object = None

    # -- plan / packing helpers (static) ----------------------------------

    def plan_for(self, tree, axis_or_k, bucket_bytes: int = 0) -> RSPlan:
        k = axis_or_k if isinstance(axis_or_k, int) else _axis_size(
            _split_axes(axis_or_k)[1])
        return make_rs_plan(tree, k, bucket_bytes)

    @staticmethod
    def pack(tree, plan: RSPlan):
        """-> (flat fp32 padded bucket list, small-leaf list, leaves)."""
        leaves = jax.tree.flatten(tree)[0]
        flats = [_flat_bucket(leaves, b) for b in plan.buckets]
        return flats, [leaves[i] for i in plan.small], leaves

    @staticmethod
    def split(tree, plan: RSPlan):
        """-> (each bucket's fp32 ``(k, ...)`` view, small-leaf list).

        Row i is what rank i reduces. A shaped bucket is its leaf split on
        the leading dimension, ``(k, d0 // k, *rest)``: no relayout. Any
        other bucket is ``pack``'s flat bucket as ``(k, shard_len)``.
        Row-major, row i holds the same elements in the same order."""
        leaves = jax.tree.flatten(tree)[0]
        views = []
        for b in plan.buckets:
            if b.shaped:
                leaf = leaves[b.leaves[0]].astype(jnp.float32)
                views.append(leaf.reshape(plan.k, -1, *leaf.shape[1:]))
            else:
                views.append(_flat_bucket(leaves, b).reshape(plan.k, -1))
        return views, [leaves[i] for i in plan.small]

    @staticmethod
    def unpack(flats, smalls, plan: RSPlan):
        """Inverse of ``pack``: rebuild the pytree at original shapes/dtypes."""
        out = [None] * len(plan.shapes)
        for b, f in zip(plan.buckets, flats):
            off = 0
            for i, n in zip(b.leaves, b.sizes):
                out[i] = f[off:off + n].reshape(plan.shapes[i]).astype(
                    plan.dtypes[i])
                off += n
        for i, s in zip(plan.small, smalls):
            out[i] = s.astype(plan.dtypes[i]).reshape(plan.shapes[i])
        return jax.tree.unflatten(plan.treedef, out)

    # -- the halves (inside shard_map) ------------------------------------

    def reduce_scatter(self, grads, axis, *, sum_fn=default_chunk_sum,
                       bucket_bytes: int = 0, plan: RSPlan | None = None,
                       raw: bool = False):
        """Mean-reduce and scatter: each rank keeps the fp32 shard of every
        bucket plus the fully psum'd small leaves.

        Returns ``({"shards", "full"}, plan)`` — or with ``raw=True`` (asa
        family only) ``{"chunks", "scales", "full"}`` where chunks are the
        un-summed per-rank receives for the fused RS+update kernel.

        Each bucket comes back in the shape of its ``split`` row: a shaped
        bucket's shard is ``(d0 // k, *rest)`` and its chunks ``(k, d0 //
        k, *rest)``, the leaf's own layout, so a caller that accumulates
        several receives does so with no relayout; any other bucket's is
        ``(shard_len,)`` / ``(k, shard_len)``. ``s.reshape(-1)`` /
        ``c.reshape(k, -1)`` gives the flat form of either, bit for bit."""
        if self.kind == "none":
            raise ValueError("'none' exchanger has no reduce_scatter half")
        if plan is None:
            plan = self.plan_for(grads, axis, bucket_bytes)
        inv_k = 1.0 / _axis_size(axis)
        views, smalls = self.split(grads, plan)
        full = [jax.lax.psum(s.astype(jnp.float32), axis) * inv_k
                for s in smalls]
        if raw:
            if not self.supports_raw:
                raise ValueError(
                    f"raw reduce-scatter unsupported for {self.name!r}")
            pairs = [_rs_asa_raw(v, axis, sum_fn, self.transfer_dtype)
                     for v in views]
            return {"chunks": [p[0] for p in pairs],
                    "scales": [p[1] for p in pairs if p[1] is not None],
                    "full": full}, plan
        rs = _RS_FNS[self.kind]
        shards = [rs(v, axis, inv_k, sum_fn, self.transfer_dtype)
                  for v in views]
        return {"shards": shards, "full": full}, plan

    def all_gather(self, shards, plan: RSPlan, axis, *,
                   wire_dtype=...):
        """Gather (s,) fp32 shards back to (k*s,) flat buckets at the wire
        dtype. ``wire_dtype`` overrides the strategy's transfer dtype (e.g.
        fp32 parameter gathers, or int8 strategies gathering params at
        fp16)."""
        if wire_dtype is ...:
            wire_dtype = self.transfer_dtype
        ag = _AG_FNS[self.kind]
        return [ag(s, axis, wire_dtype) for s in shards]

    @property
    def supports_raw(self) -> bool:
        """Whether reduce_scatter(raw=True) can hand un-summed chunks to the
        fused RS+update kernel (single-axis alltoall family)."""
        return self.kind == "asa"

    # -- full exchange (composition of the halves) ------------------------

    def exchange(self, grads, axis, sum_fn=default_chunk_sum,
                 bucket_bytes: int = 0):
        """Mean-reduce ``grads`` across ``axis`` (str or tuple of axes).

        Composition of ``reduce_scatter`` and ``all_gather``; ``ar`` keeps
        the single fused ``psum`` per bucket so the MPI_Allreduce baseline
        stays one collective (XLA lowers it to RS+AG internally anyway).

        ``bucket_bytes`` > 0 packs leaves into flat fp32 buckets of up to
        that size before exchanging (DDP-style bucketing: fewer, larger
        collectives — a latency win when leaves are many/small). Only valid
        for data-parallel-only setups: flattening would destroy
        model-parallel shardings.
        """
        if self.kind == "none":
            return grads
        plan = self.plan_for(grads, axis, bucket_bytes)
        if self.kind == "ar":
            inv_k = 1.0 / _axis_size(axis)
            flats, smalls, _ = self.pack(grads, plan)
            red = [jax.lax.psum(f, axis) * inv_k for f in flats]
            full = [jax.lax.psum(s.astype(jnp.float32), axis) * inv_k
                    for s in smalls]
            return self.unpack(red, full, plan)
        res, plan = self.reduce_scatter(grads, axis, sum_fn=sum_fn,
                                        plan=plan)
        flats = self.all_gather([s.reshape(-1) for s in res["shards"]],
                                plan, axis)
        return self.unpack(flats, res["full"], plan)


EXCHANGERS: dict[str, Exchanger] = {
    "ar": Exchanger("ar", "ar"),
    "asa": Exchanger("asa", "asa"),
    "asa16": Exchanger("asa16", "asa", jnp.float16),
    "asabf16": Exchanger("asabf16", "asa", jnp.bfloat16),
    "asa8": Exchanger("asa8", "asa", jnp.int8),
    "ring": Exchanger("ring", "ring"),
    "ring16": Exchanger("ring16", "ring", jnp.float16),
    "hier": Exchanger("hier", "asa"),
    "hier16": Exchanger("hier16", "asa", jnp.float16),
    "none": Exchanger("none", "none"),
}


def get_exchanger(name: str) -> Exchanger:
    if name not in EXCHANGERS:
        raise KeyError(f"unknown exchanger {name!r}; known: {sorted(EXCHANGERS)}")
    return EXCHANGERS[name]


def _dtype_bytes(dtype) -> int:
    return 4 if dtype is None else jnp.dtype(dtype).itemsize


def wire_summary(exchanger: Exchanger, plan: RSPlan, *,
                 param_ag: bool = False, sync_every: int = 1) -> dict:
    """Analytic per-rank bytes-on-wire for one full exchange over ``plan``.

    Host-side accounting for telemetry: the collectives themselves run
    inside jitted programs where no host code can observe them, so the
    train loop instead increments ``exchange/bytes_wire`` by this static
    per-step figure (the same modeling discipline as
    ``roofline.analysis.parse_collectives``, but from the plan rather than
    the HLO). Per rank, egress:

    - ``asa``/``ring`` RS: ``(k-1) * shard_len`` elements at the transfer
      dtype per bucket (alltoall / k-1 ppermute hops), int8 adds the
      per-row fp32 scales;
    - AG: the ``shard_len`` shard to each of the other ``k-1`` ranks — at
      the transfer dtype, or :func:`param_wire_dtype` when the gather
      carries updated *parameters* (``param_ag=True``, the RS->update->AG
      path);
    - ``ar``: the classic fused-allreduce volume ``2 (k-1)/k`` of the
      bucket at fp32;
    - small (psum'd) leaves: ``2 (k-1)/k`` of the leaf at fp32.

    ``sync_every`` > 1 (easgd/asgd tau) scales ``bytes_per_step`` down:
    the traffic only moves on averaging steps."""
    k = plan.k
    g_sz = _dtype_bytes(exchanger.transfer_dtype)
    ag_dtype = (param_wire_dtype(exchanger) if param_ag
                else exchanger.transfer_dtype)
    a_sz = _dtype_bytes(ag_dtype)
    int8_rs = exchanger.transfer_dtype == jnp.int8
    int8_ag = ag_dtype == jnp.int8
    rs_b = ag_b = 0
    per_bucket = []
    for b in plan.buckets:
        if exchanger.kind == "none":
            rs, ag = 0, 0
        elif exchanger.kind == "ar":
            half = int(2 * (k - 1) / k * b.padded * 4 / 2)
            rs, ag = half, half
        else:
            rs = (k - 1) * b.shard_len * g_sz
            if int8_rs:
                rs += (k - 1) * 4            # per-row fp32 scales
            ag = (k - 1) * b.shard_len * a_sz
            if int8_ag:
                ag += (k - 1) * 4            # one fp32 scale per shard
        rs_b += rs
        ag_b += ag
        per_bucket.append({"leaves": len(b.leaves), "padded": b.padded,
                           "rs_bytes": rs, "ag_bytes": ag})
    small_b = 0 if exchanger.kind == "none" else sum(
        int(2 * (k - 1) / k * np.prod(plan.shapes[i] or (1,)) * 4)
        for i in plan.small)
    total = rs_b + ag_b + small_b
    return {
        "strategy": exchanger.name,
        "wire_dtype": str(jnp.dtype(exchanger.transfer_dtype or jnp.float32)),
        "ag_dtype": str(jnp.dtype(ag_dtype or jnp.float32)),
        "k": k,
        "num_buckets": plan.num_buckets,
        "rs_bytes": rs_b,
        "ag_bytes": ag_b,
        "small_bytes": small_b,
        "bytes_per_exchange": total,
        "sync_every": sync_every,
        "bytes_per_step": total / max(sync_every, 1),
        "per_bucket": per_bucket,
    }


def param_wire_dtype(exchanger: Exchanger):
    """Wire format for the updated-parameter all-gather leg of the
    RS->update->AG path: the strategy's transfer dtype, except int8
    strategies gather params at fp16 (absmax-int8 on weights is too lossy
    to re-apply every step)."""
    if exchanger.transfer_dtype == jnp.int8:
        return jnp.float16
    return exchanger.transfer_dtype
