"""Pallas TPU kernel: fused reduce-scatter tail — dequant + fp32 chunk sum
+ momentum-SGD — on the local parameter shard in one VMEM pass.

After the alltoall leg of the RS half, each rank holds ``k`` low-precision
chunks of its shard. The unfused pipeline materializes the fp32 sum in HBM
(``chunk_sum``), then re-reads it together with (p, m) for the update
(``fused_sgd``). This kernel streams one tile of receives plus the
matching (p, m, wd_mask) tiles through VMEM and emits (p', m') directly:

    g  = scale * sum_k dequant(recv[k])        (fp32 accumulation)
    g += weight_decay * wd_mask * p
    m' = mu * m + g
    p' = p - lr * (g + mu * m')    (nesterov)
       = p - lr * m'               (classic)

``scale`` folds the data-parallel mean (1/k) and any microbatch-accumulation
mean (1/m) into the same pass. The int8 variant takes one fp32 scale per
rank chunk (the wire format of ``asa8``) and dequantizes in-register.

Tiling. The kernel is bound by HBM bytes (24 per parameter for a float32
receive at ``k = 1``), so each grid step must move enough to hide its fixed
cost. The wrapper views the flat float32 operands as lane-dense
``(n/128, 128)`` arrays and the receive as ``(k, n/128, 128)``. On the TPU
a 1-D float32 array tiled ``T(1024)`` (and a ``(1, n)`` one tiled
``T(1,128)``) holds its elements in the same order as a ``(rows, 128)``
array tiled ``T(8,128)``, so XLA lowers the view as a bitcast, not a copy;
only an ``n`` that is not a multiple of 128 is padded. The block is
``(br, 128)`` for float32 operands and ``(k, br, 128)`` for the receive:
the leading ``k`` is untiled, so a one-row receive fills whole vregs.
``br`` follows from ``k`` and the receive's itemsize: the largest multiple
of 32 rows (whole tiles for float32, float16 and int8) whose
double-buffered tiles of all six operands fit ``VMEM_TILE_BYTES``, which
sits inside the default scoped VMEM. A shard smaller than one block is one
block. The grid is ``cdiv(rows, br)``; Pallas masks the ragged last block,
so nothing is padded to whole blocks (the update is elementwise: the
rows past the end are read as garbage and never written).

Parity-tested against ``ref.fused_rs_update_ref`` and ``default_chunk_sum``
+ ``fused_sgd`` in ``tests/test_kernels.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

LANES = 128
ROW_ALIGN = 32                  # whole (32, 128) int8 / (16, 128) f16 tiles
VMEM_TILE_BYTES = 8 * 2 ** 20   # double-buffered tiles of all operands


def tile_rows(k: int, itemsize: int) -> int:
    """Rows per block for a ``(k, ·, 128)`` receive of ``itemsize`` bytes:
    two buffers each of the receive, p, m, mask, p' and m' tiles."""
    row_bytes = 2 * LANES * (k * itemsize + 5 * 4)
    rows = VMEM_TILE_BYTES // row_bytes // ROW_ALIGN * ROW_ALIGN
    return max(ROW_ALIGN, rows)


def _update_tail(r, p_ref, m_ref, mask_ref, lr_ref, po_ref, mo_ref, *,
                 momentum, nesterov, scale, weight_decay):
    """Shared sum + momentum-SGD tail; ``r`` is the dequantized (k, br, 128)
    receive tile (plain function — Pallas inlines it into both variants)."""
    g = jnp.sum(r, axis=0) * scale
    p = p_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    if weight_decay:
        g = g + weight_decay * mask_ref[...] * p
    lr = lr_ref[0]
    m_new = momentum * m + g
    step = g + momentum * m_new if nesterov else m_new
    po_ref[...] = p - lr * step
    mo_ref[...] = m_new


def _kernel(recv_ref, p_ref, m_ref, mask_ref, lr_ref, po_ref, mo_ref,
            **statics):
    r = recv_ref[...].astype(jnp.float32)          # (k, br, 128)
    _update_tail(r, p_ref, m_ref, mask_ref, lr_ref, po_ref, mo_ref,
                 **statics)


def _kernel_q(recv_ref, scales_ref, p_ref, m_ref, mask_ref, lr_ref,
              po_ref, mo_ref, **statics):
    r = recv_ref[...].astype(jnp.float32) * scales_ref[...]  # * (k, 1, 1)
    _update_tail(r, p_ref, m_ref, mask_ref, lr_ref, po_ref, mo_ref,
                 **statics)


@functools.partial(jax.jit,
                   static_argnames=("momentum", "nesterov", "scale",
                                    "weight_decay", "block_rows",
                                    "interpret"))
def fused_rs_update(recv, p, m, mask, lr, *, momentum: float = 0.9,
                    nesterov: bool = False, scale: float = 1.0,
                    weight_decay: float = 0.0, scales=None,
                    block_rows: int | None = None,
                    interpret: bool | None = None):
    """recv: (k, n) float or int8 chunks; p/m/mask: (n,); scales: (k,) fp32
    per-chunk dequant scales (int8 wire) or None -> (p', m') fp32 (n,).

    ``block_rows`` overrides the derived block height (tests use it to run
    several blocks at a small ``n``)."""
    interpret = resolve_interpret(interpret)
    k, n = recv.shape
    pad = (-n) % LANES
    if pad:
        recv = jnp.pad(recv, ((0, 0), (0, pad)))
        p, m, mask = (jnp.pad(x, (0, pad)) for x in (p, m, mask))
    rows = (n + pad) // LANES
    br = min(rows, block_rows or tile_rows(k, recv.dtype.itemsize))
    recv = recv.reshape(k, rows, LANES)
    p, m, mask = (x.reshape(rows, LANES) for x in (p, m, mask))
    lr_arr = jnp.asarray([lr], jnp.float32)
    tile = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    recv_spec = pl.BlockSpec((k, br, LANES), lambda i: (0, i, 0))
    lr_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    common = dict(
        grid=(pl.cdiv(rows, br),),
        out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), jnp.float32)] * 2,
        interpret=interpret,
    )
    statics = dict(momentum=momentum, nesterov=nesterov, scale=scale,
                   weight_decay=weight_decay)
    if scales is None:
        po, mo = pl.pallas_call(
            functools.partial(_kernel, **statics),
            in_specs=[recv_spec, tile, tile, tile, lr_spec],
            **common,
        )(recv, p, m, mask, lr_arr)
    else:
        po, mo = pl.pallas_call(
            functools.partial(_kernel_q, **statics),
            in_specs=[recv_spec, pl.BlockSpec((k, 1, 1), lambda i: (0, 0, 0)),
                      tile, tile, tile, lr_spec],
            **common,
        )(recv, scales.reshape(k, 1, 1).astype(jnp.float32), p, m, mask,
          lr_arr)
    return po.reshape(-1)[:n], mo.reshape(-1)[:n]
