"""Jitted public wrappers over the Pallas kernels.

Execution mode is auto-selected per backend (compiled on TPU, Pallas
interpreter elsewhere) — see ``repro.kernels.default_interpret`` for the
``REPRO_PALLAS_INTERPRET`` override.
The wrappers match the exchanger/optimizer plug-in contracts.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import chunk_sum as _cs
from repro.kernels import fused_rs_update as _fru
from repro.kernels import fused_sgd as _fs
from repro.kernels import quantize as _q


def chunk_sum(chunks, block_n: int = _cs.DEFAULT_BLOCK_N):
    """Exchanger ``sum_fn`` plug-in: (k, ...) -> (...) fp32.

    Flattens trailing dims to the kernel's (k, n) contract."""
    k = chunks.shape[0]
    flat = chunks.reshape(k, -1)
    out = _cs.chunk_sum(flat, block_n=block_n)
    return out.reshape(chunks.shape[1:])


def quant_fp16(x):
    return _q.quant_fp16(x.reshape(-1)).reshape(x.shape)


def dequant_fp16(x):
    return _q.dequant_fp16(x.reshape(-1)).reshape(x.shape)


def quant_int8(x, block_n: int = _q.DEFAULT_BLOCK_N):
    return _q.quant_int8(x.reshape(-1), block_n=block_n)


def dequant_int8(q, scales, block_n: int = _q.DEFAULT_BLOCK_N):
    return _q.dequant_int8(q, scales, block_n=block_n)


def fused_sgd(p, g, m, lr, momentum=0.9, nesterov=False):
    """Optimizer plug-in: nd-arrays, fp32 out, original shape preserved."""
    shape = p.shape
    po, mo = _fs.fused_sgd(p.reshape(-1), g.reshape(-1), m.reshape(-1), lr,
                           momentum=float(momentum), nesterov=bool(nesterov))
    return po.reshape(shape), mo.reshape(shape)


def fused_rs_update(recv, p, m, lr, *, wd_mask=None, scale=1.0,
                    momentum=0.9, nesterov=False, weight_decay=0.0,
                    scales=None):
    """RS->update fusion plug-in (``Optimizer.rs_fused_update``): un-summed
    (k, n) alltoall receives + flat shard (p, m) -> (p', m') fp32.

    ``scale`` is the mean divisor folded into the summation (1/k, or
    1/(k*microbatches) when accumulating); ``scales`` are the per-chunk
    int8 dequant scales for the ``asa8`` wire format."""
    mask = (jnp.zeros_like(p, jnp.float32) if wd_mask is None
            else wd_mask.astype(jnp.float32))
    return _fru.fused_rs_update(
        recv, p.reshape(-1), m.reshape(-1), mask.reshape(-1), lr,
        momentum=float(momentum), nesterov=bool(nesterov),
        scale=float(scale), weight_decay=float(weight_decay),
        scales=scales)
