"""Pallas flash-attention kernel family: the one attention hot path.

Tiled attention in the FlashAttention style (PAPERS.md: "FlashAttention:
Fast and Memory-Efficient Exact Attention with IO-Awareness") for every
attention site in the repo — train forward/backward, chunked prefill, and
(B,1) decode — so no path ever materializes an (S, S) score matrix in HBM.

Contracts shared by the whole family:

- **Head-major tiles.** Every block's last two dims are (rows, head_dim),
  with head_dim the array's whole last dim — the shape the TPU compiler
  tiles. So the kernels read q as (B, KV, G, Sq, Dk) and k/v as
  (B, KV, Sk, Dk/Dv). ``flash_attention`` and ``flash_decode`` take the
  model's (B, S, H, D) operands and transpose them in the wrapper; the
  paged serve cache stores its pages head-major ((P, KV, page_size, D)),
  so ``flash_decode_paged`` transposes nothing (DESIGN.md "Attention
  kernels" gives the HBM cost).
- **GQA grouping inside the kernel.** G = H // KV query heads share a kv
  head; the grid iterates (batch, kv_head, ...) and each q tile carries its
  group's G heads as extra rows of the score matmul ((G*block_q, block_k)
  on the MXU, group-major: row r is head r // block_q, query r % block_q),
  so k/v are never repeated across query heads in HBM. KV=1 with Dk != Dv
  is the MLA absorbed-matmul layout (q/k in the latent+rope space, v = the
  latent itself).
- **fp32 online softmax, bf16/fp16 I/O.** Scores, the running (m, l)
  statistics and the output accumulator live in fp32 VMEM scratch;
  q/k/v/out move through HBM in the model's compute dtype.
- **Residuals are (out, lse).** The forward saves only the output and the
  per-row log-sum-exp — the backward recomputes p tile-wise from
  (q, k, lse), never storing probabilities.
- **Masking = causal + sliding window + ragged tails.** Causality is
  evaluated against absolute positions ``q_off[b] + row`` (q_off=0 for
  train, the chunk start for prefill, the per-slot position vector for
  decode), so one kernel serves all three paths; ``window`` may be a
  traced scalar (per-layer windows inside layer scans). Positions and the
  window reach the kernels as scalar-prefetch operands. Key tiles entirely
  above the causal diagonal are skipped. Rows/keys padded up to the tile
  size are masked out (keys) or sliced off (rows).

Execution mode follows the package policy (compiled on TPU, interpreter
elsewhere); parity against the einsum oracles is pinned in
``tests/test_flash_attention.py`` and the v5e compile in
``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
DEFAULT_DECODE_BLOCK_K = 512


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _scratch(shape):
    return pltpu.VMEM(shape, jnp.float32)


def _dot(a, b, trans_b: bool = False):
    dims = (((1,), (1,)), ((), ())) if trans_b else (((1,), (0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _heads_major(x):
    """(B, S, N, D) -> (B, N, S, D)."""
    return x.transpose(0, 2, 1, 3)


def _mask(keep_shape, i, j, q_off, window, kv_len, block_q, block_k):
    """(rows, block_k) keep mask; row r is query r % block_q of the tile."""
    r = jax.lax.broadcasted_iota(jnp.int32, keep_shape, 0)
    c = jax.lax.broadcasted_iota(jnp.int32, keep_shape, 1)
    qpos = q_off + i * block_q + jax.lax.rem(r, block_q)
    kpos = j * block_k + c
    keep = (kpos <= qpos) & (kpos < kv_len)
    dist = qpos - kpos
    return keep & ((window <= 0) | (dist < window))


def _tile_live(i, j, q_off, window, block_q, block_k):
    """Whether key tile j can contribute to q tile i: not entirely above
    the causal diagonal, and (for sliding windows) not entirely older than
    the window of the tile's oldest query. Exact — a skipped tile's mask
    is all-False, so every pruned contribution was a 0. Makes windowed
    attention's grid work linear in S instead of quadratic."""
    causal = j * block_k <= q_off + (i + 1) * block_q - 1
    in_window = (window <= 0) | (
        (j + 1) * block_k > q_off + i * block_q - window + 1)
    return causal & in_window


def _row_spec(groups, block_q, d, index_map):
    """A (G, block_q, d) tile of a (B, KV, G, Sq, d) array."""
    return pl.BlockSpec((1, 1, groups, block_q, d), index_map)


def _kv_spec(block_k, d, index_map):
    """A (block_k, d) tile of a (B, KV, Sk, d) array."""
    return pl.BlockSpec((1, 1, block_k, d), index_map)


def _rows(ref, rows):
    """Load a (1, 1, G, block_q, d) tile as (G * block_q, d) rows."""
    return ref[0, 0].reshape(rows, ref.shape[-1])


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(qoff_ref, win_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, sm_scale, kv_len, block_q,
                block_k, groups):
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    rows = block_q * groups
    q_off = qoff_ref[b]
    win = win_ref[0]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(_tile_live(i, j, q_off, win, block_q, block_k))
    def _compute():
        q = _rows(q_ref, rows)
        k = k_ref[0, 0]
        s = _dot(q, k, trans_b=True) * sm_scale          # (rows, bk) fp32
        keep = _mask(s.shape, i, j, q_off, win, kv_len, block_q, block_k)
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[...][:, :1]
        l_prev = l_scr[...][:, :1]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # explicit zeroing: when every key so far is masked m_next is still
        # NEG_INF and exp(s - m_next) would be 1, not 0
        p = jnp.where(keep, jnp.exp(s - m_next), 0.0)
        alpha = jnp.exp(m_prev - m_next)
        l_next = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_next, l_scr.shape)
        v = v_ref[0, 0]
        acc_scr[...] = acc_scr[...] * alpha + _dot(p.astype(v.dtype), v)

    @pl.when(j == nk - 1)
    def _store():
        l = jnp.maximum(l_scr[...][:, :1], 1e-30)
        out = acc_scr[...] / l
        o_ref[0, 0] = out.reshape(o_ref.shape[2:]).astype(o_ref.dtype)
        lse = m_scr[...][:, :1] + jnp.log(l)
        lse_ref[0, 0] = lse.reshape(lse_ref.shape[2:])


def _fwd_call(q, k, v, q_off, window, sm_scale, kv_len, block_q, block_k,
              interpret):
    B, KV, G, Sq, Dk = q.shape
    Sk, Dv = k.shape[2], v.shape[-1]
    rows = block_q * G
    qi = lambda b, h, i, j, *_: (b, h, 0, i, 0)
    kj = lambda b, h, i, j, *_: (b, h, j, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                     # q_off, window
        grid=(B, KV, Sq // block_q, Sk // block_k),
        in_specs=[_row_spec(G, block_q, Dk, qi), _kv_spec(block_k, Dk, kj),
                  _kv_spec(block_k, Dv, kj)],
        out_specs=[_row_spec(G, block_q, Dv, qi),
                   _row_spec(G, block_q, 1, qi)],
        scratch_shapes=[_scratch((rows, 128)), _scratch((rows, 128)),
                        _scratch((rows, Dv))],
    )
    return pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, kv_len=kv_len,
                          block_q=block_q, block_k=block_k, groups=G),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, KV, G, Sq, Dv), q.dtype),
                   jax.ShapeDtypeStruct((B, KV, G, Sq, 1), jnp.float32)],
        interpret=interpret,
    )(q_off, window, q, k, v)


# ---------------------------------------------------------------------------
# backward (dq and dkv kernels; p recomputed tile-wise from lse)
# ---------------------------------------------------------------------------

def _recompute(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, i, j, q_off,
               win, *, sm_scale, kv_len, block_q, block_k, rows):
    """The tile's (q, k, do, p, ds) rebuilt from the saved lse."""
    q = _rows(q_ref, rows)
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = _rows(do_ref, rows)
    lse = _rows(lse_ref, rows)                     # (rows, 1)
    di = _rows(di_ref, rows)
    s = _dot(q, k, trans_b=True) * sm_scale
    keep = _mask(s.shape, i, j, q_off, win, kv_len, block_q, block_k)
    p = jnp.exp(jnp.where(keep, s, NEG_INF) - lse)  # masked -> exp(-inf)=0
    dp = _dot(do, v, trans_b=True)                  # (rows, bk)
    ds = p * (dp - di) * sm_scale
    return q, k, do, p, ds


def _dq_kernel(qoff_ref, win_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               di_ref, dq_ref, dq_scr, *, sm_scale, kv_len, block_q,
               block_k, groups):
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    q_off = qoff_ref[b]
    win = win_ref[0]

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(_tile_live(i, j, q_off, win, block_q, block_k))
    def _compute():
        _, k, _, _, ds = _recompute(
            q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, i, j, q_off, win,
            sm_scale=sm_scale, kv_len=kv_len, block_q=block_q,
            block_k=block_k, rows=block_q * groups)
        dq_scr[...] = dq_scr[...] + _dot(ds.astype(k.dtype), k)

    @pl.when(j == nk - 1)
    def _store():
        dq_ref[0, 0] = dq_scr[...].reshape(dq_ref.shape[2:]).astype(
            dq_ref.dtype)


def _dkv_kernel(qoff_ref, win_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                di_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale,
                kv_len, block_q, block_k, groups):
    b, j, i = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)                        # kv tile j, q tile i
    q_off = qoff_ref[b]
    win = win_ref[0]

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(_tile_live(i, j, q_off, win, block_q, block_k))
    def _compute():
        q, _, do, p, ds = _recompute(
            q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, i, j, q_off, win,
            sm_scale=sm_scale, kv_len=kv_len, block_q=block_q,
            block_k=block_k, rows=block_q * groups)
        # contract over the rows axis: the G grouped query heads fold into
        # the same dk/dv tile, which is exactly the GQA gradient
        contract_rows = (((0,), (0,)), ((), ()))
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
            p.astype(do.dtype), do, contract_rows,
            preferred_element_type=jnp.float32)
        dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
            ds.astype(q.dtype), q, contract_rows,
            preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _store():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_call(q, k, v, q_off, window, out, lse, do, sm_scale, kv_len,
              block_q, block_k, interpret):
    B, KV, G, Sq, Dk = q.shape
    Sk, Dv = k.shape[2], v.shape[-1]
    rows = block_q * G
    di = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                 axis=-1, keepdims=True)            # (B, KV, G, Sq, 1)
    kw = dict(sm_scale=sm_scale, kv_len=kv_len, block_q=block_q,
              block_k=block_k, groups=G)

    def in_specs(qi, kj):
        return [_row_spec(G, block_q, Dk, qi), _kv_spec(block_k, Dk, kj),
                _kv_spec(block_k, Dv, kj), _row_spec(G, block_q, Dv, qi),
                _row_spec(G, block_q, 1, qi), _row_spec(G, block_q, 1, qi)]

    qi = lambda b, h, i, j, *_: (b, h, 0, i, 0)
    kj = lambda b, h, i, j, *_: (b, h, j, 0)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, KV, Sq // block_q, Sk // block_k),
            in_specs=in_specs(qi, kj),
            out_specs=_row_spec(G, block_q, Dk, qi),
            scratch_shapes=[_scratch((rows, Dk))]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q_off, window, q, k, v, do, lse, di)

    qi_t = lambda b, h, j, i, *_: (b, h, 0, i, 0)
    kj_t = lambda b, h, j, i, *_: (b, h, j, 0)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, KV, Sk // block_k, Sq // block_q),
            in_specs=in_specs(qi_t, kj_t),
            out_specs=[_kv_spec(block_k, Dk, kj_t),
                       _kv_spec(block_k, Dv, kj_t)],
            scratch_shapes=[_scratch((block_k, Dk)),
                            _scratch((block_k, Dv))]),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        interpret=interpret,
    )(q_off, window, q, k, v, do, lse, di)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom VJP over the padded head-major core
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, q_off, window, sm_scale, kv_len, block_q, block_k,
           interpret):
    return _fwd_call(q, k, v, q_off, window, sm_scale, kv_len, block_q,
                     block_k, interpret)


def _flash_fwd(q, k, v, q_off, window, sm_scale, kv_len, block_q, block_k,
               interpret):
    out, lse = _fwd_call(q, k, v, q_off, window, sm_scale, kv_len, block_q,
                         block_k, interpret)
    return (out, lse), (q, k, v, q_off, window, out, lse)


def _flash_bwd(sm_scale, kv_len, block_q, block_k, interpret, res, cts):
    q, k, v, q_off, window, out, lse = res
    do, _ = cts          # the lse output is a residual, not a model output
    dq, dk, dv = _bwd_call(q, k, v, q_off, window, out, lse, do, sm_scale,
                           kv_len, block_q, block_k, interpret)
    zero = lambda x: np.zeros(x.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, zero(q_off), zero(window)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, q_off=None, window=0, sm_scale=None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool | None = None, return_lse: bool = False):
    """Fused tiled attention. q: (B, Sq, H, Dk); k: (B, Sk, KV, Dk);
    v: (B, Sk, KV, Dv) with H % KV == 0. Returns (B, Sq, H, Dv) [+ lse
    (B, Sq, H) fp32 when ``return_lse``; do not differentiate through lse].

    ``q_off``: absolute position of q row 0 — None/scalar/(B,) vector
    (train / chunked prefill / per-slot decode). ``window``: sliding
    window (<=0 = plain causal), python int or traced scalar. ``sm_scale``
    defaults to 1/sqrt(Dk). Ragged Sq/Sk are padded to the tile size
    internally; padded keys are masked, padded rows sliced off."""
    B, Sq, H, Dk = q.shape
    _, Sk, KV, _ = k.shape
    Dv = v.shape[-1]
    if H % KV:
        raise ValueError(f"H={H} not divisible by KV={KV}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(Dk)
    interpret = resolve_interpret(interpret)
    block_q = min(block_q, _round_up(Sq, 16))
    block_k = min(block_k, _round_up(Sk, 16))
    Sqp, Skp = _round_up(Sq, block_q), _round_up(Sk, block_k)
    pad = lambda x, n: jnp.pad(x, ((0, 0), (0, n), (0, 0), (0, 0)))
    q = _heads_major(pad(q, Sqp - Sq)).reshape(B, KV, H // KV, Sqp, Dk)
    k = _heads_major(pad(k, Skp - Sk))
    v = _heads_major(pad(v, Skp - Sk))
    if q_off is None:
        q_off = jnp.zeros((B,), jnp.int32)
    else:
        q_off = jnp.broadcast_to(
            jnp.asarray(q_off, jnp.int32).reshape(-1), (B,))
    window = jnp.asarray(window, jnp.int32).reshape(1)
    out, lse = _flash(q, k, v, q_off, window, float(sm_scale), Sk,
                      block_q, block_k, interpret)
    out = _heads_major(out.reshape(B, H, Sqp, Dv)[:, :, :Sq])
    if not return_lse:
        return out
    # lse is a residual, not a differentiable output — the VJP discards
    # its cotangent, so enforce the contract rather than return silent
    # zero gradients to anyone who puts lse in a loss
    lse = lse.reshape(B, H, Sqp)[:, :, :Sq].transpose(0, 2, 1)
    return out, jax.lax.stop_gradient(lse)


# ---------------------------------------------------------------------------
# split-KV decode
# ---------------------------------------------------------------------------

def _decode_kernel(pos_ref, win_ref, q_ref, k_ref, v_ref, m_ref, l_ref,
                   acc_ref, *, sm_scale, kv_len, block_k):
    """One KV split's partial (m, l, acc) for the G query heads that share
    kv head ``program_id(1)``; a dead split writes the neutral partial."""
    b, j = pl.program_id(0), pl.program_id(2)
    pos = pos_ref[b]
    win = win_ref[0]
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_tile_live(0, j, pos, win, 1, block_k))
    def _compute():
        q = q_ref[0, 0]                                  # (G, Dk)
        k = k_ref[0, 0]                                  # (bk, Dk)
        v = v_ref[0, 0]
        s = _dot(q, k, trans_b=True) * sm_scale          # (G, bk)
        c = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        kpos = j * block_k + c
        keep = (kpos <= pos) & (kpos < kv_len)
        keep &= (win <= 0) | (pos - kpos < win)
        s = jnp.where(keep, s, NEG_INF)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.where(keep, jnp.exp(s - m), 0.0)
        m_ref[0, 0, 0] = m
        l_ref[0, 0, 0] = jnp.sum(p, axis=1, keepdims=True)
        acc_ref[0, 0, 0] = _dot(p.astype(v.dtype), v)


def _decode_specs(G, Dk, Dv, block_k, kv_index):
    """Decode block specs: the group's (G, Dk) query rows, one KV tile at
    ``kv_index(b, h, j, *scalars)``, and the split's (m, l, acc) partial."""
    q_spec = pl.BlockSpec((1, 1, G, Dk), lambda b, h, j, *_: (b, h, 0, 0))
    part = lambda d: pl.BlockSpec((1, 1, 1, G, d),
                                  lambda b, h, j, *_: (b, h, j, 0, 0))
    return ([q_spec, pl.BlockSpec((1, 1, block_k, Dk), kv_index),
             pl.BlockSpec((1, 1, block_k, Dv), kv_index)],
            [part(1), part(1), part(Dv)])


def _decode_grouped_q(q, KV):
    """(B, 1, H, Dk) -> (B, KV, G, Dk), a free reshape."""
    B, Sq, H, Dk = q.shape
    if Sq != 1:
        raise ValueError(f"decode wants a single query row, Sq={Sq}")
    if H % KV:
        raise ValueError(f"H={H} not divisible by KV={KV}")
    return q.reshape(B, KV, H // KV, Dk)


def flash_decode(q, k, v, pos, *, window=0, sm_scale=None,
                 block_k: int = DEFAULT_DECODE_BLOCK_K,
                 interpret: bool | None = None):
    """Split-KV single-token decode. q: (B, 1, H, Dk); k/v: the full
    (B, S, KV, Dk/Dv) cache lanes; pos: scalar or (B,) per-slot positions
    (``decode_keep`` semantics: key t visible iff t <= pos[b] and within
    the window). The cache splits into ``ceil(S / block_k)`` independent
    key chunks — each computes a partial (m, l, acc) in one grid cell, and
    the partials merge with the standard online-softmax combine, so long
    caches parallelize across chunks instead of serializing through one
    accumulator. The lanes are transposed head-major in the wrapper.
    Returns (B, 1, H, Dv)."""
    B, _, H, Dk = q.shape
    _, S, KV, _ = k.shape
    Dv = v.shape[-1]
    qg = _decode_grouped_q(q, KV)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(Dk)
    block_k = min(block_k, _round_up(S, 16))
    Sp = _round_up(S, block_k)
    pad = lambda x: jnp.pad(x, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    k, v = _heads_major(pad(k)), _heads_major(pad(v))
    ns = Sp // block_k
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    window = jnp.asarray(window, jnp.int32).reshape(1)
    in_specs, out_specs = _decode_specs(
        H // KV, Dk, Dv, block_k, lambda b, h, j, *_: (b, h, j, 0))
    m, l, acc = pl.pallas_call(
        functools.partial(_decode_kernel, sm_scale=float(sm_scale),
                          kv_len=S, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, KV, ns),      # pos, window
            in_specs=in_specs, out_specs=out_specs),
        out_shape=_partial_shapes(B, KV, ns, H // KV, Dv),
        interpret=resolve_interpret(interpret),
    )(pos, window, qg, k, v)
    return _combine_kv_splits(m, l, acc).astype(q.dtype)


def _partial_shapes(B, KV, ns, G, Dv):
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    return [f32(B, KV, ns, G, 1), f32(B, KV, ns, G, 1),
            f32(B, KV, ns, G, Dv)]


def _combine_kv_splits(m, l, acc):
    """Online-softmax combine across independent KV splits: partials
    m/l (B, KV, ns, G, 1) and acc (B, KV, ns, G, Dv) -> (B, 1, H, Dv)
    fp32. Shared by the contiguous (``flash_decode``) and paged
    (``flash_decode_paged``) split-KV kernels — a dead split's neutral
    partial (m=NEG_INF, l=0, acc=0) drops out exactly."""
    B, KV, _, G, _ = m.shape
    Dv = acc.shape[-1]
    m_g = jnp.max(m, axis=2, keepdims=True)                  # (B,KV,1,G,1)
    alpha = jnp.exp(m - m_g)
    l_g = jnp.sum(alpha * l, axis=2)                         # (B,KV,G,1)
    out = jnp.sum(alpha * acc, axis=2)                       # (B,KV,G,Dv)
    out = out / jnp.maximum(l_g, 1e-30)
    return out.reshape(B, 1, KV * G, Dv)


def flash_decode_paged(q, k_pages, v_pages, tables, pos, *, page_size: int,
                       window=0, sm_scale=None,
                       interpret: bool | None = None):
    """Split-KV decode over a *paged* cache: the grid's chunk axis walks
    each slot's block table one page per chunk, and the K/V BlockSpec
    index_maps read the physical page id from the scalar-prefetched table
    (``pltpu.PrefetchScalarGridSpec``), so page fetch is table-indexed
    inside the kernel — no gathered lane ever materializes in HBM. The
    compiled program is one trace for any table contents (tables/pos enter
    as same-shaped int32 inputs), preserving the engine's compile-once
    guarantee under request churn.

    q: (B, 1, H, Dk); k_pages/v_pages: (P, KV, page_size, Dk/Dv) physical
    pages, head-major; tables: (B, NP) int32 page ids (logical page j of
    slot b is physical page tables[b, j]); pos: (B,) per-slot positions.
    Pages at logical index > pos // page_size are skipped with neutral
    partials exactly like dead KV chunks in ``flash_decode`` — whatever
    stale page the table maps there (typically the null page 0) is never
    read into the combine. Returns (B, 1, H, Dv).

    Math is bit-identical to ``flash_decode`` over the gathered lanes with
    ``block_k=page_size``: same per-page partials, same combine."""
    B, _, H, Dk = q.shape
    _, KV, ps, _ = k_pages.shape
    Dv = v_pages.shape[-1]
    if ps != page_size:
        raise ValueError(f"page dim {ps} != page_size {page_size}")
    qg = _decode_grouped_q(q, KV)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(Dk)
    NP = tables.shape[-1]
    tables = jnp.asarray(tables, jnp.int32).reshape(B, NP)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    window = jnp.asarray(window, jnp.int32).reshape(1)
    in_specs, out_specs = _decode_specs(
        H // KV, Dk, Dv, page_size,
        lambda b, h, j, tbl, *_: (tbl[b, j], h, 0, 0))
    kernel = functools.partial(_decode_kernel, sm_scale=float(sm_scale),
                               kv_len=NP * page_size, block_k=page_size)
    m, l, acc = pl.pallas_call(
        lambda tbl_ref, *refs: kernel(*refs),   # tables feed index_maps
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,              # tables, pos, window
            grid=(B, KV, NP), in_specs=in_specs, out_specs=out_specs),
        out_shape=_partial_shapes(B, KV, NP, H // KV, Dv),
        interpret=resolve_interpret(interpret),
    )(tables, pos, window, qg, k_pages, v_pages)
    return _combine_kv_splits(m, l, acc).astype(q.dtype)
