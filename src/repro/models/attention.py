"""Attention: GQA (+QKV bias, qk-norm, sliding window) and DeepSeek MLA.

Train path computes full (windowed-)causal attention; decode path attends one
query against a KV cache (GQA caches k/v; MLA caches the 512-d latent + the
shared rope key and uses the absorbed-matmul trick, so the cache is 576
floats/token as in the paper).

Three interchangeable attention implementations back every path
(``resolve_attn_impl``; DESIGN.md "Attention kernels"):

- ``flash``:     the Pallas tiled kernels (``kernels/flash_attention``) —
                 fused online-softmax forward + custom-VJP backward for
                 train, q-chunk×cache tiles for prefill, split-KV for
                 decode. The default wherever Pallas compiles (TPU).
- ``ref``:       the XLA einsum paths below — the parity oracles, and the
                 default on interpret-only backends (CPU). Long sequences
                 still route through the blockwise scan when
                 ``AttentionConfig.block_kv`` is set.
- ``blockwise``: force the ``lax.scan`` online-softmax fallback.

Selection: ``REPRO_ATTN_IMPL`` env > ``AttentionConfig.attn_impl`` >
backend default.
"""
from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, AttentionConfig
from repro.kernels.flash_attention import (flash_attention, flash_decode,
                                           flash_decode_paged)
from repro.models.common import (apply_rope, dense_init, head_rms_norm)

NEG_INF = -1e30

_IMPLS = ("flash", "ref", "blockwise")


def resolve_attn_impl(a: AttentionConfig | None) -> str:
    """Resolve the attention implementation for a config.

    Priority: ``REPRO_ATTN_IMPL`` env > ``a.attn_impl`` > backend default
    (``flash`` where Pallas kernels compile — i.e. not in interpreter
    mode — else the einsum ``ref`` oracles)."""
    impl = os.environ.get("REPRO_ATTN_IMPL", "") or (
        (a.attn_impl or "") if a is not None else "")
    if impl in ("", "auto"):
        from repro.kernels import default_interpret
        return "ref" if default_interpret() else "flash"
    if impl not in _IMPLS:
        raise ValueError(
            f"REPRO_ATTN_IMPL / attn_impl must be one of {_IMPLS} or "
            f"'auto', got {impl!r}")
    return impl


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_gqa(key, cfg: ArchConfig, a: AttentionConfig, dtype):
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, (a.num_heads, a.head_dim), dtype),
        "wk": dense_init(ks[1], d, (a.num_kv_heads, a.head_dim), dtype),
        "wv": dense_init(ks[2], d, (a.num_kv_heads, a.head_dim), dtype),
        "wo": dense_init(ks[3], a.num_heads * a.head_dim, (d,), dtype),
    }
    if a.qkv_bias:
        p["bq"] = jnp.zeros((a.num_heads, a.head_dim), dtype)
        p["bk"] = jnp.zeros((a.num_kv_heads, a.head_dim), dtype)
        p["bv"] = jnp.zeros((a.num_kv_heads, a.head_dim), dtype)
    return p


def init_mla(key, cfg: ArchConfig, a: AttentionConfig, dtype):
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    qd = a.qk_nope_dim + a.qk_rope_dim
    return {
        "wq": dense_init(ks[0], d, (a.num_heads, qd), dtype),
        "wdkv": dense_init(ks[1], d, (a.kv_lora_rank,), dtype),
        "wkr": dense_init(ks[2], d, (a.qk_rope_dim,), dtype),
        # up-projections from the latent
        "wuk": dense_init(ks[3], a.kv_lora_rank,
                          (a.num_heads, a.qk_nope_dim), dtype),
        "wuv": dense_init(ks[4], a.kv_lora_rank,
                          (a.num_heads, a.v_head_dim), dtype),
        "wo": dense_init(jax.random.fold_in(key, 7),
                         a.num_heads * a.v_head_dim, (d,), dtype),
    }


def init_attention(key, cfg: ArchConfig, dtype):
    a = cfg.attention
    assert a is not None
    if a.kv_lora_rank:
        return init_mla(key, cfg, a, dtype)
    return init_gqa(key, cfg, a, dtype)


# ---------------------------------------------------------------------------
# masking
# ---------------------------------------------------------------------------

def _is_static(window) -> bool:
    return isinstance(window, int)


def causal_window_mask(q_pos, k_pos, window):
    """(S_q, S_k) boolean mask. window<=0 => plain causal.

    ``window`` may be a python int (static) or a traced scalar (per-layer,
    used by hybrid archs inside layer scans)."""
    keep = k_pos[None, :] <= q_pos[:, None]
    dist = q_pos[:, None] - k_pos[None, :]
    if _is_static(window):
        if window > 0:
            keep &= dist < window
    else:
        keep &= (window <= 0) | (dist < window)
    return keep


def decode_keep(k_pos, pos, window):
    """(S_k,) mask for a single query at position ``pos``."""
    keep = k_pos <= pos
    dist = pos - k_pos
    if _is_static(window):
        if window > 0:
            keep &= dist < window
    else:
        keep &= (window <= 0) | (dist < window)
    return keep


def _decode_pos(pos, batch: int):
    """Normalize a decode position argument to ((B,1) rope positions,
    per-example (B,) cache indices or None-if-scalar).

    A scalar ``pos`` is the classic whole-batch decode step; a (B,) vector
    is the serving engine's per-slot position (each sequence in the batch
    is at its own depth)."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        return jnp.full((batch, 1), pos, jnp.int32), None
    return pos[:, None], pos


def decode_keep_batched(k_pos, pos_vec, window):
    """(B, S_k) mask for one query per batch row at position ``pos_vec[b]``."""
    keep = k_pos[None, :] <= pos_vec[:, None]
    dist = pos_vec[:, None] - k_pos[None, :]
    if _is_static(window):
        if window > 0:
            keep &= dist < window
    else:
        keep &= (window <= 0) | (dist < window)
    return keep


def _update_cache_rows(buf, new, pos, pos_vec):
    """Write the (B,1,...) ``new`` rows into ``buf`` (B,S,...) at the cache
    index — a shared scalar ``pos`` or per-example ``pos_vec``."""
    new = new.astype(buf.dtype)
    if pos_vec is None:
        return jax.lax.dynamic_update_slice_in_dim(buf, new, pos, axis=1)
    return jax.vmap(
        lambda b, u, i: jax.lax.dynamic_update_slice_in_dim(b, u, i, axis=0)
    )(buf, new, pos_vec)


def _page_coords(pos, page_size: int, num_logical: int):
    """(logical page, in-page row) for absolute positions. Pages clamp
    into the table so pad positions past the last logical page scatter
    into it (or the null page) where masking hides them."""
    return jnp.clip(pos // page_size, 0, num_logical - 1), pos % page_size


def _scatter_page_rows(buf, new, tables, pos_vec, page_size: int,
                       head_major: bool = False):
    """Write one (B, 1, ...) row per batch element into the paged buffer
    through the block table (B, NP). Idle slots map to the null page;
    their duplicate writes land there harmlessly. ``head_major`` pages are
    (P, KV, page_size, D) (GQA), otherwise (P, page_size, ...) (MLA)."""
    B = new.shape[0]
    pj, pr = _page_coords(pos_vec, page_size, tables.shape[1])
    pid = tables[jnp.arange(B), pj]
    rows = new[:, 0].astype(buf.dtype)
    if head_major:
        return buf.at[pid, :, pr].set(rows)
    return buf.at[pid, pr].set(rows)


def _scatter_chunk_rows(buf, new, tables, positions, page_size: int,
                        head_major: bool = False):
    """Scatter a (B, C, ...) prefill chunk into the paged buffer through
    each row's block table. ``positions`` (B, C) absolute — any alignment
    (prefix-cache resume starts mid-stream); rows whose page the table
    maps to 0 write the null page (pad tails), exactly the garbage-row
    contract the contiguous path has beyond ``valid``."""
    B, C = new.shape[:2]
    pj, pr = _page_coords(positions, page_size, tables.shape[1])
    pid = jnp.take_along_axis(tables, pj, axis=1).reshape(-1)  # (B*C,)
    flat = new.reshape((B * C,) + new.shape[2:]).astype(buf.dtype)
    if head_major:
        return buf.at[pid, :, pr.reshape(-1)].set(flat)
    return buf.at[pid, pr.reshape(-1)].set(flat)


def _gather_lane(buf, tables, head_major: bool = False):
    """(B, NP*page_size, ...) virtual contiguous lanes gathered from the
    paged buffer — the ref-impl read path (bit-identical rows to a
    contiguous pool lane wherever the lane was actually written)."""
    pages = buf[tables]                           # (B, NP, [KV,] ps, ...)
    if head_major:
        pages = pages.swapaxes(2, 3)              # (B, NP, ps, KV, D)
    return pages.reshape((tables.shape[0], -1) + pages.shape[3:])


def _masked_softmax(scores, keep):
    """Masked softmax that never materializes an fp32 copy of the score
    tensor: max-subtract and exp run in the score dtype and only the
    row-sum accumulates in fp32 (XLA fuses the upcast into the
    reduction), so the dense path's peak memory is the score tensor
    itself rather than 3x it. Weights return in the score dtype; pinned
    by the peak-memory regression in tests/test_flash_attention.py."""
    scores = jnp.where(keep, scores, NEG_INF)
    m = jax.lax.stop_gradient(jnp.max(scores, axis=-1, keepdims=True))
    e = jnp.exp(scores - m)
    l = jnp.sum(e, axis=-1, keepdims=True, dtype=jnp.float32)
    return e / l.astype(e.dtype)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def _project_qkv(p, x, a: AttentionConfig):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if a.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    return q, k, v


def gqa_attend(q, k, v, keep, a: AttentionConfig):
    """q:(B,Sq,H,hd) k,v:(B,Sk,KV,hd), keep:(Sq,Sk) or (B,Sq,Sk)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    q = q.reshape(B, Sq, KV, G, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", q, k) / jnp.sqrt(hd).astype(q.dtype)
    if keep.ndim == 2:
        keep_b = keep[None, None, None]
    else:
        keep_b = keep[:, None, None]
    w = _masked_softmax(scores, keep_b).astype(v.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", w, v)
    return out.reshape(B, Sq, H, hd)


def gqa_attend_blockwise(q, k, v, q_pos, k_pos, window, a: AttentionConfig,
                         block: int = 1024, scale=None):
    """Flash-style attention: lax.scan over KV blocks with an online
    softmax, so the (Sq, Sk) score matrix is never materialized in HBM —
    the per-step working set is (Sq, block). Beyond-paper optimization for
    the memory-bound prefill/train shapes.

    ``v`` may have a different trailing dim than q/k (the MLA absorbed
    layout: q/k in the latent+rope space, v = the latent); ``scale``
    overrides the default 1/sqrt(head_dim) score scale.
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    Sk = k.shape[1]
    hv = v.shape[-1]
    pad = (-Sk) % block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=10 ** 9)
    nb = (Sk + pad) // block
    qg = q.reshape(B, Sq, KV, G, hd)
    if scale is None:
        scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)

    kb = k.reshape(B, nb, block, KV, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nb, block, KV, hv).transpose(1, 0, 2, 3, 4)
    pb = k_pos.reshape(nb, block)

    def step(carry, inp):
        m, l, acc = carry                          # (B,KV,G,Sq), ., (+hd)
        kblk, vblk, pblk = inp
        s = jnp.einsum("bskgh,btkh->bkgst", qg, kblk).astype(jnp.float32)
        s = s * scale
        keep = pblk[None, :] <= q_pos[:, None]      # (Sq, block)
        dist = q_pos[:, None] - pblk[None, :]
        if _is_static(window):
            if window > 0:
                keep &= dist < window
        else:
            keep &= (window <= 0) | (dist < window)
        s = jnp.where(keep[None, None, None], s, NEG_INF)
        m_blk = jnp.max(s, axis=-1)                 # (B,KV,G,Sq)
        m_new = jnp.maximum(m, m_blk)
        p_ = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p_, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgst,btkh->bkgsh", p_.astype(vblk.dtype), vblk
        ).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Sq, hv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kb, vb, pb),
                                  unroll=nb if a.block_unroll else 1)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hv)
    return out.astype(q.dtype)


def gqa_forward(p, x, positions, a: AttentionConfig, window: int,
                impl: str | None = None):
    """Training/prefill full self-attention. x:(B,S,d)."""
    impl = impl or resolve_attn_impl(a)
    q, k, v = _project_qkv(p, x, a)
    if a.qk_norm:
        q, k = head_rms_norm(q), head_rms_norm(k)
    q = apply_rope(q, positions, a.rope_theta)
    k = apply_rope(k, positions, a.rope_theta)
    B, S = x.shape[:2]
    if impl == "flash":
        # q and k are rows of the same sequence, so the kernel's row-index
        # masking (q_off=0) is exact for any *common-offset* positions:
        # causality and window distance only depend on q_pos - k_pos.
        # Packed/non-monotonic position vectors need the ref path, whose
        # mask compares the actual position values.
        out = flash_attention(q, k, v, window=window)
    elif impl == "blockwise" or (a.block_kv and S > a.block_kv):
        out = gqa_attend_blockwise(q, k, v, positions[0], positions[0],
                                   window, a, block=a.block_kv or 1024)
    else:
        keep = causal_window_mask(positions[0], positions[0], window)
        out = gqa_attend(q, k, v, keep, a)
    return jnp.einsum("bsf,fd->bsd", out.reshape(B, S, -1), p["wo"])


def gqa_init_cache(batch: int, max_len: int, a: AttentionConfig, dtype):
    return {
        "k": jnp.zeros((batch, max_len, a.num_kv_heads, a.head_dim), dtype),
        "v": jnp.zeros((batch, max_len, a.num_kv_heads, a.head_dim), dtype),
    }


def gqa_init_pages(num_pages: int, page_size: int, a: AttentionConfig,
                   dtype):
    """Physical KV pages, head-major (P, KV, page_size, hd): one page of
    one kv head is a (page_size, hd) tile, the block ``flash_decode_paged``
    fetches."""
    shape = (num_pages, a.num_kv_heads, page_size, a.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def gqa_decode(p, cache, x, pos, a: AttentionConfig, window: int,
               impl: str | None = None, tables=None, page_size: int = 0):
    """One-token decode. x:(B,1,d); pos: scalar int (current index) or a
    (B,) vector of per-sequence indices (serving engine slots).

    ``tables`` (B, NP) int32 switches the cache to the paged layout
    (cache leaves are (P, KV, page_size, hd) physical pages): the new row
    scatters through the table, flash reads fetch pages tile-wise inside
    ``flash_decode_paged``, and the ref path gathers the virtual lane —
    identical math to the contiguous layout on the gathered rows.

    Returns (out, new_cache)."""
    impl = impl or resolve_attn_impl(a)
    q, k, v = _project_qkv(p, x, a)
    if a.qk_norm:
        q, k = head_rms_norm(q), head_rms_norm(k)
    posv, pos_vec = _decode_pos(pos, x.shape[0])
    q = apply_rope(q, posv, a.rope_theta)
    k = apply_rope(k, posv, a.rope_theta)
    B = x.shape[0]
    if tables is not None:
        pv = posv[:, 0]
        ck = _scatter_page_rows(cache["k"], k, tables, pv, page_size, True)
        cv = _scatter_page_rows(cache["v"], v, tables, pv, page_size, True)
        if impl == "flash":
            out = flash_decode_paged(q, ck, cv, tables, pv,
                                     page_size=page_size, window=window)
        else:
            lk = _gather_lane(ck, tables, True)
            lv = _gather_lane(cv, tables, True)
            keep = decode_keep_batched(jnp.arange(lk.shape[1]), pv,
                                       window)[:, None, :]
            out = gqa_attend(q, lk, lv, keep, a)
        y = jnp.einsum("bsf,fd->bsd", out.reshape(B, 1, -1), p["wo"])
        return y, {"k": ck, "v": cv}
    ck = _update_cache_rows(cache["k"], k, pos, pos_vec)
    cv = _update_cache_rows(cache["v"], v, pos, pos_vec)
    S = ck.shape[1]
    if impl == "flash":
        out = flash_decode(q, ck, cv,
                           pos_vec if pos_vec is not None else pos,
                           window=window)
    else:
        if pos_vec is None:
            keep = decode_keep(jnp.arange(S), pos, window)[None, :]  # (1,S)
        else:
            keep = decode_keep_batched(jnp.arange(S), pos_vec,
                                       window)[:, None, :]
        out = gqa_attend(q, ck, cv, keep, a)
    y = jnp.einsum("bsf,fd->bsd", out.reshape(B, 1, -1), p["wo"])
    return y, {"k": ck, "v": cv}


def gqa_prefill(p, cache, x, positions, pos0, a: AttentionConfig,
                window: int, impl: str | None = None, tables=None,
                page_size: int = 0):
    """Chunked prompt prefill: attend a whole (B,C,d) chunk against the
    cache and write its K/V rows at [pos0, pos0+C) in one pass.

    ``positions`` (B,C) are absolute positions (pos0 + arange(C)); rows
    beyond the valid prompt length write pad garbage that is masked out of
    every later read (causality) and overwritten by the decode steps.

    ``tables`` (B, NP) switches to the paged cache layout: chunk rows
    scatter through the block table (any pos0 alignment — prefix-cache
    resume and the 1-token full-hit re-prefill both land mid-page) and
    the chunk attends the gathered virtual lane."""
    impl = impl or resolve_attn_impl(a)
    q, k, v = _project_qkv(p, x, a)
    if a.qk_norm:
        q, k = head_rms_norm(q), head_rms_norm(k)
    q = apply_rope(q, positions, a.rope_theta)
    k = apply_rope(k, positions, a.rope_theta)
    B, C = x.shape[:2]
    if tables is not None:
        ck = _scatter_chunk_rows(cache["k"], k, tables, positions,
                                 page_size, True)
        cv = _scatter_chunk_rows(cache["v"], v, tables, positions,
                                 page_size, True)
        lane_k = _gather_lane(ck, tables, True)
        lane_v = _gather_lane(cv, tables, True)
    else:
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), pos0, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), pos0, axis=1)
        lane_k, lane_v = ck, cv
    S = lane_k.shape[1]
    if impl == "flash":
        # q-chunk x full-cache tiles; rows start at the chunk origin
        out = flash_attention(q, lane_k, lane_v, q_off=positions[:, 0],
                              window=window)
    else:
        keep = causal_window_mask(positions[0], jnp.arange(S), window)
        out = gqa_attend(q, lane_k, lane_v, keep, a)
    y = jnp.einsum("bsf,fd->bsd", out.reshape(B, C, -1), p["wo"])
    return y, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------

def mla_forward(p, x, positions, a: AttentionConfig, window: int,
                impl: str | None = None):
    """Training/prefill MLA.

    ``flash``/``blockwise`` attend in the absorbed-matmul layout — W_uk is
    folded into the query so keys are the cached (latent ‖ rope-key)
    vectors and values are the latent itself (the same math the decode
    path uses), which keeps attention a single KV-head problem and never
    expands per-head k_nope/v to HBM. The ``ref`` dense path keeps the
    naive per-head expansion as the oracle, but long sequences route
    through the shared blockwise scan when ``block_kv`` is set (so
    long-seq MLA never builds the (B,H,S,S) score matrix either)."""
    impl = impl or resolve_attn_impl(a)
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = jnp.split(q, [a.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, a.rope_theta)

    c_kv = jnp.einsum("bsd,dr->bsr", x, p["wdkv"])          # (B,S,R)
    k_rope = jnp.einsum("bsd,dr->bsr", x, p["wkr"])          # (B,S,rope)
    k_rope = apply_rope(k_rope[:, :, None, :], positions,
                        a.rope_theta)[:, :, 0, :]

    if impl == "flash" or impl == "blockwise" or (
            a.block_kv and S > a.block_kv):
        lat_scale = 1.0 / math.sqrt(a.qk_nope_dim + a.qk_rope_dim)
        q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["wuk"])
        q_cat = jnp.concatenate([q_lat, q_rope], axis=-1)    # (B,S,H,R+rope)
        k_cat = jnp.concatenate([c_kv, k_rope], axis=-1)[:, :, None]
        v_lat = c_kv[:, :, None]                             # (B,S,1,R)
        if impl == "flash":
            o_lat = flash_attention(q_cat, k_cat, v_lat, window=window,
                                    sm_scale=lat_scale)
        else:
            o_lat = gqa_attend_blockwise(
                q_cat, k_cat, v_lat, positions[0], positions[0], window,
                a, block=a.block_kv or 1024,
                scale=jnp.float32(lat_scale))
        out = jnp.einsum("bshr,rhk->bshk", o_lat.astype(x.dtype),
                         p["wuv"]).reshape(B, S, -1)
        return jnp.einsum("bsf,fd->bsd", out, p["wo"])

    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["wuk"])     # (B,S,H,nope)
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["wuv"])          # (B,S,H,vd)
    scale = 1.0 / jnp.sqrt(a.qk_nope_dim + a.qk_rope_dim).astype(x.dtype)
    s_nope = jnp.einsum("bshk,bthk->bhst", q_nope, k_nope)
    s_rope = jnp.einsum("bshk,btk->bhst", q_rope, k_rope)
    keep = causal_window_mask(positions[0], positions[0], window)
    w = _masked_softmax((s_nope + s_rope) * scale,
                        keep[None, None]).astype(x.dtype)
    out = jnp.einsum("bhst,bthk->bshk", w, v).reshape(B, S, -1)
    return jnp.einsum("bsf,fd->bsd", out, p["wo"])


def mla_init_cache(batch: int, max_len: int, a: AttentionConfig, dtype):
    return {
        "ckv": jnp.zeros((batch, max_len, a.kv_lora_rank), dtype),
        "kr": jnp.zeros((batch, max_len, a.qk_rope_dim), dtype),
    }


def mla_decode(p, cache, x, pos, a: AttentionConfig, window: int,
               tables=None, page_size: int = 0):
    """Absorbed-matmul MLA decode: attends in the 512-d latent space.
    ``pos`` may be a scalar or a (B,) per-sequence vector. ``tables``
    switches to paged latent/rope-key caches ((P, page_size, R/rope)):
    row writes scatter through the block table and attention runs on the
    gathered virtual lanes — the absorbed einsum path is already the
    memory-lean kernel here, so there is no separate flash variant."""
    B = x.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = jnp.split(q, [a.qk_nope_dim], axis=-1)
    posv, pos_vec = _decode_pos(pos, B)
    q_rope = apply_rope(q_rope, posv, a.rope_theta)
    # absorb W_uk into the query: (B,1,H,nope) x (R,H,nope) -> (B,1,H,R)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["wuk"])

    c_new = jnp.einsum("bsd,dr->bsr", x, p["wdkv"])
    kr_new = jnp.einsum("bsd,dr->bsr", x, p["wkr"])
    kr_new = apply_rope(kr_new[:, :, None, :], posv, a.rope_theta)[:, :, 0, :]
    if tables is not None:
        pv = posv[:, 0]
        ckv = _scatter_page_rows(cache["ckv"], c_new, tables, pv, page_size)
        kr = _scatter_page_rows(cache["kr"], kr_new, tables, pv, page_size)
        lat, ropek = _gather_lane(ckv, tables), _gather_lane(kr, tables)
        keep = decode_keep_batched(jnp.arange(lat.shape[1]), pv,
                                   window)[:, None, None, :]
    else:
        ckv = _update_cache_rows(cache["ckv"], c_new, pos, pos_vec)
        kr = _update_cache_rows(cache["kr"], kr_new, pos, pos_vec)
        lat, ropek = ckv, kr
        S = lat.shape[1]
        if pos_vec is None:
            keep = decode_keep(jnp.arange(S), pos,
                               window)[None, None, None, :]
        else:
            keep = decode_keep_batched(jnp.arange(S), pos_vec,
                                       window)[:, None, None, :]
    scale = 1.0 / jnp.sqrt(a.qk_nope_dim + a.qk_rope_dim).astype(x.dtype)
    s_lat = jnp.einsum("bshr,btr->bhst", q_lat, lat)
    s_rope = jnp.einsum("bshk,btk->bhst", q_rope, ropek)
    w = _masked_softmax((s_lat + s_rope) * scale, keep).astype(x.dtype)
    o_lat = jnp.einsum("bhst,btr->bshr", w, lat)             # (B,1,H,R)
    out = jnp.einsum("bshr,rhk->bshk", o_lat, p["wuv"]).reshape(B, 1, -1)
    y = jnp.einsum("bsf,fd->bsd", out, p["wo"])
    return y, {"ckv": ckv, "kr": kr}


def mla_prefill(p, cache, x, positions, pos0, a: AttentionConfig,
                window: int, tables=None, page_size: int = 0):
    """Chunked MLA prefill: absorbed-matmul attention (same math as
    ``mla_decode``, C query rows instead of 1) that writes the latent +
    rope-key cache rows at [pos0, pos0+C) — through the block table when
    ``tables`` is given (paged layout, any alignment)."""
    B, C, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = jnp.split(q, [a.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, a.rope_theta)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["wuk"])

    c_new = jnp.einsum("bsd,dr->bsr", x, p["wdkv"])
    kr_new = jnp.einsum("bsd,dr->bsr", x, p["wkr"])
    kr_new = apply_rope(kr_new[:, :, None, :], positions,
                        a.rope_theta)[:, :, 0, :]
    if tables is not None:
        ckv = _scatter_chunk_rows(cache["ckv"], c_new, tables, positions,
                                  page_size)
        kr = _scatter_chunk_rows(cache["kr"], kr_new, tables, positions,
                                 page_size)
        lat, ropek = _gather_lane(ckv, tables), _gather_lane(kr, tables)
    else:
        ckv = jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], c_new.astype(cache["ckv"].dtype), pos0, axis=1)
        kr = jax.lax.dynamic_update_slice_in_dim(
            cache["kr"], kr_new.astype(cache["kr"].dtype), pos0, axis=1)
        lat, ropek = ckv, kr

    S = lat.shape[1]
    keep = causal_window_mask(positions[0], jnp.arange(S), window)  # (C,S)
    scale = 1.0 / jnp.sqrt(a.qk_nope_dim + a.qk_rope_dim).astype(x.dtype)
    s_lat = jnp.einsum("bshr,btr->bhst", q_lat, lat)
    s_rope = jnp.einsum("bshk,btk->bhst", q_rope, ropek)
    w = _masked_softmax((s_lat + s_rope) * scale,
                        keep[None, None]).astype(x.dtype)
    o_lat = jnp.einsum("bhst,btr->bshr", w, lat)             # (B,C,H,R)
    out = jnp.einsum("bshr,rhk->bshk", o_lat, p["wuv"]).reshape(B, C, -1)
    y = jnp.einsum("bsf,fd->bsd", out, p["wo"])
    return y, {"ckv": ckv, "kr": kr}


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def attn_forward(p, x, positions, cfg: ArchConfig, window: int,
                 impl: str | None = None):
    a = cfg.attention
    if a.kv_lora_rank:
        return mla_forward(p, x, positions, a, window, impl=impl)
    return gqa_forward(p, x, positions, a, window, impl=impl)


def attn_init_cache(batch: int, max_len: int, cfg: ArchConfig, dtype):
    a = cfg.attention
    if a.kv_lora_rank:
        return mla_init_cache(batch, max_len, a, dtype)
    return gqa_init_cache(batch, max_len, a, dtype)


def attn_init_pages(num_pages: int, page_size: int, cfg: ArchConfig,
                    dtype):
    """Paged pool leaves: GQA pages head-major, MLA latent/rope-key pages
    (P, page_size, R/rope) — already (rows, dim) tiles."""
    a = cfg.attention
    if a.kv_lora_rank:
        return mla_init_cache(num_pages, page_size, a, dtype)
    return gqa_init_pages(num_pages, page_size, a, dtype)


def attn_decode(p, cache, x, pos, cfg: ArchConfig, window: int,
                impl: str | None = None, tables=None, page_size: int = 0):
    a = cfg.attention
    if a.kv_lora_rank:
        # MLA decode attends in the latent space already ((B,H,1,S) scores
        # against the 576-float cache rows) — the absorbed ref path *is*
        # the memory-lean kernel here
        return mla_decode(p, cache, x, pos, a, window, tables=tables,
                          page_size=page_size)
    return gqa_decode(p, cache, x, pos, a, window, impl=impl,
                      tables=tables, page_size=page_size)


def attn_prefill(p, cache, x, positions, pos0, cfg: ArchConfig, window: int,
                 impl: str | None = None, tables=None, page_size: int = 0):
    a = cfg.attention
    if a.kv_lora_rank:
        return mla_prefill(p, cache, x, positions, pos0, a, window,
                           tables=tables, page_size=page_size)
    return gqa_prefill(p, cache, x, positions, pos0, a, window, impl=impl,
                       tables=tables, page_size=page_size)
