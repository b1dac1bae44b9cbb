"""Decoder-only LM executor: dense / MoE / SSM / hybrid blocks.

Layers with identical parameter structure are stacked and scanned
(``lax.scan`` over the leading layer axis, rematerialized); heterogeneous
layer kinds (e.g. DeepSeek's first dense layer + MoE rest) are grouped into
consecutive homogeneous *segments*, each with its own stack.

Supports:
- train/prefill forward (full sequence) -> logits (+ MoE aux loss)
- one-token decode against a KV/SSM cache (``init_cache`` / ``decode_step``)
- early-fusion VLM inputs (precomputed image-patch embeddings, stub frontend)
- Hymba meta tokens (learnable prefix) and per-layer global/sliding windows
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist import act
from repro.models import attention as attn_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (dense_init, dtype_of, embed_init, rms_norm,
                                 softmax_xent)
from repro.models.mlp import init_mlp, mlp_forward
from repro.models.moe import init_moe, moe_forward


def _unroll_of(unroll, count: int) -> int:
    """unroll: False/0->1 (scan), True->full, int n->min(n, count).

    The dry-run compiles with unroll=1 and unroll=2 and extrapolates
    per-layer costs (scan bodies are costed once by XLA)."""
    if unroll is True:
        return count
    u = int(unroll)
    if count % max(u, 1):
        # keep trip count integral: fall back to 1
        return count if u >= count else 1 if u <= 1 else (u if count % u == 0 else 1)
    return max(1, min(u, count))


# ---------------------------------------------------------------------------
# layer layout
# ---------------------------------------------------------------------------

def layer_kinds(cfg: ArchConfig) -> list[str]:
    """Per-layer kind: 'dense' | 'moe' | 'ssm' | 'hybrid'."""
    kinds = []
    for i in range(cfg.num_layers):
        if cfg.block == "ssm":
            kinds.append("ssm")
        elif cfg.block == "hybrid":
            kinds.append("hybrid")
        elif cfg.moe is not None:
            m = cfg.moe
            if i < m.first_k_dense or ((i - m.first_k_dense) % m.moe_every) != 0:
                kinds.append("dense")
            else:
                kinds.append("moe")
        else:
            kinds.append("dense")
    return kinds


def layer_windows(cfg: ArchConfig, shape_kind: str, seq_len: int) -> list[int]:
    """Static per-layer attention window (0 = full causal)."""
    a = cfg.attention
    wins = []
    for i in range(cfg.num_layers):
        w = a.sliding_window if a else 0
        if cfg.global_attn_every:
            is_global = (i % cfg.global_attn_every == 0) or i == cfg.num_layers - 1
            w = 0 if is_global else (a.sliding_window or 1024)
        # long-context shapes force a window on full-attention layers
        if seq_len > 100_000 and cfg.long_context_window and w == 0:
            w = cfg.long_context_window
        wins.append(w)
    return wins


def segments(cfg: ArchConfig) -> list[tuple[str, int]]:
    """Group consecutive identical kinds -> [(kind, count), ...]."""
    segs: list[tuple[str, int]] = []
    for k in layer_kinds(cfg):
        if segs and segs[-1][0] == k:
            segs[-1] = (k, segs[-1][1] + 1)
        else:
            segs.append((k, 1))
    return segs


# ---------------------------------------------------------------------------
# single-layer init/apply
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ArchConfig, kind: str, dtype):
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p: dict = {"ln1": jnp.zeros((d,), jnp.float32)}
    if kind in ("dense", "moe", "hybrid"):
        p["attn"] = attn_mod.init_attention(ks[0], cfg, dtype)
    if kind in ("ssm", "hybrid"):
        p["ssm"] = ssm_mod.init_ssm(ks[1], d, cfg.ssm, dtype)
    if kind == "hybrid":
        p["fuse_na"] = jnp.zeros((d,), jnp.float32)
        p["fuse_ns"] = jnp.zeros((d,), jnp.float32)
    if kind == "dense":
        p["ln2"] = jnp.zeros((d,), jnp.float32)
        p["mlp"] = init_mlp(ks[2], d, cfg.d_ff, dtype)
    elif kind == "moe":
        p["ln2"] = jnp.zeros((d,), jnp.float32)
        p["moe"] = init_moe(ks[2], d, cfg.moe, dtype)
    elif kind == "hybrid" and cfg.d_ff:
        p["ln2"] = jnp.zeros((d,), jnp.float32)
        p["mlp"] = init_mlp(ks[2], d, cfg.d_ff, dtype)
    return p


def _apply_layer(p, x, positions, cfg: ArchConfig, kind: str, window,
                 attn_impl=None):
    """Full-sequence layer application. Returns (x, aux)."""
    eps = cfg.norm_eps
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, p["ln1"], eps)
    if kind == "dense" or kind == "moe":
        x = x + attn_mod.attn_forward(p["attn"], h, positions, cfg, window,
                                      impl=attn_impl)
    elif kind == "ssm":
        x = x + ssm_mod.ssm_forward(p["ssm"], h, cfg.d_model, cfg.ssm, eps)
    elif kind == "hybrid":
        ya = attn_mod.attn_forward(p["attn"], h, positions, cfg, window,
                                   impl=attn_impl)
        ys = ssm_mod.ssm_forward(p["ssm"], h, cfg.d_model, cfg.ssm, eps)
        x = x + 0.5 * (rms_norm(ya, p["fuse_na"], eps)
                       + rms_norm(ys, p["fuse_ns"], eps))
    if "mlp" in p:
        x = x + mlp_forward(p["mlp"], rms_norm(x, p["ln2"], eps))
    elif "moe" in p:
        y, a = moe_forward(p["moe"], rms_norm(x, p["ln2"], eps), cfg.moe)
        x = x + y
        aux = aux + a
    return x, aux


def _decode_layer(p, cache, x, pos, cfg: ArchConfig, kind: str, window,
                  attn_impl=None, tables=None, page_size=0):
    eps = cfg.norm_eps
    h = rms_norm(x, p["ln1"], eps)
    new_cache = {}
    if kind in ("dense", "moe"):
        y, new_cache["attn"] = attn_mod.attn_decode(
            p["attn"], cache["attn"], h, pos, cfg, window, impl=attn_impl,
            tables=tables, page_size=page_size)
        x = x + y
    elif kind == "ssm":
        y, new_cache["ssm"] = ssm_mod.ssm_decode(
            p["ssm"], cache["ssm"], h, cfg.d_model, cfg.ssm, eps)
        x = x + y
    elif kind == "hybrid":
        ya, new_cache["attn"] = attn_mod.attn_decode(
            p["attn"], cache["attn"], h, pos, cfg, window, impl=attn_impl,
            tables=tables, page_size=page_size)
        ys, new_cache["ssm"] = ssm_mod.ssm_decode(
            p["ssm"], cache["ssm"], h, cfg.d_model, cfg.ssm, eps)
        x = x + 0.5 * (rms_norm(ya, p["fuse_na"], eps)
                       + rms_norm(ys, p["fuse_ns"], eps))
    if "mlp" in p:
        x = x + mlp_forward(p["mlp"], rms_norm(x, p["ln2"], eps))
    elif "moe" in p:
        # full capacity: decode routing must be drop-free so each slot's
        # output is independent of what the other slots are decoding (the
        # serving engine's bit-exactness contract under churn)
        y, _ = moe_forward(p["moe"], rms_norm(x, p["ln2"], eps), cfg.moe,
                           full_capacity=True)
        x = x + y
    return x, new_cache


def _prefill_layer(p, cache, x, positions, pos0, valid_count, valid_flat,
                   cfg: ArchConfig, kind: str, window, attn_impl=None,
                   tables=None, page_size=0):
    """Whole-chunk layer application that also writes the layer cache.

    x: (B,C,d); positions (B,C) absolute; pos0 scalar chunk start;
    valid_count scalar <= C (same for every batch row); valid_flat (B*C,)
    bool marks real (non-pad) tokens."""
    eps = cfg.norm_eps
    h = rms_norm(x, p["ln1"], eps)
    new_cache = {}
    if kind in ("dense", "moe"):
        y, new_cache["attn"] = attn_mod.attn_prefill(
            p["attn"], cache["attn"], h, positions, pos0, cfg, window,
            impl=attn_impl, tables=tables, page_size=page_size)
        x = x + y
    elif kind == "ssm":
        y, new_cache["ssm"] = ssm_mod.ssm_prefill(
            p["ssm"], cache["ssm"], h, valid_count, cfg.d_model,
            cfg.ssm, eps)
        x = x + y
    elif kind == "hybrid":
        ya, new_cache["attn"] = attn_mod.attn_prefill(
            p["attn"], cache["attn"], h, positions, pos0, cfg, window,
            impl=attn_impl, tables=tables, page_size=page_size)
        ys, new_cache["ssm"] = ssm_mod.ssm_prefill(
            p["ssm"], cache["ssm"], h, valid_count, cfg.d_model,
            cfg.ssm, eps)
        x = x + 0.5 * (rms_norm(ya, p["fuse_na"], eps)
                       + rms_norm(ys, p["fuse_ns"], eps))
    if "mlp" in p:
        x = x + mlp_forward(p["mlp"], rms_norm(x, p["ln2"], eps))
    elif "moe" in p:
        y, _ = moe_forward(p["moe"], rms_norm(x, p["ln2"], eps), cfg.moe,
                           full_capacity=True, valid=valid_flat)
        x = x + y
    return x, new_cache


def _init_layer_cache(batch: int, max_len: int, cfg: ArchConfig, kind: str,
                      dtype):
    c = {}
    if kind in ("dense", "moe", "hybrid"):
        c["attn"] = attn_mod.attn_init_cache(batch, max_len, cfg, dtype)
    if kind in ("ssm", "hybrid"):
        c["ssm"] = ssm_mod.ssm_init_cache(batch, cfg.d_model, cfg.ssm, dtype)
    return c


# ---------------------------------------------------------------------------
# model init
# ---------------------------------------------------------------------------

def init_decoder(key, cfg: ArchConfig):
    dtype = dtype_of(cfg.param_dtype)
    kemb, khead, kblocks, kmeta = jax.random.split(key, 4)
    params: dict = {
        "embed": embed_init(kemb, cfg.vocab_size, cfg.d_model, dtype),
        "ln_f": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["head"] = dense_init(khead, cfg.d_model, (cfg.vocab_size,),
                                    dtype)
    if cfg.num_meta_tokens:
        params["meta"] = (jax.random.normal(
            kmeta, (cfg.num_meta_tokens, cfg.d_model), jnp.float32)
            * 0.02).astype(dtype)

    segs = segments(cfg)
    blocks = []
    lkeys = jax.random.split(kblocks, cfg.num_layers)
    li = 0
    for kind, count in segs:
        seg_keys = jnp.stack(lkeys[li:li + count])
        li += count
        stacked = jax.vmap(
            lambda k: _init_layer(k, cfg, kind, dtype))(seg_keys)
        blocks.append(stacked)
    params["blocks"] = blocks
    return params


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _embed_inputs(params, batch, cfg: ArchConfig, dtype):
    """Token embedding + early fusion + meta tokens. Returns (h, positions)."""
    tokens = batch["tokens"]
    h = params["embed"][tokens].astype(dtype)
    if cfg.modality == "vlm" and "image_embeds" in batch:
        img = batch["image_embeds"].astype(dtype)       # (B, n_img, d)
        h = jnp.concatenate([img, h], axis=1)
    if cfg.num_meta_tokens:
        meta = jnp.broadcast_to(
            params["meta"].astype(dtype)[None],
            (h.shape[0], cfg.num_meta_tokens, cfg.d_model))
        h = jnp.concatenate([meta, h], axis=1)
    B, S = h.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    return h, positions


def decoder_forward(params, batch, cfg: ArchConfig, *, unroll: bool = False):
    """batch: {tokens:(B,St) [, image_embeds:(B,Ni,d)]}. Returns (logits, aux).

    logits cover only the token positions (meta/image prefixes stripped)."""
    dtype = dtype_of(cfg.dtype)
    h, positions = _embed_inputs(params, batch, cfg, dtype)
    wins = layer_windows(cfg, "train", h.shape[1])
    kinds = layer_kinds(cfg)
    segs = segments(cfg)
    # resolve the attention implementation once per forward (env/config/
    # backend dispatch happens here, not per layer inside the scan body)
    attn_impl = (attn_mod.resolve_attn_impl(cfg.attention)
                 if cfg.attention is not None else None)

    aux_total = jnp.zeros((), jnp.float32)
    li = 0
    for seg_idx, (kind, count) in enumerate(segs):
        stacked = params["blocks"][seg_idx]
        seg_wins = jnp.asarray(wins[li:li + count], jnp.int32)
        uniform = len(set(wins[li:li + count])) == 1
        static_win = wins[li] if uniform else None
        li += count

        def body(carry, xs, _kind=kind, _static=static_win):
            x, aux = carry
            lp, w = xs
            win = _static if _static is not None else w
            x, a = _apply_layer(lp, x, positions, cfg, _kind, win,
                                attn_impl=attn_impl)
            x = act.constrain(x)
            return (x, aux + a), None

        body_fn = jax.checkpoint(body) if cfg.remat else body
        if cfg.scan_layers and count > 1:
            (h, aux_total), _ = jax.lax.scan(
                body_fn, (h, aux_total), (stacked, seg_wins),
                unroll=_unroll_of(unroll, count))
        else:
            for j in range(count):
                lp = jax.tree.map(lambda v: v[j], stacked)
                (h, aux_total), _ = body_fn((h, aux_total),
                                            (lp, seg_wins[j]))

    h = rms_norm(h, params["ln_f"], cfg.norm_eps)
    n_prefix = cfg.num_meta_tokens + (
        batch["image_embeds"].shape[1]
        if (cfg.modality == "vlm" and "image_embeds" in batch) else 0)
    if n_prefix:
        h = h[:, n_prefix:]
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = jnp.einsum("bsd,dv->bsv", h, head.astype(dtype))
    return logits, aux_total


def decoder_loss(params, batch, cfg: ArchConfig, *, unroll: bool = False):
    logits, aux = decoder_forward(params, batch, cfg, unroll=unroll)
    labels = batch["labels"]
    mask = (labels >= 0)
    loss = softmax_xent(logits, jnp.maximum(labels, 0), mask)
    return loss + aux, {"loss": loss, "aux": aux}


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_decoder_cache(cfg: ArchConfig, batch: int, max_len: int):
    """Cache pytree mirroring the segment structure."""
    dtype = dtype_of(cfg.dtype)
    total_len = max_len + cfg.num_meta_tokens + (
        cfg.num_image_tokens if cfg.modality == "vlm" else 0)
    caches = []
    for kind, count in segments(cfg):
        one = _init_layer_cache(batch, total_len, cfg, kind, dtype)
        stacked = jax.tree.map(
            lambda v: jnp.broadcast_to(v[None], (count, *v.shape)), one)
        caches.append(stacked)
    return caches


def init_paged_decoder_cache(cfg: ArchConfig, max_slots: int,
                             page_size: int, num_pages: int):
    """Paged cache pool mirroring the segment structure: attention leaves
    hold (count, num_pages, ...) physical pages (``attn_init_pages``)
    shared by every slot through a block table, SSM conv/state leaves
    keep one lane per slot (count, max_slots, ...) — they have no
    sequence dim to page. The meta/VLM row padding of
    ``init_decoder_cache`` does not apply: the serve paths never write
    meta prefixes, and pages are allocated by demand, not worst case."""
    dtype = dtype_of(cfg.dtype)
    caches = []
    for kind, count in segments(cfg):
        one = {}
        if kind in ("dense", "moe", "hybrid"):
            one["attn"] = attn_mod.attn_init_pages(num_pages, page_size,
                                                   cfg, dtype)
        if kind in ("ssm", "hybrid"):
            one["ssm"] = ssm_mod.ssm_init_cache(max_slots, cfg.d_model,
                                                cfg.ssm, dtype)
        stacked = jax.tree.map(
            lambda v: jnp.broadcast_to(v[None], (count, *v.shape)), one)
        caches.append(stacked)
    return caches


def decoder_decode_step(params, caches, tokens, pos, cfg: ArchConfig,
                        *, seq_len: int, unroll: bool = False,
                        block_tables=None, page_size: int = 0):
    """One decode step. tokens:(B,1) int32; pos: scalar int32 (cache index
    shared by the whole batch) or (B,) int32 per-sequence indices (the
    serving engine's slot pool, where every sequence is at its own depth).

    ``block_tables`` (B, NP) int32 routes attention caches through the
    paged layout (``init_paged_decoder_cache``); the tables are a scan
    constant — same physical pages for every layer of a slot's lane.

    Returns (logits (B,1,V), new_caches)."""
    dtype = dtype_of(cfg.dtype)
    h = params["embed"][tokens].astype(dtype)
    wins = layer_windows(cfg, "decode", seq_len)
    segs = segments(cfg)
    attn_impl = (attn_mod.resolve_attn_impl(cfg.attention)
                 if cfg.attention is not None else None)

    li = 0
    new_caches = []
    for seg_idx, (kind, count) in enumerate(segs):
        stacked = params["blocks"][seg_idx]
        cache = caches[seg_idx]
        seg_wins = jnp.asarray(wins[li:li + count], jnp.int32)
        uniform = len(set(wins[li:li + count])) == 1
        static_win = wins[li] if uniform else None
        li += count

        def body(x, xs, _kind=kind, _static=static_win):
            lp, lc, w = xs
            win = _static if _static is not None else w
            x, nc = _decode_layer(lp, lc, x, pos, cfg, _kind, win,
                                  attn_impl=attn_impl, tables=block_tables,
                                  page_size=page_size)
            return x, nc

        if cfg.scan_layers and count > 1:
            h, nc = jax.lax.scan(body, h, (stacked, cache, seg_wins),
                                 unroll=_unroll_of(unroll, count))
        else:
            ncs = []
            for j in range(count):
                lp = jax.tree.map(lambda v: v[j], stacked)
                lc = jax.tree.map(lambda v: v[j], cache)
                h, nc1 = body(h, (lp, lc, seg_wins[j]))
                ncs.append(nc1)
            nc = jax.tree.map(lambda *vs: jnp.stack(vs), *ncs)
        new_caches.append(nc)

    h = rms_norm(h, params["ln_f"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = jnp.einsum("bsd,dv->bsv", h, head.astype(dtype))
    return logits, new_caches


def decoder_prefill(params, caches, tokens, pos0, valid, cfg: ArchConfig,
                    *, seq_len: int, unroll: bool = False,
                    block_tables=None, page_size: int = 0):
    """Chunked whole-prompt prefill: one full-sequence pass over a (B,C)
    token chunk starting at cache position ``pos0`` that computes logits
    for every chunk position AND writes all layer caches — replacing the
    token-by-token forced-decode loop (C model calls, C wasted LM-head
    projections) with a single call.

    ``valid`` (scalar int32 <= C, shared by the batch) marks how many
    leading chunk positions are real tokens; trailing pad positions are
    excluded from SSM state updates and MoE routing, and their (garbage)
    cache rows sit beyond the live sequence where causal masking hides
    them until the decode steps overwrite them in order.

    Long prompts run as consecutive calls with pos0 = 0, C, 2C, ...; the
    attention chunk attends the whole cache written so far, and SSM state
    carries through the cache. Meta-token/VLM prefixes are not applied
    (consistent with ``decoder_decode_step``).

    Returns (logits (B,C,V), new_caches)."""
    dtype = dtype_of(cfg.dtype)
    B, C = tokens.shape
    h = params["embed"][tokens].astype(dtype)
    pos0 = jnp.asarray(pos0, jnp.int32)
    valid = jnp.asarray(valid, jnp.int32)
    positions = jnp.broadcast_to(
        pos0 + jnp.arange(C, dtype=jnp.int32)[None], (B, C))
    valid_flat = jnp.broadcast_to((jnp.arange(C) < valid)[None],
                                  (B, C)).reshape(-1)
    wins = layer_windows(cfg, "decode", seq_len)
    segs = segments(cfg)
    attn_impl = (attn_mod.resolve_attn_impl(cfg.attention)
                 if cfg.attention is not None else None)

    li = 0
    new_caches = []
    for seg_idx, (kind, count) in enumerate(segs):
        stacked = params["blocks"][seg_idx]
        cache = caches[seg_idx]
        seg_wins = jnp.asarray(wins[li:li + count], jnp.int32)
        uniform = len(set(wins[li:li + count])) == 1
        static_win = wins[li] if uniform else None
        li += count

        def body(x, xs, _kind=kind, _static=static_win):
            lp, lc, w = xs
            win = _static if _static is not None else w
            x, nc = _prefill_layer(lp, lc, x, positions, pos0, valid,
                                   valid_flat, cfg, _kind, win,
                                   attn_impl=attn_impl, tables=block_tables,
                                   page_size=page_size)
            x = act.constrain(x)
            return x, nc

        if cfg.scan_layers and count > 1:
            h, nc = jax.lax.scan(body, h, (stacked, cache, seg_wins),
                                 unroll=_unroll_of(unroll, count))
        else:
            ncs = []
            for j in range(count):
                lp = jax.tree.map(lambda v: v[j], stacked)
                lc = jax.tree.map(lambda v: v[j], cache)
                h, nc1 = body(h, (lp, lc, seg_wins[j]))
                ncs.append(nc1)
            nc = jax.tree.map(lambda *vs: jnp.stack(vs), *ncs)
        new_caches.append(nc)

    h = rms_norm(h, params["ln_f"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = jnp.einsum("bsd,dv->bsv", h, head.astype(dtype))
    return logits, new_caches
