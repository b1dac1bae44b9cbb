"""Pallas TPU kernel: fused per-slot logit gather + sampling transform.

The serving engine's decode/prefill steps end with, per slot s:

    row_s   = logits[s, idx_s, :]                (gather the slot's token row)
    greedy  = argmax(row_s)
    sampled = argmax(row_s / T_s + gumbel_s)     (Gumbel-max == categorical)

The unfused pipeline materializes the gathered (S, V) rows in HBM, then
re-reads them twice (scale+noise, argmax). This kernel streams one
(S, C, block_v) logit tile through VMEM per grid step and carries the
running (max, argmax) for both the greedy and the noise-perturbed rows in
the revisited output vectors — logits are read exactly once. The gather is
a one-hot contraction over the chunk axis (C == 1 for decode steps,
C == prefill_chunk for the prefill tail), which maps onto the VPU instead
of a dynamic gather.

Top-k/top-p sampling needs a vocab sort and stays on the jnp path
(``repro.serve.sampling``); the kernel serves the greedy/temperature fast
path. Parity-tested against ``slot_gather_sample_ref`` in
``tests/test_kernels.py`` (shared noise makes the comparison exact).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

NEG_INF = -1e30
DEFAULT_BLOCK_V = 512


def _kernel(lg_ref, oh_ref, t_ref, nz_ref, gv_ref, gi_ref, sv_ref, si_ref,
            *, block_v: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        gv_ref[...] = jnp.full_like(gv_ref, NEG_INF)
        gi_ref[...] = jnp.zeros_like(gi_ref)
        sv_ref[...] = jnp.full_like(sv_ref, NEG_INF)
        si_ref[...] = jnp.zeros_like(si_ref)

    lg = lg_ref[...].astype(jnp.float32)            # (S, C, bv)
    oh = oh_ref[...]                                # (S, C) one-hot fp32
    row = jnp.sum(lg * oh[..., None], axis=1)       # (S, bv) gathered rows

    def fold(vals, bv_ref, bi_ref):
        m = jnp.max(vals, axis=1)
        gidx = jnp.argmax(vals, axis=1).astype(jnp.int32) + i * block_v
        better = m > bv_ref[...]                    # strict: first tile wins
        bi_ref[...] = jnp.where(better, gidx, bi_ref[...])
        bv_ref[...] = jnp.where(better, m, bv_ref[...])

    fold(row, gv_ref, gi_ref)
    t = jnp.maximum(t_ref[...], 1e-6)               # (S,)
    fold(row / t[:, None] + nz_ref[...], sv_ref, si_ref)


@functools.partial(jax.jit, static_argnames=("block_v", "interpret"))
def slot_gather_sample(logits, onehot, temperature, noise, *,
                       block_v: int = DEFAULT_BLOCK_V,
                       interpret: bool | None = None):
    """logits: (S, C, V); onehot: (S, C) fp32 selecting each slot's token
    row; temperature: (S,) fp32; noise: (S, V) fp32 Gumbel.

    Returns (greedy (S,), sampled (S,)) int32 — the argmax of each slot's
    gathered row and of its temperature-scaled noise-perturbed row."""
    interpret = resolve_interpret(interpret)
    S, C, V = logits.shape
    pad = (-V) % block_v
    if pad:
        logits = jnp.pad(logits, ((0, 0), (0, 0), (0, pad)),
                         constant_values=NEG_INF)
        noise = jnp.pad(noise, ((0, 0), (0, pad)))
    vp = V + pad
    grid = (vp // block_v,)
    vec = pl.BlockSpec((S,), lambda i: (0,))
    gv, gi, sv, si = pl.pallas_call(
        functools.partial(_kernel, block_v=block_v),
        grid=grid,
        in_specs=[pl.BlockSpec((S, C, block_v), lambda i: (0, 0, i)),
                  pl.BlockSpec((S, C), lambda i: (0, 0)),
                  vec,
                  pl.BlockSpec((S, block_v), lambda i: (0, i))],
        out_specs=[vec, vec, vec, vec],
        out_shape=[jax.ShapeDtypeStruct((S,), jnp.float32),
                   jax.ShapeDtypeStruct((S,), jnp.int32),
                   jax.ShapeDtypeStruct((S,), jnp.float32),
                   jax.ShapeDtypeStruct((S,), jnp.int32)],
        interpret=interpret,
    )(logits, onehot.astype(jnp.float32), temperature.astype(jnp.float32),
      noise.astype(jnp.float32))
    del gv, sv
    return gi, si
