"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
sweeping shapes and dtypes."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:   # no hypothesis in this env: deterministic fallback
    from repro.testing.hypofallback import given, settings, st

from repro.kernels import default_interpret, ops, ref
from repro.kernels.chunk_sum import chunk_sum as raw_chunk_sum
from repro.kernels.fused_rs_update import fused_rs_update as raw_rs_update
from repro.kernels.fused_sgd import fused_sgd as raw_fused_sgd
from repro.kernels.quantize import (quant_int8 as raw_quant_int8,
                                    dequant_int8 as raw_dequant_int8)
from repro.kernels.slot_gather import slot_gather_sample


@pytest.mark.parametrize("k", [2, 4, 8, 16])
@pytest.mark.parametrize("n", [100, 2048, 5000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float16, jnp.bfloat16])
def test_chunk_sum_matches_ref(k, n, dtype):
    x = (jax.random.normal(jax.random.key(k * n), (k, n)) * 3).astype(dtype)
    got = raw_chunk_sum(x, interpret=True)
    want = ref.chunk_sum_ref(x)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("block_n", [256, 2048])
def test_chunk_sum_block_sizes(block_n):
    x = jax.random.normal(jax.random.key(0), (4, 3333)).astype(jnp.bfloat16)
    got = raw_chunk_sum(x, block_n=block_n, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.chunk_sum_ref(x)), rtol=1e-6)


def test_chunk_sum_fp32_accumulation_beats_fp16():
    # many small fp16 values: fp16 accumulation would lose precision
    k, n = 16, 512
    x = jnp.full((k, n), 0.1, jnp.float16)
    got = raw_chunk_sum(x, interpret=True)
    fp16_sum = x.sum(axis=0)  # fp16 accumulate
    exact = k * np.float32(np.float16(0.1))
    assert abs(float(got[0]) - exact) <= abs(float(fp16_sum[0]) - exact)


@pytest.mark.parametrize("n", [100, 2048, 4096 + 17])
def test_quant_int8_roundtrip_and_ref(n):
    x = jax.random.normal(jax.random.key(n), (n,)) * 5
    q, s = raw_quant_int8(x, interpret=True)
    qr, sr = ref.quant_int8_ref(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    d = raw_dequant_int8(q, s, interpret=True)
    # error bounded by scale/2 per block
    err = np.max(np.abs(np.asarray(d) - np.asarray(x)))
    assert err <= float(jnp.max(s)) * 0.5 + 1e-6


@pytest.mark.parametrize("n", [128, 5000])
@pytest.mark.parametrize("nesterov", [False, True])
def test_fused_sgd_matches_ref(n, nesterov):
    key = jax.random.key(n)
    p = jax.random.normal(key, (n,))
    g = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    m = jax.random.normal(jax.random.fold_in(key, 2), (n,))
    po, mo = raw_fused_sgd(p, g, m, 0.05, momentum=0.9, nesterov=nesterov,
                           interpret=True)
    pr, mr = ref.fused_sgd_ref(p, g, m, 0.05, momentum=0.9, nesterov=nesterov)
    np.testing.assert_allclose(np.asarray(po), np.asarray(pr), rtol=2e-5,
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(mo), np.asarray(mr), rtol=2e-5,
                               atol=1e-7)


def test_ops_wrappers_nd_shapes():
    x = jax.random.normal(jax.random.key(0), (4, 8, 16)).astype(jnp.bfloat16)
    got = ops.chunk_sum(x)
    assert got.shape == (8, 16)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.chunk_sum_ref(x.reshape(4, -1))
                                          .reshape(8, 16)), rtol=1e-6)
    p = jax.random.normal(jax.random.key(1), (8, 16))
    po, mo = ops.fused_sgd(p, p, jnp.zeros_like(p), 0.1)
    assert po.shape == (8, 16)


@pytest.mark.parametrize("k", [2, 8])
@pytest.mark.parametrize("n", [128, 5000])
@pytest.mark.parametrize("nesterov", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float16])
def test_fused_rs_update_matches_ref(k, n, nesterov, dtype):
    key = jax.random.key(k * n + nesterov)
    recv = (jax.random.normal(key, (k, n)) * 2).astype(dtype)
    p = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    m = jax.random.normal(jax.random.fold_in(key, 2), (n,))
    mask = (jax.random.uniform(jax.random.fold_in(key, 3), (n,))
            > 0.5).astype(jnp.float32)
    kw = dict(momentum=0.9, nesterov=nesterov, scale=1.0 / k,
              weight_decay=5e-4)
    po, mo = raw_rs_update(recv, p, m, mask, 0.05, interpret=True, **kw)
    pr, mr = ref.fused_rs_update_ref(recv, p, m, mask, 0.05, **kw)
    np.testing.assert_allclose(np.asarray(po), np.asarray(pr), rtol=2e-5,
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(mo), np.asarray(mr), rtol=2e-5,
                               atol=1e-7)


def test_fused_rs_update_matches_chunk_sum_plus_fused_sgd():
    """The fused kernel == default_chunk_sum -> (wd) -> fused_sgd chain."""
    k, n = 8, 4000
    key = jax.random.key(7)
    recv = (jax.random.normal(key, (k, n)) * 2).astype(jnp.float16)
    p = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    m = jax.random.normal(jax.random.fold_in(key, 2), (n,))
    mask = jnp.ones((n,), jnp.float32)
    po, mo = raw_rs_update(recv, p, m, mask, 0.05, momentum=0.9,
                           nesterov=True, scale=1.0 / k, weight_decay=5e-4,
                           interpret=True)
    g = ref.chunk_sum_ref(recv) / k + 5e-4 * p
    pc, mc = ops.fused_sgd(p, g, m, 0.05, momentum=0.9, nesterov=True)
    np.testing.assert_allclose(np.asarray(po), np.asarray(pc), rtol=1e-6,
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(mo), np.asarray(mc), rtol=1e-6,
                               atol=1e-7)


def test_fused_rs_update_int8_dequant():
    """int8 wire variant dequantizes with one fp32 scale per rank chunk."""
    k, n = 4, 3001
    key = jax.random.key(3)
    q = jax.random.randint(key, (k, n), -127, 128, dtype=jnp.int8)
    scales = jax.random.uniform(jax.random.fold_in(key, 1), (k,)) * 0.01
    p = jax.random.normal(jax.random.fold_in(key, 2), (n,))
    m = jnp.zeros((n,))
    mask = jnp.zeros((n,), jnp.float32)
    po, mo = raw_rs_update(q, p, m, mask, 0.1, scale=1.0 / k, scales=scales,
                           interpret=True)
    pr, mr = ref.fused_rs_update_ref(q, p, m, mask, 0.1, scale=1.0 / k,
                                     scales=scales)
    np.testing.assert_allclose(np.asarray(po), np.asarray(pr), rtol=1e-6,
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(mo), np.asarray(mr), rtol=1e-6,
                               atol=1e-7)


def _rs_update_case(k, n, wire, seed):
    key = jax.random.key(seed)
    if wire == "int8":
        recv = jax.random.randint(key, (k, n), -127, 128, dtype=jnp.int8)
        scales = jax.random.uniform(jax.random.fold_in(key, 4), (k,)) * 0.01
    else:
        recv = (jax.random.normal(key, (k, n)) * 2).astype(wire)
        scales = None
    p = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    m = jax.random.normal(jax.random.fold_in(key, 2), (n,))
    mask = (jax.random.uniform(jax.random.fold_in(key, 3), (n,))
            > 0.5).astype(jnp.float32)
    return recv, p, m, mask, scales


# (k, n, block_rows): k = 1 is the one-chip cell's receive; block_rows
# forces several blocks with a ragged last one (5000 pads to 40 rows of
# 128 = 16 + 16 + 8; 12800 is 100 rows = 3 x 32 + 4); None derives it
@pytest.mark.parametrize("k,n,block_rows", [(1, 5000, 16), (1, 12800, 32),
                                            (1, 4096, None),
                                            (4, 3001, 8), (3, 12800, None)])
@pytest.mark.parametrize("wire", [jnp.float32, jnp.float16, "int8"])
@pytest.mark.parametrize("nesterov", [False, True])
def test_fused_rs_update_tiles_match_ref(k, n, block_rows, wire, nesterov):
    """Lane-dense (rows, 128) tiling, a ragged last block, n % 128 != 0.

    A one-chunk float receive (the one-chip cell's) has no sum order to
    differ in, so the kernel must equal the compiled reference bit for
    bit; elsewhere the CPU compiler may associate the chunk sum or fuse
    the dequant multiply differently, so the tolerance of fp32 rounding."""
    recv, p, m, mask, scales = _rs_update_case(k, n, wire, k * n + nesterov)
    kw = dict(momentum=0.9, nesterov=nesterov, scale=1.0 / k,
              weight_decay=5e-4, scales=scales)
    po, mo = raw_rs_update(recv, p, m, mask, 0.05, block_rows=block_rows,
                           interpret=True, **kw)
    assert po.shape == mo.shape == (n,)
    if k == 1 and wire != "int8":
        pr, mr = jax.jit(functools.partial(ref.fused_rs_update_ref, **kw))(
            recv, p, m, mask, 0.05)
        np.testing.assert_array_equal(np.asarray(po), np.asarray(pr))
        np.testing.assert_array_equal(np.asarray(mo), np.asarray(mr))
    else:
        pr, mr = ref.fused_rs_update_ref(recv, p, m, mask, 0.05, **kw)
        np.testing.assert_allclose(np.asarray(po), np.asarray(pr), rtol=2e-5,
                                   atol=1e-7)
        np.testing.assert_allclose(np.asarray(mo), np.asarray(mr), rtol=2e-5,
                                   atol=1e-7)


@pytest.mark.parametrize("k,itemsize,want", [(1, 4, 1344), (4, 1, 1344),
                                             (4, 2, 1152), (4, 4, 896),
                                             (64, 4, 96), (512, 4, 32)])
def test_fused_rs_update_tile_rows_fill_the_vmem_budget(k, itemsize, want):
    """Rows per block: whole 32-row tiles whose double-buffered operands
    stay inside the budget (at least one tile however large ``k``)."""
    from repro.kernels.fused_rs_update import VMEM_TILE_BYTES, tile_rows
    br = tile_rows(k, itemsize)
    assert br == want and br % 32 == 0
    row = 2 * 128 * (k * itemsize + 5 * 4)
    assert br * row <= VMEM_TILE_BYTES or br == 32
    assert (br + 32) * row > VMEM_TILE_BYTES


def test_default_interpret_cpu_and_env(monkeypatch):
    """Backend autodetect: interpret on CPU; env overrides win."""
    assert default_interpret() is True   # this container is CPU-only
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert default_interpret() is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert default_interpret() is True
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET")
    # the removed legacy switch no longer forces compiled mode off-TPU
    monkeypatch.setenv("REPRO_PALLAS_COMPILED", "1")
    assert default_interpret() is True


@settings(max_examples=20, deadline=None)
@given(k=st.integers(2, 8), n=st.integers(1, 600))
def test_chunk_sum_property(k, n):
    x = (jax.random.normal(jax.random.key(k + 31 * n), (k, n)) * 2).astype(
        jnp.float16)
    got = raw_chunk_sum(x, block_n=256, interpret=True)
    want = np.asarray(x, np.float32).sum(axis=0)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 3000))
def test_int8_error_bound_property(n):
    x = jax.random.normal(jax.random.key(n), (n,)) * 10
    q, s = ref.quant_int8_ref(x)
    d = ref.dequant_int8_ref(q, s)
    err = np.max(np.abs(np.asarray(d) - np.asarray(x)))
    assert err <= float(jnp.max(s)) * 0.5 + 1e-6


# ---------------------------------------------------------------------------
# slot_gather: fused per-slot logit gather + sampling transform
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,C,V", [(1, 1, 500), (4, 1, 512),
                                   (5, 8, 700), (3, 16, 130)])
def test_slot_gather_sample_matches_ref(S, C, V):
    key = jax.random.key(S * 1000 + C * 10 + V)
    logits = jax.random.normal(key, (S, C, V), jnp.float32) * 3
    idx = jax.random.randint(jax.random.fold_in(key, 1), (S,), 0, C)
    onehot = jax.nn.one_hot(idx, C)
    temps = jax.random.uniform(jax.random.fold_in(key, 2), (S,)) * 2
    temps = temps.at[0].set(0.0)              # one greedy slot
    noise = jax.random.gumbel(jax.random.fold_in(key, 3), (S, V))
    g1, s1 = slot_gather_sample(logits, onehot, temps, noise,
                                interpret=True, block_v=256)
    g2, s2 = ref.slot_gather_sample_ref(logits, onehot, temps, noise)
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_slot_gather_bf16_logits_and_tie_breaking():
    # bf16 decode logits produce ties; argmax must pick the first (ref
    # semantics) in compiled-grid accumulation too
    S, V = 3, 600
    logits = jnp.zeros((S, 1, V), jnp.bfloat16)
    logits = logits.at[:, 0, 37].set(2.0).at[:, 0, 412].set(2.0)
    onehot = jnp.ones((S, 1))
    temps = jnp.zeros((S,))
    noise = jnp.zeros((S, V))
    g, _ = slot_gather_sample(logits, onehot, temps, noise,
                              interpret=True, block_v=128)
    assert np.asarray(g).tolist() == [37, 37, 37]


def test_slot_gather_gathers_correct_row():
    # each slot picks a different chunk row; greedy index must follow it
    S, C, V = 4, 4, 256
    base = jnp.full((S, C, V), -1.0, jnp.float32)
    idx = jnp.asarray([0, 1, 2, 3])
    want = jnp.asarray([10, 20, 30, 40])
    logits = base
    for s in range(S):
        logits = logits.at[s, idx[s], want[s]].set(5.0)
        # decoy max in a row the slot must NOT gather
        logits = logits.at[s, (idx[s] + 1) % C, (want[s] + 1) % V].set(9.0)
    onehot = jax.nn.one_hot(idx, C)
    g, _ = slot_gather_sample(logits, onehot, jnp.zeros((S,)),
                              jnp.zeros((S, V)), interpret=True)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(want))
