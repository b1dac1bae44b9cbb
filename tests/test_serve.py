"""Serving engine: greedy parity vs the generate() oracle per family,
compile-once under request churn, chunked-prefill bit-exactness, sampling
determinism, scheduler lifecycle, and checkpoint round-trip onto the serve
mesh."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.serve import Engine, Request, SamplingParams, SlotScheduler
from repro.serve import cache as cache_mod
from repro.serve import sampling as sampling_mod
from repro.train.serve import generate, _generate_stepwise

FAMILIES = ["llama3.2-1b", "mamba2-1.3b", "deepseek-v2-lite-16b"]


@functools.lru_cache(maxsize=None)
def _setup(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _mixed_workload(cfg, n_req=4, seed=0):
    rng = np.random.RandomState(seed)
    lens = [5, 12, 9, 17, 7, 14][:n_req]
    news = [6, 3, 9, 5, 8, 4][:n_req]
    prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in lens]
    return prompts, news


# ---------------------------------------------------------------------------
# greedy parity: engine == generate() per request, under churn
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", FAMILIES)
def test_engine_greedy_parity_with_generate(arch):
    """Mixed prompt/output lengths over fewer slots than requests: slots
    churn (evict + refill mid-flight) and every request's greedy tokens
    must still be bit-exact with the whole-batch-free oracle."""
    cfg, model, params = _setup(arch)
    prompts, news = _mixed_workload(cfg)
    eng = Engine(model, params, max_slots=3, max_seq=64, prefill_chunk=16)
    rids = [eng.submit(p, m) for p, m in zip(prompts, news)]
    res = eng.run()
    for rid, p, m in zip(rids, prompts, news):
        want = generate(model, params, jnp.asarray([p], jnp.int32),
                        max_new=m, seq_len=len(p) + m)
        assert res[rid] == np.asarray(want)[0, len(p):].tolist(), \
            f"{arch}: engine diverged from generate() for rid={rid}"


@pytest.mark.parametrize("arch", FAMILIES)
def test_generate_one_call_prefill_matches_stepwise(arch):
    """Satellite guard: the one-call prefill rewrite of generate() keeps
    outputs identical to the old token-by-token forced-decode loop."""
    cfg, model, params = _setup(arch)
    prompt = jax.random.randint(jax.random.key(3), (2, 11), 0,
                                cfg.vocab_size)
    new = generate(model, params, prompt, max_new=6, seq_len=17)
    old = _generate_stepwise(model, params, prompt, max_new=6, seq_len=17)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


# ---------------------------------------------------------------------------
# static-shape contract: one compile across churn
# ---------------------------------------------------------------------------

def test_decode_compiles_once_across_churn():
    cfg, model, params = _setup("llama3.2-1b")
    prompts, news = _mixed_workload(cfg, n_req=6)
    eng = Engine(model, params, max_slots=2, max_seq=64, prefill_chunk=8)
    for p, m in zip(prompts, news):
        eng.submit(p, m)
    eng.run()
    # 6 requests over 2 slots: many joins/evictions happened
    assert eng.stats.steps > 6
    assert eng.trace_counts["decode"] == 1, \
        f"decode retraced {eng.trace_counts['decode']}x under churn"
    assert eng.trace_counts["prefill"] == 1
    assert eng.trace_counts["sample"] == 1


def test_engine_late_submissions_no_retrace():
    """Requests arriving while the engine is mid-flight reuse the same
    compiled step."""
    cfg, model, params = _setup("llama3.2-1b")
    prompts, news = _mixed_workload(cfg, n_req=4)
    eng = Engine(model, params, max_slots=2, max_seq=64, prefill_chunk=8)
    eng.submit(prompts[0], news[0])
    for _ in range(2):
        eng.step()
    eng.submit(prompts[1], news[1])      # joins mid-decode
    eng.submit(prompts[2], news[2])
    res = eng.run()
    assert len(res) == 3
    assert eng.trace_counts["decode"] == 1


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["llama3.2-1b", "mamba2-1.3b"])
def test_chunked_prefill_cache_bitwise(arch):
    """Prefilling a prompt in aligned chunks leaves the cache bit-identical
    to a single-call prefill (SSM state/conv tail included)."""
    cfg, model, params = _setup(arch)
    total, S0, C = 48, 24, 16
    prompt = jax.random.randint(jax.random.key(1), (1, S0), 0,
                                cfg.vocab_size)
    pf = jax.jit(functools.partial(model.chunk_prefill, seq_len=total))
    cc = model.init_cache(1, total)
    lg = None
    for c in range(0, S0, C):
        sl = prompt[:, c:c + C]
        v = sl.shape[1]
        sl = jnp.pad(sl, ((0, 0), (0, C - v)))
        lg, cc = pf(params, cc, sl, jnp.int32(c), jnp.int32(v))
    cr = model.init_cache(1, total)
    lgr, cr = pf(params, cr, prompt, jnp.int32(0), jnp.int32(S0))
    np.testing.assert_array_equal(np.asarray(lg[:, v - 1]),
                                  np.asarray(lgr[:, -1]))
    if cfg.ssm is not None:
        # SSM cache must match on every leaf (state carries across chunks)
        for a, b in zip(jax.tree.leaves(cc), jax.tree.leaves(cr)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


def test_engine_rounds_prefill_chunk_to_ssd_blocks():
    cfg, model, params = _setup("mamba2-1.3b")
    eng = Engine(model, params, max_slots=1, max_seq=48, prefill_chunk=10)
    assert eng.prefill_chunk % cfg.ssm.chunk == 0


def test_last_chunk_window_cannot_clobber_prompt_rows():
    """Regression: an 18-token prompt on max_seq=20, prefill_chunk=16 puts
    the second chunk's write window [16, 32) past the pool edge; an
    unclamped pool would let dynamic_update_slice clamp pos0 to 4 and
    silently overwrite prompt K/V rows (engine returned garbage). The
    engine rounds max_seq up to a chunk multiple so every window fits."""
    cfg, model, params = _setup("llama3.2-1b")
    eng = Engine(model, params, max_slots=1, max_seq=20, prefill_chunk=16)
    assert eng.max_seq % eng.prefill_chunk == 0
    prompt = jax.random.randint(jax.random.key(5), (1, 18), 0,
                                cfg.vocab_size)
    rid = eng.submit(np.asarray(prompt)[0].tolist(), 2)
    got = eng.run()[rid]
    want = generate(model, params, prompt, max_new=2, seq_len=20)
    assert got == np.asarray(want)[0, 18:].tolist()


def test_submit_rejects_degenerate_requests():
    cfg, model, params = _setup("llama3.2-1b")
    eng = Engine(model, params, max_slots=1, max_seq=32, prefill_chunk=8)
    with pytest.raises(ValueError):
        eng.submit([], 4)
    with pytest.raises(ValueError):
        eng.submit([1, 2], 0)


def test_prefill_overwrites_stale_slot_state():
    """A reused slot must behave as if freshly reset: run a request on a
    dirty lane and on an explicitly reset lane, outputs match."""
    cfg, model, params = _setup("mamba2-1.3b")
    prompts, news = _mixed_workload(cfg, n_req=3)
    eng = Engine(model, params, max_slots=1, max_seq=64, prefill_chunk=16)
    r0 = eng.submit(prompts[0], news[0])
    res_dirty = eng.run()
    # same request on a zeroed pool
    eng.pool = cache_mod.reset_slot(eng.pool, jnp.int32(0))
    r1 = eng.submit(prompts[0], news[0])
    res_clean = eng.run()
    assert res_dirty[r0] == res_clean[r1]
    # and after serving a different request in between (dirty lane)
    r2 = eng.submit(prompts[1], news[1])
    eng.run()
    r3 = eng.submit(prompts[0], news[0])
    assert eng.run()[r3] == res_dirty[r0]


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def test_sampling_deterministic_under_fixed_keys():
    cfg, model, params = _setup("llama3.2-1b")
    prompts, news = _mixed_workload(cfg, n_req=2)
    sp = SamplingParams(temperature=0.9, seed=42)

    def run_once():
        eng = Engine(model, params, max_slots=2, max_seq=48,
                     prefill_chunk=8)
        rids = [eng.submit(p, m, sp) for p, m in zip(prompts, news)]
        return [eng.run()[r] for r in rids]

    assert run_once() == run_once()


def test_fused_sampling_matches_full_path():
    """slot_gather kernel path == jnp path for greedy and temperature."""
    cfg, model, params = _setup("llama3.2-1b")
    prompts, news = _mixed_workload(cfg, n_req=2)
    for temp in (0.0, 0.9):
        outs = []
        for fused in (False, True):
            eng = Engine(model, params, max_slots=2, max_seq=48,
                         prefill_chunk=8, fused_sampling=fused)
            rids = [eng.submit(p, m, SamplingParams(temperature=temp,
                                                    seed=7))
                    for p, m in zip(prompts, news)]
            res = eng.run()
            outs.append([res[r] for r in rids])
        assert outs[0] == outs[1], f"fused != full at temperature {temp}"


def test_fused_engine_rejects_topk_topp():
    cfg, model, params = _setup("llama3.2-1b")
    eng = Engine(model, params, max_slots=1, max_seq=32, prefill_chunk=8,
                 fused_sampling=True)
    with pytest.raises(ValueError):
        eng.submit([1, 2, 3], 2, SamplingParams(temperature=1.0, top_k=5))


def test_top_k_one_is_greedy():
    cfg, model, params = _setup("llama3.2-1b")
    prompts, news = _mixed_workload(cfg, n_req=1)

    def run_once(sp):
        eng = Engine(model, params, max_slots=1, max_seq=48,
                     prefill_chunk=8)
        rid = eng.submit(prompts[0], news[0], sp)
        return eng.run()[rid]

    assert run_once(SamplingParams(temperature=1.0, top_k=1, seed=5)) \
        == run_once(SamplingParams())


def test_sample_tokens_masks():
    """Unit checks of the fused sampler math on a hand-built distribution."""
    logits = jnp.log(jnp.asarray([[0.5, 0.25, 0.15, 0.07, 0.03]]))
    noise = jnp.zeros((1, 5))
    temps = jnp.ones((1,), jnp.float32)
    # top_p = 0.6: nucleus is {0, 1} (0.5 < 0.6 <= 0.75); noise=0 -> argmax
    tok = sampling_mod.sample_tokens(logits, temps, jnp.zeros((1,), jnp.int32),
                                     jnp.asarray([0.6]), noise)
    assert int(tok[0]) == 0
    # huge noise on a token outside the top_p nucleus cannot select it
    noise2 = jnp.zeros((1, 5)).at[0, 4].set(100.0)
    tok2 = sampling_mod.sample_tokens(logits, temps,
                                      jnp.zeros((1,), jnp.int32),
                                      jnp.asarray([0.6]), noise2)
    assert int(tok2[0]) in (0, 1)
    # same noise with top_p off selects it
    tok3 = sampling_mod.sample_tokens(logits, temps,
                                      jnp.zeros((1,), jnp.int32),
                                      jnp.asarray([1.0]), noise2)
    assert int(tok3[0]) == 4
    # top_k = 2 masks index >= 2 even with huge noise
    noise3 = jnp.zeros((1, 5)).at[0, 2].set(100.0)
    tok4 = sampling_mod.sample_tokens(logits, temps,
                                      jnp.asarray([2], jnp.int32),
                                      jnp.asarray([1.0]), noise3)
    assert int(tok4[0]) in (0, 1)


# ---------------------------------------------------------------------------
# scheduler lifecycle
# ---------------------------------------------------------------------------

def test_scheduler_fifo_and_slot_reuse():
    s = SlotScheduler(max_slots=2, max_seq=32)
    rids = [s.submit(Request(tokens=[1, 2], max_new=2)) for _ in range(4)]
    placed = s.admit()
    assert [r.rid for _, r in placed] == rids[:2]
    assert s.num_active == 2 and len(s.pending) == 2
    # finish slot 0's request -> evicted, refilled FIFO
    s.record_first_token(0, 9)
    s.record_first_token(1, 9)
    s.record_step([9, 9])      # both reach max_new=2 -> both freed
    assert s.num_active == 0
    placed = s.admit()
    assert [r.rid for _, r in placed] == rids[2:]
    assert sorted(sl for sl, _ in placed) == [0, 1]


def test_scheduler_eos_and_overflow():
    s = SlotScheduler(max_slots=1, max_seq=16)
    with pytest.raises(ValueError):
        s.submit(Request(tokens=[0] * 10, max_new=10))
    rid = s.submit(Request(tokens=[1, 2, 3], max_new=8, eos=7))
    s.admit()
    s.record_first_token(0, 4)
    s.record_step([7])         # eos fires mid-flight
    assert s.results()[rid] == [4, 7]
    assert s.num_active == 0


def test_scheduler_positions_track_cache_rows():
    s = SlotScheduler(max_slots=2, max_seq=32)
    s.submit(Request(tokens=[1, 2, 3], max_new=4))
    s.admit()
    assert s.positions() == [3, 0]
    s.record_first_token(0, 5)
    assert s.feed_tokens() == [5, 0]
    s.record_step([6, 0])
    assert s.positions() == [4, 0]


# ---------------------------------------------------------------------------
# mesh placement + checkpoint round-trip
# ---------------------------------------------------------------------------

def test_engine_on_mesh_matches_unsharded():
    cfg, model, params = _setup("llama3.2-1b")
    prompts, news = _mixed_workload(cfg, n_req=2)
    mesh = make_mesh((1,), ("data",))
    eng_m = Engine(model, params, max_slots=2, max_seq=48,
                   prefill_chunk=8, mesh=mesh)
    eng_u = Engine(model, params, max_slots=2, max_seq=48, prefill_chunk=8)
    rids_m = [eng_m.submit(p, m) for p, m in zip(prompts, news)]
    rids_u = [eng_u.submit(p, m) for p, m in zip(prompts, news)]
    res_m, res_u = eng_m.run(), eng_u.run()
    assert [res_m[r] for r in rids_m] == [res_u[r] for r in rids_u]


def test_checkpoint_roundtrip_into_serving(tmp_path):
    """ckpt.save params -> restore onto the serve-mesh sharding -> engine
    output matches pre-save."""
    from repro.checkpoint.ckpt import save_checkpoint, restore_checkpoint
    from repro.dist.sharding import param_shardings

    cfg, model, params = _setup("llama3.2-1b")
    prompts, news = _mixed_workload(cfg, n_req=2)
    save_checkpoint(str(tmp_path / "ck"), params, step=7)

    mesh = make_mesh((1,), ("data",))
    like = jax.device_put(params, param_shardings(mesh, params))
    restored = restore_checkpoint(str(tmp_path / "ck"), like)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))

    eng0 = Engine(model, params, max_slots=2, max_seq=48, prefill_chunk=8)
    eng1 = Engine(model, restored, max_slots=2, max_seq=48,
                  prefill_chunk=8, mesh=mesh)
    r0 = [eng0.submit(p, m) for p, m in zip(prompts, news)]
    r1 = [eng1.submit(p, m) for p, m in zip(prompts, news)]
    out0, out1 = eng0.run(), eng1.run()
    assert [out0[r] for r in r0] == [out1[r] for r in r1]


# ---------------------------------------------------------------------------
# MoE slot independence (the drop-free routing contract)
# ---------------------------------------------------------------------------

def test_moe_decode_independent_of_batch_composition():
    """A request's greedy tokens must not depend on what other slots are
    doing — deepseek routes through MoE layers where capacity drops would
    couple lanes; drop-free decode routing removes that."""
    cfg, model, params = _setup("deepseek-v2-lite-16b")
    prompts, news = _mixed_workload(cfg, n_req=3)
    solo = Engine(model, params, max_slots=1, max_seq=64, prefill_chunk=16)
    rid_s = solo.submit(prompts[0], news[0])
    want = solo.run()[rid_s]
    crowd = Engine(model, params, max_slots=3, max_seq=64, prefill_chunk=16)
    rids = [crowd.submit(p, m) for p, m in zip(prompts, news)]
    got = crowd.run()[rids[0]]
    assert got == want
