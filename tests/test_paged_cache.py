"""Paged KV cache + hashed prefix caching: allocator lifecycle (refcounts,
reservations, LRU eviction, OutOfPages), hash-collision safety, COW
isolation, paged-vs-contiguous greedy bit-identity across GQA and
absorbed-MLA layouts under request churn, slot density at fixed cache
rows, compile-once with block tables, the paged flash-decode kernel vs
gathered-lane oracle, paged pool shardings, and the SSM clean-lane
regression for the O(d_state) admission reset."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serve import Engine, SamplingParams
from repro.serve import cache as cache_mod
from repro.serve.cache import NULL_PAGE, OutOfPages, PageAllocator
from repro.train.serve import generate


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
# allocator: refcounts, reservations, eviction, exhaustion
# ---------------------------------------------------------------------------

def test_alloc_refcount_lifecycle():
    al = PageAllocator(num_pages=9, page_size=4, max_slots=2,
                       pages_per_slot=4)
    # admit a 6-token prompt + 3 new -> ceil(9/4) = 3 pages reserved
    assert al.try_admit(0, list(range(6)), 3) == 0      # no cache yet
    assert al._reserved[0] == 3
    assert al.available() == 8 - 3
    # first-touch allocation walks the reservation down
    assert al.ensure_writable(0, 0) == []
    assert al.ensure_writable(0, 4) == []
    assert al.tables[0, 0] != NULL_PAGE
    assert al._reserved[0] == 1
    assert al.ensure_writable(0, 2) == []     # same page: already private
    al.register_prefix(0, list(range(6)))     # publishes 1 full page
    pid = int(al.tables[0, 0])
    assert al.refs[pid] == 2                  # slot + cache
    al.release_slot(0)
    assert al.refs[pid] == 1                  # cache keeps it
    assert not al.tables[0].any() and al._reserved[0] == 0
    # the second allocated page went back to the free list
    assert al.allocated == 1


def test_alloc_admission_reserves_and_blocks():
    al = PageAllocator(num_pages=5, page_size=4, max_slots=2,
                       pages_per_slot=4, prefix_cache=False)
    assert al.try_admit(0, list(range(8)), 4) is not None   # 3 pages
    assert al.try_admit(1, list(range(8)), 4) is None       # 3 > 4-3
    # zero mutation on refusal
    assert not al.tables[1].any() and al._reserved[1] == 0
    al.release_slot(0)
    assert al.try_admit(1, list(range(8)), 4) is not None


def test_alloc_out_of_pages_is_guarded():
    al = PageAllocator(num_pages=2, page_size=4, max_slots=1,
                       pages_per_slot=2, prefix_cache=False)
    assert al.ensure_writable(0, 0) == []
    with pytest.raises(OutOfPages):
        al.ensure_writable(0, 4)


def test_alloc_lru_eviction_of_cache_pages():
    al = PageAllocator(num_pages=4, page_size=2, max_slots=1,
                       pages_per_slot=3)
    # request A: 4-token prompt -> 2 cached pages after release
    assert al.try_admit(0, [1, 2, 3, 4], 1) == 0
    al.ensure_writable(0, 0), al.ensure_writable(0, 2)
    al.register_prefix(0, [1, 2, 3, 4])
    al.release_slot(0)
    assert al.allocated == 2 and al._evictable() == 2
    # request B needs all 3 pages -> evicts the oldest cache pages
    assert al.try_admit(0, [9, 8, 7, 6], 2) == 0
    al.ensure_writable(0, 0), al.ensure_writable(0, 2)
    al.ensure_writable(0, 4)
    assert al.evictions >= 1
    al.release_slot(0)


def test_prefix_hit_and_full_hit_accounting():
    al = PageAllocator(num_pages=8, page_size=2, max_slots=2,
                       pages_per_slot=3)
    toks = [5, 6, 7, 8]
    assert al.try_admit(0, toks, 2) == 0
    al.ensure_writable(0, 0), al.ensure_writable(0, 2)
    al.register_prefix(0, toks)
    # partial hit: same 2-page head, longer tail
    got = al.try_admit(1, toks + [9, 9], 1)
    assert got == 4
    assert al.tables[1, 0] == al.tables[0, 0]
    assert al.tables[1, 1] == al.tables[0, 1]
    al.release_slot(1)
    al.release_slot(0)
    # full hit: entire prompt cached -> re-run 1 token, need = +1 COW page
    got = al.try_admit(0, toks, 2)
    assert got == 4
    assert al._reserved[0] == 2               # 1 decode page + 1 COW


def test_hash_collision_is_miss_not_corruption(monkeypatch):
    al = PageAllocator(num_pages=8, page_size=2, max_slots=2,
                       pages_per_slot=3)
    monkeypatch.setattr(cache_mod, "hash_prefix_chunk",
                        lambda prev, tokens: b"same-digest")
    assert al.try_admit(0, [1, 2], 1) == 0
    al.ensure_writable(0, 0)
    al.register_prefix(0, [1, 2])
    # different tokens, same digest: token verification rejects the entry
    assert al.try_admit(1, [3, 4], 1) == 0
    assert al.collisions == 1
    # identical tokens still hit through the colliding digest
    al.release_slot(1)
    assert al.try_admit(1, [1, 2], 1) == 2


def test_release_refcounts_under_shared_pages():
    """Two slots sharing hit pages + the cache ref: releases in any order
    never underflow and the cache copy survives for the next hit."""
    al = PageAllocator(num_pages=10, page_size=2, max_slots=3,
                       pages_per_slot=3)
    toks = [4, 4, 4, 4]
    al.try_admit(0, toks, 2)
    al.ensure_writable(0, 0), al.ensure_writable(0, 2)
    al.register_prefix(0, toks)
    assert al.try_admit(1, toks + [1, 1], 1) == 4
    assert al.try_admit(2, toks + [2, 2], 1) == 4
    pid = int(al.tables[0, 0])
    assert al.refs[pid] == 4                  # cache + 3 slots
    al.release_slot(0)
    al.release_slot(2)
    al.release_slot(1)
    assert al.refs[pid] == 1
    assert al.try_admit(0, toks, 2) == 4      # still serves hits


# ---------------------------------------------------------------------------
# engine: paged vs contiguous bit-identity under churn
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["llama3.2-1b", "deepseek-v2-lite-16b"])
def test_paged_engine_bit_identical_to_contiguous(arch):
    """Greedy tokens from the paged engine (default) match the contiguous
    oracle engine AND generate(), under slot churn, for GQA and absorbed
    MLA — and the paged decode step compiles exactly once."""
    cfg, model, params = _setup(arch)
    prompts, news = _mixed_workload(cfg)
    eng_p = Engine(model, params, max_slots=3, max_seq=64,
                   prefill_chunk=16, page_size=8)
    eng_c = Engine(model, params, max_slots=3, max_seq=64,
                   prefill_chunk=16, page_size=0)
    assert eng_p.paged and not eng_c.paged
    rp = [eng_p.submit(p, m) for p, m in zip(prompts, news)]
    rc = [eng_c.submit(p, m) for p, m in zip(prompts, news)]
    res_p, res_c = eng_p.run(), eng_c.run()
    for a, b, p, m in zip(rp, rc, prompts, news):
        assert res_p[a] == res_c[b], f"{arch}: paged != contiguous"
        want = generate(model, params, jnp.asarray([p], jnp.int32),
                        max_new=m, seq_len=len(p) + m)
        assert res_p[a] == np.asarray(want)[0, len(p):].tolist(), \
            f"{arch}: paged engine diverged from generate()"
    assert eng_p.trace_counts["decode"] == 1
    assert eng_p.trace_counts["prefill"] == 1


def test_paged_pool_doubles_active_slots_at_fixed_cache_rows():
    """Density at a fixed cache budget: the contiguous pool reserves
    ``max_seq`` rows per slot whatever a request needs; the same rows cut
    into pages (plus the null page) hold twice the slots at once, since
    every request here needs far fewer than ``max_seq`` rows."""
    cfg, model, params = _setup("llama3.2-1b")
    slots, page = 4, 16
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in rng.randint(4, 24, size=2 * slots)]
    kw = dict(max_seq=256, prefill_chunk=16)

    def peak_active(eng):
        for p in prompts:
            eng.submit(p, 16)
        peak = 0
        while eng.sched.has_work():
            eng.step()
            peak = max(peak, eng.sched.num_active)
        return peak

    eng_c = Engine(model, params, max_slots=slots, page_size=0, **kw)
    cache_rows = slots * eng_c.max_seq
    eng_p = Engine(model, params, max_slots=2 * slots, page_size=page,
                   num_pages=cache_rows // page + 1, **kw)
    peak_c = peak_active(eng_c)
    assert peak_c == slots
    assert peak_active(eng_p) == 2 * peak_c


def test_prefix_hit_skips_prefill_and_stays_bit_identical():
    """Warm requests reuse cached pages: fewer prefill tokens computed,
    same greedy tokens, and the diverging-tail request COWs instead of
    mutating the shared pages (the repeated request still hits after)."""
    cfg, model, params = _setup("llama3.2-1b")
    rng = np.random.RandomState(3)
    head = rng.randint(0, cfg.vocab_size, size=16).tolist()
    tail = rng.randint(0, cfg.vocab_size, size=5).tolist()
    eng = Engine(model, params, max_slots=2, max_seq=64, prefill_chunk=8,
                 page_size=8)
    oracle = {}
    for p, m in [(head, 6), (head + tail, 6), (head, 6)]:
        want = generate(model, params, jnp.asarray([p], jnp.int32),
                        max_new=m, seq_len=len(p) + m)
        oracle[tuple(p)] = np.asarray(want)[0, len(p):].tolist()

    r0 = eng.submit(head, 6)
    eng.run()
    cold_prefill = eng.stats.prefill_tokens
    assert eng.allocator.hit_tokens == 0
    res = eng.run() or eng.sched.results()
    assert res[r0] == oracle[tuple(head)]

    # warm: same head + diverging tail -> 2 pages hit, tail computed
    r1 = eng.submit(head + tail, 6)
    eng.run()
    assert eng.allocator.hit_tokens == 16
    res = eng.sched.results()
    assert res[r1] == oracle[tuple(head + tail)]

    # the full-hit repeat: only the last prompt token re-runs (for its
    # logits), through a COW copy — cached pages were never mutated by r1
    r2 = eng.submit(head, 6)
    eng.run()
    assert eng.allocator.hit_tokens == 32
    assert eng.allocator.cow_copies >= 1
    res = eng.sched.results()
    assert res[r2] == oracle[tuple(head)]
    warm_prefill = eng.stats.prefill_tokens - cold_prefill
    assert warm_prefill == len(tail) + 1      # tail chunk-rounded? no: 5+1
    assert eng.trace_counts["decode"] == 1


def test_cow_isolation_under_concurrent_divergence():
    """Two live requests sharing a cached head and diverging mid-page must
    not see each other's tails (COW splits the shared page)."""
    cfg, model, params = _setup("llama3.2-1b")
    rng = np.random.RandomState(11)
    head = rng.randint(0, cfg.vocab_size, size=8).tolist()   # 1 full page
    t1 = rng.randint(0, cfg.vocab_size, size=3).tolist()
    t2 = rng.randint(0, cfg.vocab_size, size=3).tolist()
    eng = Engine(model, params, max_slots=2, max_seq=64, prefill_chunk=8,
                 page_size=8)
    # publish the head
    eng.submit(head, 2)
    eng.run()
    # both tails decode concurrently from the shared head pages
    ra = eng.submit(head + t1, 8)
    rb = eng.submit(head + t2, 8)
    res = eng.run()
    for p, r in [(head + t1, ra), (head + t2, rb)]:
        want = generate(model, params, jnp.asarray([p], jnp.int32),
                        max_new=8, seq_len=len(p) + 8)
        assert res[r] == np.asarray(want)[0, len(p):].tolist()


def test_tiny_page_pool_head_of_line_completes():
    """A page pool far smaller than worst case still serves the whole
    queue: head-of-line admission waits for releases instead of
    deadlocking, and results stay bit-identical to the oracle."""
    cfg, model, params = _setup("llama3.2-1b")
    prompts, news = _mixed_workload(cfg)
    # worst case would want 3 slots * 64 rows = 24 pages; give 9 usable
    eng = Engine(model, params, max_slots=3, max_seq=64, prefill_chunk=16,
                 page_size=8, num_pages=10)
    rids = [eng.submit(p, m) for p, m in zip(prompts, news)]
    res = eng.run()
    for rid, p, m in zip(rids, prompts, news):
        want = generate(model, params, jnp.asarray([p], jnp.int32),
                        max_new=m, seq_len=len(p) + m)
        assert res[rid] == np.asarray(want)[0, len(p):].tolist()
    assert eng.trace_counts["decode"] == 1


def test_submit_rejects_request_larger_than_page_pool():
    cfg, model, params = _setup("llama3.2-1b")
    eng = Engine(model, params, max_slots=2, max_seq=64, prefill_chunk=16,
                 page_size=8, num_pages=4)
    with pytest.raises(ValueError, match="pages"):
        eng.submit(list(range(30)), 10)


def test_ssm_engine_falls_back_to_slot_granular():
    """Pure-SSM families have nothing to page: the engine runs the
    contiguous pool, parity with generate() holds, and a reused slot
    starts from clean conv/state lanes (the O(d_state) admission reset)."""
    cfg, model, params = _setup("mamba2-1.3b")
    eng = Engine(model, params, max_slots=1, max_seq=64, prefill_chunk=16,
                 page_size=16)
    assert not eng.paged and eng.allocator is None
    prompts, news = _mixed_workload(cfg, n_req=3)
    # serial through one slot: each request inherits the previous
    # occupant's lane and must still match the clean-pool oracle
    rids = [eng.submit(p, m) for p, m in zip(prompts, news)]
    res = eng.run()
    for rid, p, m in zip(rids, prompts, news):
        want = generate(model, params, jnp.asarray([p], jnp.int32),
                        max_new=m, seq_len=len(p) + m)
        assert res[rid] == np.asarray(want)[0, len(p):].tolist()


def test_hybrid_paged_attn_with_ssm_lanes():
    """Hybrid families page their attention leaves while SSM lanes stay
    slot-granular; the prefix cache is disabled (SSM state is not
    reconstructible from pages) and parity still holds under churn."""
    cfg, model, params = _setup("hymba-1.5b")
    eng = Engine(model, params, max_slots=2, max_seq=64, prefill_chunk=16,
                 page_size=8)
    if not eng.paged:
        pytest.skip("family has no attention leaves")
    assert not eng.allocator.prefix_cache
    prompts, news = _mixed_workload(cfg, n_req=3)
    rids = [eng.submit(p, m) for p, m in zip(prompts, news)]
    res = eng.run()
    for rid, p, m in zip(rids, prompts, news):
        want = generate(model, params, jnp.asarray([p], jnp.int32),
                        max_new=m, seq_len=len(p) + m)
        assert res[rid] == np.asarray(want)[0, len(p):].tolist()


# ---------------------------------------------------------------------------
# kernel: paged flash decode vs gathered-lane flash decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [0, 7])
def test_flash_decode_paged_matches_contiguous(window):
    from repro.kernels.flash_attention import flash_decode, flash_decode_paged
    B, H, KV, Dk, Dv, ps, npg = 2, 4, 2, 16, 16, 8, 7
    key = jax.random.key(0)
    q = jax.random.normal(jax.random.fold_in(key, 1), (B, 1, H, Dk))
    k_pages = jax.random.normal(jax.random.fold_in(key, 2),
                                (npg, KV, ps, Dk))
    v_pages = jax.random.normal(jax.random.fold_in(key, 3),
                                (npg, KV, ps, Dv))
    tables = jnp.asarray([[1, 3, 5], [2, 4, 6]], jnp.int32)
    pos = jnp.asarray([13, 20], jnp.int32)
    got = flash_decode_paged(q, k_pages, v_pages, tables, pos,
                             page_size=ps, window=window, interpret=True)
    # oracle: gather each slot's lane contiguously, run the 1D kernel
    lanes_k = k_pages[tables].swapaxes(2, 3).reshape(B, -1, KV, Dk)
    lanes_v = v_pages[tables].swapaxes(2, 3).reshape(B, -1, KV, Dv)
    want = flash_decode(q, lanes_k, lanes_v, pos, window=window,
                        block_k=ps, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# placement + pool structure
# ---------------------------------------------------------------------------

def test_paged_pool_shardings_put_pages_on_data():
    """The page dim of a paged pool shards over the data axes exactly like
    the slot dim of a contiguous pool (pages are the unit of cache
    parallelism); structure check on a 1-device mesh."""
    import numpy as onp
    from jax.sharding import Mesh

    cfg, model, params = _setup("llama3.2-1b")
    pool = model.init_paged_cache(3, 8, 16)   # slots=3, ps=8, pages=16
    mesh = Mesh(onp.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    sh = cache_mod.pool_shardings(mesh, pool, 3, num_pages=16)
    for (path, leaf), (_, s) in zip(
            jax.tree_util.tree_leaves_with_path(pool),
            jax.tree_util.tree_leaves_with_path(sh)):
        if cache_mod.is_paged_leaf(path):
            # (layers, pages, KV, page_size, hd): head-major pages
            assert leaf.shape[1] == 16 and leaf.shape[3] == 8
            assert s.spec[1] == "data", f"page dim unsharded: {s.spec}"
        else:
            assert leaf.shape[1] == 3     # ssm lanes keep the slot dim


def test_reset_slot_ssm_zeroes_only_ssm_lanes():
    cfg, model, params = _setup("llama3.2-1b")
    pool = model.init_paged_cache(2, 8, 6)
    pool = jax.tree.map(lambda v: jnp.ones_like(v), pool)
    out = cache_mod.reset_slot_ssm(pool, jnp.int32(0))
    for path, leaf in jax.tree_util.tree_leaves_with_path(out):
        assert bool(jnp.all(leaf == 1.0))   # attn-only family: untouched


def test_copy_page_copies_all_layers_of_paged_leaves():
    cfg, model, params = _setup("llama3.2-1b")
    pool = model.init_paged_cache(2, 4, 6)
    pool = jax.tree_util.tree_map_with_path(
        lambda p, v: v.at[:, 3].set(7.0) if cache_mod.is_paged_leaf(p)
        else v, pool)
    out = cache_mod.copy_page(pool, jnp.int32(1), jnp.int32(3))
    for path, leaf in jax.tree_util.tree_leaves_with_path(out):
        if cache_mod.is_paged_leaf(path):
            assert bool(jnp.all(leaf[:, 1] == 7.0))
            assert bool(jnp.all(leaf[:, 2] == 0.0))
