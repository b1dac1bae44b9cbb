"""Continuous-batching decode engine.

Design contract (the reason this engine never recompiles):

- **Fixed-slot request pool.** The jitted decode step always sees
  ``(max_slots, 1)`` tokens, a ``(max_slots,)`` position vector, the full
  cache pool, and ``(max_slots,)`` sampling-parameter vectors. Requests
  joining or leaving only change the *values* in those arrays, never a
  shape — the step compiles exactly once per process (asserted in
  ``tests/test_serve.py`` via ``trace_counts``).
- **Per-slot positions.** Every lane decodes at its own depth
  (``decoder_decode_step`` with a (B,) position vector); a freed lane is
  reused immediately by the next queued request.
- **Chunked whole-prompt prefill.** A new request's prompt is written into
  its slot's cache lane by ``model.chunk_prefill`` in ``prefill_chunk``-
  token chunks — one model call per chunk instead of one per token, with
  the LM head applied once. For SSM/hybrid families the chunk is rounded
  up to a multiple of ``cfg.ssm.chunk`` so the SSD block decomposition
  aligns with a single-call prefill bit-for-bit.
- **Paged KV cache (default).** Attention lanes live in a shared pool of
  fixed-size pages routed per slot by a block table (``page_size``,
  ``num_pages``); the host-side :class:`~repro.serve.cache.PageAllocator`
  owns the free list, refcounts and the hashed prefix cache, so admission
  capacity follows what the traffic actually holds, not ``max_slots *
  max_seq`` worst case. Block tables enter the jitted programs as
  same-shaped int32 inputs per dispatch — compile-once still holds under
  churn. ``page_size=0`` selects the contiguous per-slot pool (the parity
  oracle). See DESIGN.md "Paged KV cache & prefix caching".
- **Slot-independent numerics.** Greedy decode of a request is bit-exact
  with ``repro.train.serve.generate`` on the same prompt no matter what
  the other slots are doing (MoE routes drop-free at decode/prefill;
  attention/SSM lanes are batch-independent) — the property the parity
  tests pin per family.
- **SLO guardrails are host-side only.** Deadline shedding, in-flight
  cancellation, the bounded queue, brownout degradation, the stuck-step
  watchdog and drain/restore all live between dispatches — the jitted
  decode/prefill programs are byte-identical with guardrails on or off
  and still compile exactly once (tested). See DESIGN.md "Serve
  robustness" for the deadline math and the brownout ladder.

Sampling is fused into the decode dispatch: greedy/temperature/top-k/top-p
with per-request parameters and per-slot PRNG keys in the same jit
(``fused_sampling=True`` additionally routes the greedy/temperature fast
path through the ``slot_gather`` Pallas kernel).

The engine is synchronous: admission and prefill happen between decode
steps (a prefill stall bounded by ``prefill_chunk``), which keeps the loop
deterministic and testable; see DESIGN.md "Serving engine" for the slot
lifecycle diagram.
"""
from __future__ import annotations

import json
import time
import zlib
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.telemetry import anomaly, profile, trace
from repro.telemetry.registry import Registry
from repro.serve import cache as cache_mod
from repro.serve import sampling as sampling_mod
from repro.serve.scheduler import (AdmissionResult, FINISH_SHED,
                                   REJECTED_QUEUE_FULL, Request,
                                   SamplingParams, SlotScheduler, SlotState)


STATS_WINDOW = 4096   # decode steps of latency history kept for percentiles
EWMA_ALPHA = 0.2      # step-time EWMA (the watchdog/deadline-estimate base)

# brownout ladder thresholds on page-pool occupancy (DESIGN.md "Serve
# robustness"): sustained occupancy >= HI1 enters level 1 (prefix-cache
# registration off), >= HI2 level 2 (+ max_new clamp); dropping below LO
# for the same patience leaves brownout entirely
BROWNOUT_HI1 = 0.85
BROWNOUT_HI2 = 0.95
BROWNOUT_LO = 0.60
BROWNOUT_PATIENCE = 3

SNAPSHOT_SCHEMA = 1


class EngineStats:
    """Serve statistics, backed by a telemetry :class:`Registry`.

    The public surface (``prefill_tokens``, ``decode_tok_s()``,
    ``token_latency_percentiles()``, ...) is unchanged from the old
    dataclass, but every scalar now lives in a registry metric
    (``serve/...`` counters/gauges/histograms) owned by this object — so
    a ``--metrics-out`` dump exports exactly the numbers the stats report,
    with no second bookkeeping path. The registry is private and always
    live (the stats must work with ``REPRO_TELEMETRY=0``); when telemetry
    is enabled the engine attaches it to the process-wide export stream.

    Exact (non-bucketed) p50/p99 readbacks keep the bounded deque windows
    the old implementation used; the registry histograms carry the same
    observations for the JSONL view.

    The guardrail layer adds shed/cancel/deadline-miss/queue-rejection
    counters, queue-depth + brownout gauges, a goodput counter (tokens of
    requests that finished inside their deadline), and ``step_ewma`` —
    the always-live step-time EWMA the admission estimate and the stuck-
    step watchdog read (the telemetry-gated PR 8 ``StreamDetector`` sees
    the same stream when enabled).
    """

    def __init__(self):
        r = self.registry = Registry(label="serve")
        self._prefill_tokens = r.counter("serve/prefill_tokens")
        self._prefill_time = r.counter("serve/prefill_time_s")
        self._decoded_tokens = r.counter("serve/decoded_tokens")
        self._decode_time = r.counter("serve/decode_time_s")
        self._steps = r.counter("serve/decode_steps")
        self._admissions = r.counter("serve/admissions")
        self._evictions = r.counter("serve/evictions")
        self._page_occupancy = r.gauge("serve/page_occupancy")
        self._prefix_hit_rate = r.gauge("serve/prefix_hit_rate")
        self._cow_copies = r.gauge("serve/cow_copies")
        # SLO guardrails
        self._shed = r.counter("serve/shed")
        self._cancelled = r.counter("serve/cancelled")
        self._deadline_miss = r.counter("serve/deadline_miss")
        self._rejected_queue_full = r.counter("serve/rejected_queue_full")
        self._watchdog_stalls = r.counter("serve/watchdog_stalls")
        self._brownout_clamped = r.counter("serve/brownout_clamped")
        self._goodput_tokens = r.counter("serve/goodput_tokens")
        self._queue_depth = r.gauge("serve/queue_depth")
        self._brownout_level = r.gauge("serve/brownout_level")
        self._h_step = r.histogram("serve/step_time_s")
        self._h_ttft = r.histogram("serve/ttft_s")
        self._h_queue = r.histogram("serve/queue_wait_s")
        self.step_ewma: float | None = None   # warm steps only
        # bounded windows (a long-running server must not grow per step):
        # seconds per dispatch / live tokens per dispatch / per-request
        self.step_times: deque = deque(maxlen=STATS_WINDOW)
        self.step_tokens: deque = deque(maxlen=STATS_WINDOW)
        self.ttfts: deque = deque(maxlen=STATS_WINDOW)
        self.queue_waits: deque = deque(maxlen=STATS_WINDOW)

    # -- the recording path (engine-internal) -------------------------------

    def record_prefill(self, tokens: int, dt: float) -> None:
        self._prefill_tokens.inc(tokens)
        self._prefill_time.inc(dt)

    def record_admission(self, queue_wait: float) -> None:
        self._admissions.inc()
        self._h_queue.observe(queue_wait)
        self.queue_waits.append(queue_wait)

    def record_first_token(self, ttft: float) -> None:
        self._h_ttft.observe(ttft)
        self.ttfts.append(ttft)

    def record_decode(self, n_active: int, dt: float) -> None:
        self._steps.inc()
        self._decode_time.inc(dt)
        self._decoded_tokens.inc(n_active)
        self._h_step.observe(dt)
        self.step_times.append(dt)
        self.step_tokens.append(n_active)
        if self.steps > 1:       # step 1 was the compile dispatch
            self.step_ewma = (dt if self.step_ewma is None
                              else EWMA_ALPHA * dt
                              + (1 - EWMA_ALPHA) * self.step_ewma)

    def record_evictions(self, n: int) -> None:
        self._evictions.inc(n)

    def record_finish(self, ev: dict) -> None:
        """Fold one scheduler finish-log event into the SLO counters."""
        if ev["slot"] is not None:
            self._evictions.inc()
        reason = ev["reason"]
        if reason == "cancel":
            self._cancelled.inc()
        elif reason == "shed":
            self._shed.inc()
        if ev["had_deadline"]:
            if reason == "stop" and ev["within_deadline"]:
                self._goodput_tokens.inc(ev["tokens"])
            else:
                self._deadline_miss.inc()
        elif reason == "stop":
            self._goodput_tokens.inc(ev["tokens"])

    def record_rejection(self) -> None:
        self._rejected_queue_full.inc()

    def record_watchdog(self) -> None:
        self._watchdog_stalls.inc()

    def record_brownout_clamp(self) -> None:
        self._brownout_clamped.inc()

    def set_queue_depth(self, n: int) -> None:
        self._queue_depth.set(n)

    def set_brownout_level(self, level: int) -> None:
        self._brownout_level.set(level)

    def set_page_stats(self, occupancy: float, hit_rate: float,
                       cow: int) -> None:
        """Cache-health gauges, refreshed per step. In a paged engine they
        come from the :class:`~repro.serve.cache.PageAllocator` (fraction
        of the physical page pool holding live/cached rows, prefix-cache
        hit rate over page lookups, cumulative copy-on-write page copies);
        the contiguous fallback reports slot-pool occupancy and zeros."""
        self._page_occupancy.set(occupancy)
        self._prefix_hit_rate.set(hit_rate)
        self._cow_copies.set(cow)

    # -- the read surface (public, unchanged + TTFT/queue-wait) -------------

    @property
    def prefill_tokens(self) -> int:
        return self._prefill_tokens.value

    @property
    def prefill_time(self) -> float:
        return self._prefill_time.value

    @property
    def decoded_tokens(self) -> int:
        return self._decoded_tokens.value

    @property
    def decode_time(self) -> float:
        return self._decode_time.value

    @property
    def steps(self) -> int:
        return self._steps.value

    @property
    def admissions(self) -> int:
        return self._admissions.value

    @property
    def evictions(self) -> int:
        return self._evictions.value

    @property
    def page_occupancy(self) -> float:
        return self._page_occupancy.value

    @property
    def prefix_hit_rate(self) -> float:
        return self._prefix_hit_rate.value

    @property
    def cow_copies(self) -> int:
        return int(self._cow_copies.value)

    @property
    def shed(self) -> int:
        return self._shed.value

    @property
    def cancelled(self) -> int:
        return self._cancelled.value

    @property
    def deadline_misses(self) -> int:
        return self._deadline_miss.value

    @property
    def rejected_queue_full(self) -> int:
        return self._rejected_queue_full.value

    @property
    def watchdog_stalls(self) -> int:
        return self._watchdog_stalls.value

    @property
    def brownout_clamped(self) -> int:
        return self._brownout_clamped.value

    @property
    def goodput_tokens(self) -> int:
        return self._goodput_tokens.value

    @property
    def brownout_level(self) -> int:
        return int(self._brownout_level.value)

    def prefill_tok_s(self) -> float:
        return self.prefill_tokens / max(self.prefill_time, 1e-9)

    def decode_tok_s(self) -> float:
        return self.decoded_tokens / max(self.decode_time, 1e-9)

    def goodput_tok_s(self) -> float:
        """Tokens delivered within deadline per second of engine time."""
        return (self.goodput_tokens
                / max(self.decode_time + self.prefill_time, 1e-9))

    def token_latency_percentiles(self, qs=(50, 99)) -> dict:
        """Per-token latency over the stats window: each live token in a
        step experienced that step's wall time."""
        if not self.step_times:
            return {q: 0.0 for q in qs}
        lats = np.repeat(np.fromiter(self.step_times, np.float64),
                         np.fromiter(self.step_tokens, np.int64))
        return {q: float(np.percentile(lats, q)) for q in qs}

    def ttft_percentiles(self, qs=(50, 99)) -> dict:
        """Submit -> first-token latency (queue wait + prefill) over the
        most recent requests."""
        if not self.ttfts:
            return {q: 0.0 for q in qs}
        arr = np.fromiter(self.ttfts, np.float64)
        return {q: float(np.percentile(arr, q)) for q in qs}

    def queue_wait_percentiles(self, qs=(50, 99)) -> dict:
        if not self.queue_waits:
            return {q: 0.0 for q in qs}
        arr = np.fromiter(self.queue_waits, np.float64)
        return {q: float(np.percentile(arr, q)) for q in qs}


class Engine:
    """Continuous-batching inference engine over a fixed slot pool."""

    def __init__(self, model, params, *, max_slots: int = 8,
                 max_seq: int = 256, prefill_chunk: int = 32,
                 mesh=None, fused_sampling: bool = False,
                 unroll: bool = False, attn_impl: str | None = None,
                 page_size: int = 16, num_pages: int = 0,
                 prefix_cache: bool = True,
                 max_queue: int = 0, shed_policy: str = "reject-newest",
                 watchdog_k: float = 6.0, brownout: bool = True,
                 brownout_max_new: int = 16, finished_keep: int = 4096,
                 guardrails: bool = True, clock=None, cost_model=None):
        """``page_size`` > 0 (the default) runs the paged KV cache: slots
        share a physical page pool through block tables, sized by
        ``num_pages`` (0 = worst-case auto: every slot can still reach
        ``max_seq``). ``page_size=0`` keeps the contiguous per-slot pool —
        the parity oracle, and the baseline of the paged pool's density
        test (``tests/test_paged_cache.py``).
        ``prefix_cache`` hands shared page-aligned prompt prefixes to new
        requests by refcount (attention families only; SSM state is not
        reconstructible from cache pages, so it is ignored there).

        SLO guardrails (all host-side; see DESIGN.md "Serve robustness"):
        ``max_queue`` bounds the submit queue (0 = unbounded) with
        ``shed_policy`` deciding who loses on overflow; requests may carry
        ``deadline_ms``/``max_queue_ms`` budgets — hopeless queued
        requests are shed at admission time and in-flight requests past
        deadline are cancelled at step boundaries; ``watchdog_k`` flags a
        decode dispatch slower than k x the step-time EWMA; ``brownout``
        degrades service under sustained page-pool pressure (prefix-cache
        registration off, then ``max_new`` clamped to
        ``brownout_max_new``) before admissions start blocking.
        ``guardrails=False`` disables all enforcement (budgets are still
        recorded, so goodput can be measured post-hoc — the A/B baseline).
        ``clock``/``cost_model`` are the determinism seams
        ``repro.serve.chaos`` drives virtual time through; production
        leaves both at None (wall clock)."""
        cfg = model.cfg
        if cfg.family != "decoder":
            raise ValueError(f"serve engine supports decoder models, "
                             f"got family={cfg.family!r}")
        if attn_impl and cfg.attention is not None:
            # pin the attention implementation for this engine (prefill's
            # q-chunk x cache tiles and decode's split-KV both route
            # through it); attention-less families (pure SSM) ignore it
            from repro.configs.base import with_attn_impl
            from repro.models import build_model
            cfg = with_attn_impl(cfg, attn_impl)
            model = build_model(cfg)
        if cfg.ssm is not None and prefill_chunk % cfg.ssm.chunk:
            # SSD block boundaries must align across chunked calls for the
            # cache state to match a single-call prefill bitwise
            prefill_chunk += cfg.ssm.chunk - prefill_chunk % cfg.ssm.chunk
        if max_seq % prefill_chunk:
            # every chunk writes a full [pos0, pos0+C) window; if the last
            # window could cross max_seq, dynamic_update_slice would clamp
            # pos0 and silently overwrite earlier prompt rows — round the
            # pool up so ceil(S0/C)*C <= max_seq for any admissible S0
            max_seq += prefill_chunk - max_seq % prefill_chunk
        if page_size > 0 and max_seq % page_size:
            # block tables cover whole pages; growing max_seq keeps the
            # prefill-chunk invariant above intact
            max_seq += page_size - max_seq % page_size
        self.model = model
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.prefill_chunk = prefill_chunk
        self.mesh = mesh
        self.fused_sampling = fused_sampling
        self.unroll = unroll
        self.guardrails = guardrails
        self.watchdog_k = watchdog_k
        self.brownout = brownout and guardrails
        self.brownout_max_new = brownout_max_new
        self._brownout_level = 0
        self._hot = [0, 0]       # consecutive steps above HI1 / HI2
        self._cool = 0           # consecutive steps below LO
        self.draining = False
        self._clock = clock or time.perf_counter
        self._cost_model = cost_model
        self.trace_counts = {"prefill": 0, "decode": 0, "sample": 0}

        if mesh is not None:
            from repro.dist.sharding import param_shardings
            params = jax.device_put(params, param_shardings(mesh, params))
        self.params = params

        # pure-SSM families have no sequence-dim leaves to page: fall back
        # to the slot-granular pool automatically
        self.paged = bool(page_size > 0 and model.init_paged_cache is not None
                          and cfg.attention is not None)
        self.page_size = page_size if self.paged else 0
        self.allocator = None
        sched_kw = dict(max_queue=max_queue if guardrails else 0,
                        shed_policy=shed_policy,
                        finished_keep=finished_keep, clock=self._clock)
        if self.paged:
            pps = max_seq // page_size
            if num_pages <= 0:
                # worst case (every slot at max_seq) + null page + one
                # spare so a full-prompt-hit COW never waits
                num_pages = max_slots * pps + 2
            self.num_pages = num_pages
            pool = cache_mod.make_paged_pool(model, max_slots, page_size,
                                             num_pages)
            assert cache_mod.has_paged_leaves(pool)
            self.pool = cache_mod.place_pool(mesh, pool, max_slots,
                                             num_pages)
            self.allocator = cache_mod.PageAllocator(
                num_pages, page_size, max_slots, pps,
                prefix_cache=prefix_cache and cfg.ssm is None)
            self.sched = SlotScheduler(max_slots, max_seq,
                                       allocator=self.allocator, **sched_kw)
        else:
            self.num_pages = 0
            self.pool = cache_mod.place_pool(
                mesh, cache_mod.make_pool(model, max_slots, max_seq),
                max_slots)
            self.sched = SlotScheduler(max_slots, max_seq, **sched_kw)
        # block tables enter every dispatch as one same-shaped int32 array
        # (a (1, 1) dummy keeps the contiguous signature stable)
        self._no_tables = jnp.zeros((1, 1), jnp.int32)
        self.stats = EngineStats()
        if telemetry.enabled():
            telemetry.attach_registry(self.stats.registry)

        # per-slot sampling state (host mirrors; uploaded per dispatch)
        self._temps = np.zeros((max_slots,), np.float32)
        self._top_ks = np.zeros((max_slots,), np.int32)
        self._top_ps = np.ones((max_slots,), np.float32)
        self._keys = jnp.zeros((max_slots, 2), jnp.uint32)

        # first dispatch captures cost_analysis (lower() shares the jit
        # trace cache, so trace_counts still sees exactly one trace) and
        # records the blocked compile time as compile/serve_* gauges
        self._prefill = profile.instrument(
            "serve/prefill_chunk",
            jax.jit(self._prefill_fn, donate_argnums=(1,)))
        self._decode = profile.instrument(
            "serve/decode_step",
            jax.jit(self._decode_fn, donate_argnums=(1,)))
        self._sample_prefill = jax.jit(self._sample_prefill_fn)
        self._prefill_warm = False  # first chunk dispatch is the compile
        self._det_step = anomaly.StreamDetector(
            "serve/step_time", registry=self.stats.registry)

    # -- traced steps -------------------------------------------------------

    def _prefill_fn(self, params, pool, tokens, slot, pos0, valid, tables):
        """One prompt chunk into one slot's cache lane (contiguous) or its
        block-table pages (paged; ``tables`` row ``slot`` routes the
        chunk's scatter/gather)."""
        self.trace_counts["prefill"] += 1
        if self.paged:
            view = cache_mod.paged_view(pool, slot)
            row = jax.lax.dynamic_slice_in_dim(tables, slot, 1, axis=0)
            logits, view = self.model.chunk_prefill(
                params, view, tokens, pos0, valid, seq_len=self.max_seq,
                unroll=self.unroll, block_tables=row,
                page_size=self.page_size)
            return cache_mod.paged_write(pool, slot, view), logits
        view = cache_mod.slot_view(pool, slot)
        logits, view = self.model.chunk_prefill(
            params, view, tokens, pos0, valid, seq_len=self.max_seq,
            unroll=self.unroll)
        return cache_mod.slot_write(pool, slot, view), logits

    def _sample_prefill_fn(self, logits, valid, temp, top_k, top_p, key):
        """Sample the prompt continuation from the last valid prefill row."""
        self.trace_counts["sample"] += 1
        k_use, k_next = jax.random.split(key)
        if self.fused_sampling:
            from repro.kernels.slot_gather import slot_gather_sample
            C = logits.shape[1]
            onehot = (jnp.arange(C) == valid - 1).astype(jnp.float32)[None]
            noise = jax.random.gumbel(k_use, (1, logits.shape[-1]),
                                      jnp.float32)
            greedy, sampled = slot_gather_sample(logits, onehot,
                                                 temp[None], noise)
            tok = jnp.where(temp <= 0.0, greedy[0], sampled[0])
        else:
            row = jax.lax.dynamic_index_in_dim(logits[0], valid - 1, axis=0,
                                               keepdims=False)
            noise = jax.random.gumbel(k_use, (1, row.shape[-1]), jnp.float32)
            tok = sampling_mod.sample_tokens(
                row[None], temp[None], top_k[None], top_p[None], noise)[0]
        return tok, k_next

    def _decode_fn(self, params, pool, tokens, pos, temps, top_ks, top_ps,
                   keys, tables):
        """One decode step for the whole slot pool + fused sampling."""
        self.trace_counts["decode"] += 1
        if self.paged:
            logits, pool = self.model.decode_step(
                params, pool, {"tokens": tokens}, pos, seq_len=self.max_seq,
                unroll=self.unroll, block_tables=tables,
                page_size=self.page_size)
        else:
            logits, pool = self.model.decode_step(
                params, pool, {"tokens": tokens}, pos, seq_len=self.max_seq,
                unroll=self.unroll)
        ks = jax.vmap(jax.random.split)(keys)        # (S, 2, 2)
        k_use, k_next = ks[:, 0], ks[:, 1]
        # all-greedy steps (the default) skip the (S, V) Gumbel draw
        noise = jax.lax.cond(
            jnp.any(temps > 0.0),
            lambda k: sampling_mod.gumbel_noise(k, logits.shape[-1]),
            lambda k: jnp.zeros((keys.shape[0], logits.shape[-1]),
                                jnp.float32), k_use)
        if self.fused_sampling:
            from repro.kernels.slot_gather import slot_gather_sample
            onehot = jnp.ones((logits.shape[0], 1), jnp.float32)
            greedy, sampled = slot_gather_sample(logits, onehot, temps,
                                                 noise)
            tok = jnp.where(temps <= 0.0, greedy, sampled)
        else:
            tok = sampling_mod.sample_tokens(logits[:, 0, :], temps, top_ks,
                                             top_ps, noise)
        return pool, tok, k_next

    # -- host loop ----------------------------------------------------------

    def submit(self, tokens, max_new: int,
               sampling: SamplingParams | None = None,
               eos: int | None = None, *,
               deadline_ms: float | None = None,
               max_queue_ms: float | None = None) -> AdmissionResult:
        """Queue a request. Returns a typed :class:`AdmissionResult` that
        coerces to the request id when accepted; a full bounded queue (or
        a draining engine) rejects with zero state mutated. Malformed or
        never-fits requests still raise ``ValueError``."""
        sampling = sampling or SamplingParams()
        if self.fused_sampling and sampling_mod.needs_full_path(sampling):
            raise ValueError("fused_sampling engine handles greedy/"
                             "temperature only; top-k/top-p need the full "
                             "path (fused_sampling=False)")
        if self.draining:
            self.stats.record_rejection()
            return AdmissionResult(-1, REJECTED_QUEUE_FULL,
                                   "engine draining")
        req = Request(tokens=list(map(int, tokens)), max_new=max_new,
                      sampling=sampling, eos=eos, deadline_ms=deadline_ms,
                      max_queue_ms=max_queue_ms)
        res = self.sched.submit(req)
        if not res:
            self.stats.record_rejection()
        self._account_finished()    # a displaced victim (reject-no-deadline)
        self.stats.set_queue_depth(self.sched.queue_depth)
        return res

    def cancel(self, rid: int) -> bool:
        """Cancel a request wherever it is (queued or in-flight); pages and
        refcounts are released exactly as on a natural finish. Returns
        False for unknown/finished rids."""
        ok = self.sched.cancel(rid)
        if ok:
            self._account_finished()
        return ok

    def _bind_slot(self, slot: int, req: Request) -> None:
        s = req.sampling
        self._temps[slot] = s.temperature
        self._top_ks[slot] = s.top_k
        self._top_ps[slot] = s.top_p
        # seed only — a request's sample stream is a pure function of
        # (params, prompt, seed), independent of submission order
        self._keys = self._keys.at[slot].set(jax.random.PRNGKey(s.seed))

    def _tables(self):
        """The block tables for the next dispatch (same-shaped int32 every
        time — values churn, shapes never do)."""
        if self.allocator is None:
            return self._no_tables
        return jnp.asarray(self.allocator.tables)

    def _make_writable(self, slot: int, lo: int, hi: int) -> None:
        """Pages covering rows [lo, hi) of ``slot`` become privately
        writable before a dispatch writes them: first touch allocates off
        the free list, a prefix-shared page copies-on-write."""
        ps = self.page_size
        for j in range(lo // ps, -(-hi // ps)):
            for dst, src in self.allocator.ensure_writable(slot, j * ps):
                self.pool = cache_mod.copy_page(self.pool, jnp.int32(dst),
                                                jnp.int32(src))

    def _prefill_request(self, slot: int, req: Request) -> None:
        self._bind_slot(slot, req)
        toks = np.asarray(req.tokens, np.int32)
        S0, C = len(req.tokens), self.prefill_chunk
        # prefix-cache hits skip their pages entirely; a full-prompt hit
        # still re-runs the last prompt token for its sampling logits (the
        # write COWs the shared final page, keeping the cached copy clean)
        hit = self.sched.slots[slot].hit_tokens
        start = S0 - 1 if hit >= S0 else hit
        t0 = self._clock()
        with trace.span("serve/prefill", slot=slot, rid=req.rid, tokens=S0,
                        cached=hit):
            if self.cfg.ssm is not None:
                # SSM state/conv carry across prefill chunks by design, so
                # a previous occupant's state must not leak in. Attention
                # lanes need no zeroing: stale rows are causally masked
                # until overwritten in order (paged slots start from the
                # null table anyway) — admission cost is O(d_state), not
                # the old O(max_seq) full-lane wipe.
                self.pool = cache_mod.reset_slot_ssm(self.pool,
                                                     jnp.int32(slot))
            logits = None
            for c in range(start, S0, C):
                sl = toks[c:c + C]
                valid = len(sl)
                if valid < C:
                    sl = np.pad(sl, (0, C - valid))
                if self.paged:
                    self._make_writable(slot, c, c + valid)
                t_c = self._clock()
                self.pool, logits = self._prefill(
                    self.params, self.pool, jnp.asarray(sl[None]),
                    jnp.int32(slot), jnp.int32(c), jnp.int32(valid),
                    self._tables())
                if self._cost_model is not None:
                    self._clock.advance(self._cost_model("prefill_chunk", C))
                if self._prefill_warm:
                    profile.observe("serve/prefill_chunk",
                                    self._clock() - t_c)
                else:
                    self._prefill_warm = True
            if self.allocator is not None and self._brownout_level < 1:
                # brownout level >= 1 stops publishing new prefixes —
                # cache holds are exactly the pressure being shed
                self.allocator.register_prefix(slot, toks)
            tok, k_next = self._sample_prefill(
                logits, jnp.int32(valid),
                jnp.float32(req.sampling.temperature),
                jnp.int32(req.sampling.top_k),
                jnp.float32(req.sampling.top_p),
                self._keys[slot])
            tok = int(tok)
        self._keys = self._keys.at[slot].set(k_next)
        self.stats.record_prefill(S0 - start, self._clock() - t0)
        self.sched.record_first_token(slot, tok)
        self.stats.record_first_token(req.ttft)

    def _account_finished(self) -> None:
        """Drain the scheduler's finish-event stream into the stats —
        evictions (a finish/cancel frees its slot mid-flight), shed/cancel
        counters, deadline misses and goodput. Event-driven, so it
        survives ``pop_finished`` hand-offs and drain/restore cycles (the
        old ``len(finished)`` watermark did not)."""
        while self.sched.finish_log:
            self.stats.record_finish(self.sched.finish_log.popleft())

    # -- SLO guardrails (all host-side, between dispatches) -----------------

    def _estimate_service_s(self, req: Request) -> float:
        """Cheap admission-time completion estimate from measured rates:
        prompt tokens over the prefill rate plus ``max_new`` decode steps
        at the step-time EWMA. Unmeasured components contribute 0 — a
        cold engine never sheds on a blind guess."""
        est = 0.0
        st = self.stats
        if st.prefill_tokens > 0 and st.prefill_time > 0:
            est += len(req.tokens) / st.prefill_tok_s()
        if st.step_ewma is not None:
            est += req.max_new * st.step_ewma
        return est

    def _shed_hopeless(self, now: float) -> None:
        """Shed queued requests whose queue budget is blown or whose
        deadline can no longer be met (anywhere in the queue — an
        impossible head must not block feasible work behind it)."""
        for req in list(self.sched.pending):
            over_queue = (req.max_queue_ms is not None
                          and now - req.t_submit > req.max_queue_ms / 1e3)
            dl = req.deadline_at
            hopeless = (dl is not None
                        and now + self._estimate_service_s(req) > dl)
            if over_queue or hopeless:
                self.sched.shed_queued(req, FINISH_SHED)
                trace.instant("serve/shed", rid=req.rid,
                              why="queue_budget" if over_queue
                              else "deadline_unmeetable")

    def _update_brownout(self, occupancy: float) -> None:
        """Walk the brownout ladder on sustained page-pool pressure:
        level 1 stops prefix-cache registration, level 2 additionally
        clamps new admissions' ``max_new`` — degradation before refusal.
        Hysteresis: entering needs ``BROWNOUT_PATIENCE`` consecutive hot
        steps, leaving needs the same below the low watermark."""
        if occupancy >= BROWNOUT_HI1:
            self._hot[0] += 1
            self._hot[1] = self._hot[1] + 1 if occupancy >= BROWNOUT_HI2 \
                else 0
            self._cool = 0
        else:
            self._hot = [0, 0]
            self._cool = self._cool + 1 if occupancy < BROWNOUT_LO else 0
        if self._hot[1] >= BROWNOUT_PATIENCE:
            level = 2
        elif self._hot[0] >= BROWNOUT_PATIENCE:
            level = max(self._brownout_level, 1)
        elif self._cool >= BROWNOUT_PATIENCE:
            level = 0
        else:
            level = self._brownout_level
        if level != self._brownout_level:
            trace.instant("serve/brownout", level=level,
                          occupancy=round(occupancy, 3))
        self._brownout_level = level
        self.stats.set_brownout_level(level)
        if level >= 2:
            for req in self.sched.pending:
                if req.max_new > self.brownout_max_new:
                    req.max_new = self.brownout_max_new
                    self.stats.record_brownout_clamp()

    def step(self) -> int:
        """Admit + prefill new requests, run one decode dispatch over the
        pool. Returns the number of live tokens produced."""
        now = self._clock()
        if self.guardrails:
            if not self.draining:
                self._shed_hopeless(now)
            for rid in self.sched.cancel_past_deadline(now):
                trace.instant("serve/deadline_cancel", rid=rid)
        self._account_finished()
        if not self.draining:
            for slot, req in self.sched.admit():
                self.stats.record_admission(req.queue_wait)
                self._prefill_request(slot, req)
        self._account_finished()       # max_new=1/eos at first token
        n_active = self.sched.num_active
        self.stats.set_queue_depth(self.sched.queue_depth)
        if self.allocator is not None:
            occ = self.allocator.occupancy()
            self.stats.set_page_stats(occ, self.allocator.hit_rate(),
                                      self.allocator.cow_copies)
            if self.brownout:
                self._update_brownout(occ)
        else:
            self.stats.set_page_stats(n_active / self.max_slots, 0.0, 0)
        if n_active == 0:
            return 0
        if self.paged:
            # the step writes cache row st.pos per live slot: make the
            # covering page private first (idle slots park on the null
            # page and need nothing)
            for slot, st in enumerate(self.sched.slots):
                if st is not None:
                    self._make_writable(slot, st.pos, st.pos + 1)
        tokens = jnp.asarray(self.sched.feed_tokens(),
                             jnp.int32)[:, None]
        pos = jnp.asarray(self.sched.positions(), jnp.int32)
        ewma_prior = self.stats.step_ewma
        t0 = self._clock()
        with trace.span("serve/decode_step", active=n_active):
            self.pool, tok, self._keys = self._decode(
                self.params, self.pool, tokens, pos,
                jnp.asarray(self._temps), jnp.asarray(self._top_ks),
                jnp.asarray(self._top_ps), self._keys, self._tables())
            tok = np.asarray(tok)                     # sync point
        if self._cost_model is not None:
            self._clock.advance(self._cost_model("decode", n_active))
        dt = self._clock() - t0
        if self.stats.steps > 0:     # step 0 is the compile dispatch
            profile.observe("serve/decode_step", dt)
            self._det_step.observe(dt)
            if (self.guardrails and ewma_prior is not None
                    and dt > self.watchdog_k * ewma_prior):
                # the stuck-step watchdog: this dispatch blew far past the
                # EWMA the anomaly detector tracks — flag it (host-side;
                # a wedged device shows up here before anything else)
                self.stats.record_watchdog()
                trace.instant("serve/watchdog_stall", dt=round(dt, 6),
                              ewma=round(ewma_prior, 6), k=self.watchdog_k)
        self.sched.record_step(tok)
        self._account_finished()
        self.stats.record_decode(n_active, dt)
        return n_active

    def run(self) -> dict:
        """Drive to completion; returns {request id: generated tokens}."""
        while self.sched.has_work():
            self.step()
        return self.sched.results()

    # -- graceful drain + crash-safe restore --------------------------------

    def drain(self, path: str | None = None, *,
              max_steps: int | None = None) -> dict:
        """Graceful drain: stop admitting, finish what's in flight (up to
        ``max_steps`` dispatches), snapshot the host-side request state.
        Queued — and any still-unfinished in-flight — requests are
        recorded by prompt; a restored engine re-runs them from scratch,
        which is bit-identical for greedy (and for seeded sampling) decode.
        ``path`` writes the snapshot crash-safely (temp file + fsync +
        atomic rename, crc32-stamped — the PR 7 checkpoint idiom)."""
        self.draining = True
        steps = 0
        while (self.sched.num_active > 0
               and (max_steps is None or steps < max_steps)):
            self.step()
            steps += 1
        self._account_finished()
        snap = self._snapshot()
        if path is not None:
            payload = json.dumps(snap, sort_keys=True).encode()
            from repro.checkpoint.ckpt import _atomic_write
            _atomic_write(path, json.dumps(
                {"schema": SNAPSHOT_SCHEMA, "crc": zlib.crc32(payload),
                 "payload": snap}, sort_keys=True).encode())
        trace.instant("serve/drain", steps=steps,
                      queued=len(snap["queued"]),
                      inflight=len(snap["inflight"]))
        return snap

    def _snapshot(self) -> dict:
        sched = self.sched
        return {
            "rid_next": sched._next_rid,
            "queued": [r.to_state() for r in sched.pending],
            "inflight": [st.req.to_state() for st in sched.slots
                         if st is not None],
            "finished": [{"req": st.req.to_state(),
                          "generated": list(st.generated),
                          "reason": st.req.finish_reason}
                         for st in sched.finished.values()],
            "finished_total": sched.finished_total,
            "finished_dropped": sched.finished_dropped,
        }

    def load_snapshot(self, path_or_snap) -> list:
        """Restore a drained engine's unfinished work into THIS (freshly
        constructed) engine: finished results come back verbatim, queued
        and interrupted in-flight requests are re-queued under their
        original rids (outputs stay keyed identically; deadlines restart
        from now). Returns the re-queued rids. A corrupt snapshot file
        fails loudly (crc32 mismatch)."""
        if isinstance(path_or_snap, str):
            with open(path_or_snap, "rb") as f:
                wrapper = json.load(f)
            payload = json.dumps(wrapper["payload"], sort_keys=True).encode()
            if zlib.crc32(payload) != wrapper["crc"]:
                raise ValueError(
                    f"serve snapshot {path_or_snap!r} failed its crc32 "
                    f"integrity check")
            snap = wrapper["payload"]
        else:
            snap = path_or_snap
        sched = self.sched
        if sched.finished or sched.has_work():
            raise ValueError("load_snapshot needs a fresh engine")
        for ent in snap["finished"]:
            req = Request.from_state(ent["req"])
            req.finish_reason = ent["reason"]
            sched.finished[req.rid] = SlotState(
                req=req, generated=list(ent["generated"]), done=True)
        sched.finished_total = int(snap["finished_total"])
        sched.finished_dropped = int(snap["finished_dropped"])
        sched._next_rid = int(snap["rid_next"])
        requeued = []
        # interrupted in-flight requests re-run from their prompts, ahead
        # of the still-queued tail — the original FIFO order survives
        for ent in snap["inflight"] + snap["queued"]:
            req = Request.from_state(ent)
            sched.resubmit(req)
            requeued.append(req.rid)
        self.stats.set_queue_depth(sched.queue_depth)
        return requeued

    def reset_stats(self) -> None:
        """Zero the timing stats (post-warmup). ``trace_counts`` is *not*
        reset: compile-once is a property of the engine's lifetime."""
        telemetry.detach_registry(self.stats.registry)
        self.stats = EngineStats()
        self._det_step = anomaly.StreamDetector(
            "serve/step_time", registry=self.stats.registry)
        if telemetry.enabled():
            telemetry.attach_registry(self.stats.registry)
