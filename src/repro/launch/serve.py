"""Serving launcher: continuous-batching engine over a (smoke) model.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --num-requests 16 --max-slots 4 --prefill-chunk 16 \
        --temperature 0.8 --top-k 40 --top-p 0.95

``--reference`` runs the old static-batch greedy path
(``train.serve.generate``) instead — the engine's parity oracle.

SLO guardrails (DESIGN.md "Serve robustness"): ``--deadline-ms`` stamps a
per-request budget (hopeless requests are shed, in-flight ones past
deadline cancelled), ``--max-queue``/``--shed-policy`` bound the submit
queue, ``--drain-on-sigterm PATH`` installs a SIGTERM handler that drains
gracefully and snapshots unfinished work (restartable via the same path),
and ``--fault-plan`` hands the run to the deterministic chaos loop
(``repro.serve.chaos``) instead of the plain workload.
"""
from __future__ import annotations

import argparse
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.configs import get_smoke_config, get_config
from repro.configs.base import with_attn_impl
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serve import Engine, SamplingParams
from repro.train.serve import generate


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--num-requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=8,
                    help="mean prompt length (mixed workload)")
    ap.add_argument("--max-new", type=int, default=16,
                    help="mean output length (mixed workload)")
    ap.add_argument("--max-slots", type=int, default=4,
                    help="decode lanes in the fixed slot pool")
    ap.add_argument("--max-seq", type=int, default=0,
                    help="cache rows per slot (0: auto from workload)")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="prompt tokens prefilled per model call")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV cache page size in tokens (0: contiguous "
                         "per-slot lanes — the legacy/oracle layout)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="physical pages in the shared KV pool (0: "
                         "worst-case auto — every slot can reach max_seq; "
                         "smaller values oversubscribe HBM and gate "
                         "admission on actual usage)")
    ap.add_argument("--prefix-cache", dest="prefix_cache",
                    action="store_true", default=True,
                    help="hash page-aligned prompt prefixes and serve "
                         "repeats from shared pages (copy-on-write; "
                         "attention families only)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fused-sampling", action="store_true",
                    help="slot_gather Pallas kernel fast path "
                         "(greedy/temperature only)")
    ap.add_argument("--attn-impl", default=None,
                    choices=["auto", "flash", "ref", "blockwise"],
                    help="attention implementation for prefill/decode: "
                         "Pallas flash kernels, einsum ref oracles, or "
                         "the blockwise scan (default: auto — flash "
                         "where Pallas compiles)")
    ap.add_argument("--reference", action="store_true",
                    help="static-batch greedy generate() instead of the "
                         "engine")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request SLO budget: shed if unmeetable in "
                         "queue, cancel in-flight past deadline")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bound the submit queue (0: unbounded); full "
                         "queues reject with REJECTED_QUEUE_FULL")
    ap.add_argument("--shed-policy", default="reject-newest",
                    choices=["reject-newest", "reject-no-deadline"],
                    help="who loses when the bounded queue overflows")
    ap.add_argument("--drain-on-sigterm", default=None, metavar="SNAP",
                    help="SIGTERM drains gracefully and snapshots "
                         "unfinished work to SNAP (atomic+crc32); if SNAP "
                         "exists at startup, queued work resumes from it")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC",
                    help="run the deterministic serve chaos loop under "
                         "this seeded FaultPlan instead of the plain "
                         "workload (kinds: qflood/stall/cancel/pagepress, "
                         "grammar kind:magnitude@step[xD])")
    ap.add_argument("--metrics-out", default=None, metavar="JSONL",
                    help="write telemetry metrics (schema'd JSONL: "
                         "prefill/decode throughput, TTFT, queue wait, "
                         "page-pool occupancy, prefix hit-rate, COW and "
                         "admission/eviction counters)")
    ap.add_argument("--trace-out", default=None, metavar="JSON",
                    help="write host-side spans (per-request lifecycle + "
                         "decode dispatches) as Chrome-trace/Perfetto JSON")
    ap.add_argument("--no-profile", action="store_true",
                    help="disable per-program cost attribution "
                         "(profile/* and compile/* gauges); same as "
                         "REPRO_TELEMETRY_PROFILE=0")
    args = ap.parse_args()
    enable_compile_cache()
    if args.no_profile:
        telemetry.configure(profile=False)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.family != "decoder":
        raise SystemExit(f"{cfg.family!r} models have no serve path")
    cfg = with_attn_impl(cfg, args.attn_impl)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    rng = np.random.RandomState(args.seed)
    lens = np.maximum(1, rng.poisson(args.prompt_len, args.num_requests))
    news = np.maximum(1, rng.poisson(args.max_new, args.num_requests))
    prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in lens]

    if args.reference:
        t0 = time.perf_counter()
        done = 0
        for p, m in zip(prompts, news):
            out = generate(model, params, jnp.asarray([p], jnp.int32),
                           max_new=int(m), seq_len=len(p) + int(m))
            jax.block_until_ready(out)
            done += int(m)
        dt = time.perf_counter() - t0
        print(f"reference generate: {done} tokens in {dt:.2f}s "
              f"({done / dt:.1f} tok/s)")
        return

    if args.fault_plan:
        from repro.serve.chaos import main as chaos_main
        chaos_main(["--arch", args.arch, "--fault-plan", args.fault_plan,
                    "--seed", str(args.seed),
                    "--requests", str(args.num_requests),
                    "--max-slots", str(args.max_slots),
                    "--page-size", str(args.page_size or 8),
                    "--num-pages", str(args.num_pages),
                    "--max-queue", str(args.max_queue or 16),
                    "--shed-policy", args.shed_policy, "--replay"]
                   + (["--metrics-out", args.metrics_out]
                      if args.metrics_out else [])
                   + (["--trace-out", args.trace_out]
                      if args.trace_out else []))
        return

    max_seq = args.max_seq or int((lens + news).max())
    eng = Engine(model, params, max_slots=args.max_slots, max_seq=max_seq,
                 prefill_chunk=args.prefill_chunk,
                 fused_sampling=args.fused_sampling,
                 page_size=args.page_size, num_pages=args.num_pages,
                 prefix_cache=args.prefix_cache,
                 max_queue=args.max_queue, shed_policy=args.shed_policy)
    if args.drain_on_sigterm:
        import os

        def _drain(signum, frame):
            snap = eng.drain(args.drain_on_sigterm)
            print(f"SIGTERM: drained to {args.drain_on_sigterm} "
                  f"({len(snap['queued']) + len(snap['inflight'])} "
                  f"requests snapshotted)")
            raise SystemExit(0)

        signal.signal(signal.SIGTERM, _drain)
        if os.path.exists(args.drain_on_sigterm):
            resumed = eng.load_snapshot(args.drain_on_sigterm)
            print(f"resumed {len(resumed)} queued requests from "
                  f"{args.drain_on_sigterm}")
    sp = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                        top_p=args.top_p, seed=args.seed)
    rids = [eng.submit(p, int(m), sp, deadline_ms=args.deadline_ms)
            for p, m in zip(prompts, news)]
    rids = [r for r in rids if r]          # bounded queue may refuse some
    t0 = time.perf_counter()
    results = eng.run()
    dt = time.perf_counter() - t0
    st = eng.stats
    lat = st.token_latency_percentiles()
    ttft = st.ttft_percentiles()
    qw = st.queue_wait_percentiles()
    print(f"served {len(rids)} requests / {st.decoded_tokens} tokens "
          f"in {dt:.2f}s on {args.max_slots} slots "
          f"(prefill {st.prefill_tok_s():.1f} tok/s, "
          f"decode {st.decode_tok_s():.1f} tok/s, "
          f"p50/p99 token latency {lat[50] * 1e3:.1f}/{lat[99] * 1e3:.1f} ms)")
    print(f"ttft p50/p99 {ttft[50] * 1e3:.1f}/{ttft[99] * 1e3:.1f} ms "
          f"(queue wait p50/p99 {qw[50] * 1e3:.1f}/{qw[99] * 1e3:.1f} ms, "
          f"{st.admissions} admitted / {st.evictions} evicted)")
    print(f"decode compiled {eng.trace_counts['decode']}x across "
          f"{st.steps} steps")
    if args.deadline_ms is not None or args.max_queue:
        print(f"guardrails: {st.goodput_tokens} tokens within deadline "
              f"(goodput {st.goodput_tok_s():.1f} tok/s), {st.shed} shed, "
              f"{st.cancelled} cancelled, {st.deadline_misses} deadline "
              f"misses, {st.rejected_queue_full} queue-rejected, "
              f"{st.watchdog_stalls} watchdog stalls, brownout clamped "
              f"{st.brownout_clamped}")
    if eng.allocator is not None:
        al = eng.allocator
        print(f"paged cache: {eng.num_pages} pages x {eng.page_size} tok, "
              f"final occupancy {al.occupancy():.2f}, "
              f"prefix hit-rate {al.hit_rate():.2f} "
              f"({al.hit_tokens} tok cached), {al.cow_copies} COW copies, "
              f"{al.evictions} cache evictions")
    if rids:
        print("sample:", results[int(rids[0])][:16])
    if args.metrics_out:
        telemetry.dump_metrics(args.metrics_out)
        print(f"metrics -> {args.metrics_out}")
    if args.trace_out:
        telemetry.trace.export(args.trace_out)
        print(f"trace -> {args.trace_out}")


if __name__ == "__main__":
    main()
