"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --smoke --steps 50 --exchanger asa --scheme subgd

    # async (EASGD center with fp16-wire elastic exchange):
    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --smoke --algo easgd --tau 4 --alpha 0.5 --exchanger asa16

    # resume a checkpointed run:
    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --smoke --steps 100 --ckpt /tmp/ck --resume /tmp/ck

    # elastic chaos run (quorum sync + injected faults; see repro.fault):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --smoke --algo easgd --tau 4 --workers 4 --quorum 2 \
        --fault-plan 'kill:3@9,straggle:2@13x2,join:3@33'

Runs the reduced (smoke) variant by default on the host CPU devices; the
full config is exercised through the dry-run (-m repro.launch.dryrun).
Every algorithm goes through the same engine (``repro.train.engine``), so
``--ckpt``/``--resume`` work for all of them. ``--quorum``/``--fault-plan``
(async algos only) route through ``repro.fault.elastic.elastic_train``:
dynamic membership, staleness-scaled quorum averaging, deterministic
fault injection.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro import telemetry
from repro.configs import get_config, get_smoke_config
from repro.configs.base import with_attn_impl
from repro.launch.compile_cache import enable_compile_cache
from repro.data.synthetic import LMTokenSource, ImageSource
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.optim import sgd_momentum, adamw, warmup_cosine, constant
from repro.train.engine import TrainPlan
from repro.train.loop import train


def synthetic_batch(cfg, batch_size: int, step: int, seq_len: int = 128):
    """The batch at index ``step`` — deterministic in (cfg, sizes, step),
    so it doubles as the elastic loop's ``batch_fn(step, k)``."""
    if cfg.family == "conv":
        return ImageSource(cfg.image_size, cfg.num_classes).batch(
            batch_size, step)
    b = LMTokenSource(cfg.vocab_size, seq_len).batch(batch_size, step)
    if cfg.family == "encdec":
        b["frames"] = np.random.default_rng(step).normal(
            0, 1, (batch_size, cfg.encoder_seq_len,
                   cfg.d_model)).astype(np.float32)
    if cfg.modality == "vlm":
        b["image_embeds"] = np.zeros(
            (batch_size, cfg.num_image_tokens, cfg.d_model), np.float32)
    return b


def synthetic_batches(cfg, batch_size: int, steps: int, seq_len: int = 128):
    for i in range(steps):
        yield synthetic_batch(cfg, batch_size, i, seq_len)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--optimizer", default="sgd", choices=["sgd", "adamw"])
    ap.add_argument("--algo", default="bsp",
                    choices=["bsp", "easgd", "asgd", "gspmd"],
                    help="training plan: sync BSP, async EASGD/ASGD, or "
                         "GSPMD/FSDP")
    ap.add_argument("--exchanger", default="asa")
    ap.add_argument("--scheme", default="subgd", choices=["subgd", "awagd"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--bucket-bytes", type=int, default=0,
                    help="pack gradient leaves into flat buckets of up to "
                         "this many bytes before exchanging")
    ap.add_argument("--sharded-update", action="store_true",
                    help="ZeRO-1-style RS->update->AG: update only the "
                         "local 1/k shard between the exchange halves")
    ap.add_argument("--overlap", default=None, choices=["buckets"],
                    help="double-buffer the microbatch scan so bucket "
                         "reduce-scatters overlap the next backprop "
                         "(implies --sharded-update)")
    ap.add_argument("--tau", type=int, default=1,
                    help="easgd/asgd averaging period (steps between "
                         "center exchanges)")
    ap.add_argument("--alpha", type=float, default=None,
                    help="easgd elastic coefficient (default 0.5; asgd is "
                         "pinned to 1)")
    ap.add_argument("--mode", default="zero1", choices=["zero1", "ar"],
                    help="gspmd gradient reduction mode")
    ap.add_argument("--workers", type=int, default=None,
                    help="elastic fleet size (default: all visible "
                         "devices); only with --quorum/--fault-plan")
    ap.add_argument("--quorum", type=int, default=None,
                    help="min reporting workers for an averaging round "
                         "(easgd/asgd): below it the round degrades to a "
                         "local step; enables the elastic loop")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC",
                    help="deterministic fault injection, e.g. "
                         "'kill:1@9,straggle:2@5x3,corrupt:0@13' "
                         "(kind:worker@step[xrounds]); enables the "
                         "elastic loop")
    ap.add_argument("--attn-impl", default=None,
                    choices=["auto", "flash", "ref", "blockwise"],
                    help="attention implementation for the train step: "
                         "Pallas flash kernels (fwd + custom-VJP bwd), "
                         "einsum ref oracles, or the blockwise scan "
                         "(default: auto — flash where Pallas compiles)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--resume", default=None, metavar="CKPT",
                    help="restore state/step/rng offset from a checkpoint "
                         "written by the same plan and continue")
    ap.add_argument("--metrics-out", default=None, metavar="JSONL",
                    help="write telemetry metrics (schema'd JSONL: "
                         "per-step time split, loss/lr, examples/s, "
                         "achieved model FLOP/s, exchange bytes-on-wire)")
    ap.add_argument("--trace-out", default=None, metavar="JSON",
                    help="write host-side spans as Chrome-trace/Perfetto "
                         "JSON (load at ui.perfetto.dev)")
    ap.add_argument("--no-profile", action="store_true",
                    help="disable per-program cost attribution "
                         "(profile/* and compile/* gauges); same as "
                         "REPRO_TELEMETRY_PROFILE=0")
    args = ap.parse_args()
    enable_compile_cache()

    if args.metrics_out:
        telemetry.configure(metrics_out=args.metrics_out)
    if args.no_profile:
        telemetry.configure(profile=False)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = with_attn_impl(cfg, args.attn_impl)
    model = build_model(cfg)
    mesh = make_host_mesh()
    jax.set_mesh(mesh)
    opt = (sgd_momentum(weight_decay=0.0) if args.optimizer == "sgd"
           else adamw())
    lr_fn = warmup_cosine(args.lr, 10, args.steps)
    elastic = args.quorum is not None or args.fault_plan is not None
    try:
        plan = TrainPlan(algo=args.algo, exchanger=args.exchanger,
                         scheme=args.scheme, microbatches=args.microbatches,
                         bucket_bytes=args.bucket_bytes,
                         sharded_update=args.sharded_update,
                         overlap=args.overlap, tau=args.tau,
                         alpha=args.alpha, mode=args.mode,
                         quorum=args.quorum if elastic else None)
    except ValueError as e:
        ap.error(str(e))
    if elastic:
        if not plan.is_async:
            ap.error("--quorum/--fault-plan need an async plan "
                     "(--algo easgd|asgd); bsp/gspmd fault tolerance is "
                     "checkpoint restart via --ckpt/--resume")
        from repro.fault.elastic import elastic_train

        def batch_fn(step, k):
            # per-worker batch size held constant: the global batch
            # scales with the live fleet, like a real elastic run
            return synthetic_batch(cfg, args.batch * k, step, args.seq)

        try:
            _, erep = elastic_train(
                model, opt, lr_fn, batch_fn, plan=plan,
                num_workers=args.workers, num_steps=args.steps,
                fault_plan=args.fault_plan, ckpt_path=args.ckpt,
                ckpt_every=args.steps // 4 if args.ckpt else 0,
                resume_from=args.resume)
        except ValueError as e:
            raise SystemExit(str(e))
        if args.metrics_out:
            telemetry.flush(force=True)
            print(f"metrics -> {args.metrics_out}")
        if args.trace_out:
            telemetry.trace.export(args.trace_out)
            print(f"trace -> {args.trace_out}")
        print(f"done: {erep.steps} steps ({plan.algo} elastic), "
              f"fleet {erep.final_workers}, "
              f"rounds {erep.rounds_synced} synced / "
              f"{erep.rounds_skipped_quorum} below-quorum, "
              f"kills {erep.kills}, joins {erep.joins}, "
              f"rebuilds {erep.rebuilds}, payloads dropped "
              f"{erep.payloads_dropped} / corrupt {erep.payloads_corrupt}, "
              f"loss {erep.losses[0]:.4f} -> {erep.losses[-1]:.4f}")
        return
    batches = synthetic_batches(cfg, args.batch, args.steps, args.seq)
    try:
        _, report = train(model, opt, lr_fn, mesh, batches, plan=plan,
                          num_steps=args.steps, ckpt_path=args.ckpt,
                          resume_from=args.resume)
    except ValueError as e:
        if args.resume and "mismatch" in str(e):
            raise SystemExit(f"--resume {args.resume}: {e}")
        raise
    if args.metrics_out:
        # the JSONL sink attached above received periodic + final
        # snapshots from the train loop's flush boundaries
        print(f"metrics -> {args.metrics_out}")
    if args.trace_out:
        telemetry.trace.export(args.trace_out)
        print(f"trace -> {args.trace_out}")
    if not report.losses:
        if args.resume:
            print(f"done: nothing to do (resumed at step {report.steps})")
        else:
            print("done: no steps ran (empty batch source or --steps 0)")
        return
    print(f"done: {report.steps} steps ({plan.algo}), "
          f"{report.examples_per_s:.1f} ex/s total "
          f"({report.steady_examples_per_s:.1f} ex/s steady-state, "
          f"compile+first step {report.compile_time:.2f}s), "
          f"loss {report.losses[0]:.4f} -> {report.losses[-1]:.4f}")


if __name__ == "__main__":
    main()
