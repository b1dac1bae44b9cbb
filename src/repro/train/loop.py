"""Training loop: engine step + parallel loader + metrics + checkpointing.

Algorithm-agnostic: a :class:`~repro.train.engine.TrainPlan` resolves to an
engine and the loop drives it — bsp, easgd, asgd and gspmd all share this
loop, its checkpoint save/resume, and its loss accounting. The legacy
keyword surface (``exchanger=``, ``scheme=``, ...) still works and simply
builds a bsp plan.

Resume contract: the rng is folded with the *global* step index and the
loop consumes (and discards) the first ``start_step`` batches of the
iterable, so a run restored from a mid-run checkpoint replays exactly the
uninterrupted run (bitwise — tested per algo in ``tests/test_engine.py``).
Callers therefore pass a batch iterable that restarts from step 0. The
skip pays the loader's cost for the discarded batches — cheap for the
synthetic/index-keyed sources here, where producing batch i is O(1); a
loader with expensive staging should defer device transfer until a batch
is actually consumed so the skip stays metadata-only.

Reads one step behind. Losses stay on the device between boundaries: a
log step (every ``log_every``, and the last), or a full buffer of
``_FLUSH_CAP`` losses. Each loss starts its copy to the host as it is
enqueued, and a boundary's read waits until the next step is enqueued,
so the device always has a step queued while the host blocks, and the
next dispatch, its allocations and a late completion hide behind it.
One blocking read per boundary prints the logged loss, sets the gauges
and moves the buffer up to that step into ``TrainReport.losses``; the
last boundary is read after ``train/final_block``. What is printed,
gauged and reported is the same, in number, order and value, as with a
synchronous read: only its time moves, by one step.

Telemetry (host-side only — no op is added to the jitted step):

- one ``train`` step span per iteration (``trace.step``, the profiler's
  ``StepTraceAnnotation``) holding the phase spans ``train/data`` (the
  ``next()`` on the batches), ``train/step`` (the host enqueue of the
  step's programs, its key included), ``train/flush`` (the host blocked on
  the previous boundary's losses, with the next step queued),
  and ``train/compile_block`` / ``train/checkpoint`` where they
  happen; ``train/final_block`` follows the loop. Inside a
  ``jax.profiler`` session these land in the trace's host plane on the
  device events' clock; time in a ``train`` span outside its phase spans
  is the loop's own bookkeeping.
- histograms ``train/data_time_s`` / ``train/step_time_s`` (loop
  iteration, first step excluded — that one is compile) /
  ``train/flush_time_s`` and counters ``train/steps`` /
  ``train/examples`` / ``train/tokens`` / ``exchange/bytes_wire`` (the
  engine's analytic per-step wire traffic).
- counter ``train/queue_drains``: blocking reads the loop makes while no
  later step is enqueued, ``train/compile_block`` and
  ``train/final_block`` aside. It reads 0 in steady state; each in-loop
  checkpoint save counts one (it reads the newest state), and so does
  each ``train/lr`` read where JAX has no CPU backend to compute it on.
- gauges at log boundaries only, read with the boundary's loss:
  ``train/loss``, ``train/lr`` (the schedule computed on the host's CPU
  device), ``train/examples_per_s``, ``train/grad_norm`` when the opt-in
  is on, and ``train/device_mem_bytes`` when the backend exposes
  ``memory_stats()``.

The first step's wall time (compile + first execution) is recorded as
``TrainReport.compile_time`` and excluded from
``TrainReport.steady_examples_per_s`` — ``examples_per_s`` keeps the
total-wall-clock meaning it always had.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import NamedTuple

import jax

from repro import telemetry
from repro.checkpoint.ckpt import restore_for_resume, save_checkpoint
from repro.models.registry import Model
from repro.optim.optimizers import Optimizer
from repro.telemetry import metrics, trace
from repro.train.engine import TrainPlan, build_engine

# when logging is off, losses still move to host in bounded windows (a long
# run must not accumulate one device scalar per step)
_FLUSH_CAP = 100


@dataclass
class TrainReport:
    steps: int = 0
    losses: list = field(default_factory=list)
    wall_time: float = 0.0
    examples_per_s: float = 0.0
    # first-step wall time (compile + first execution) and the rate with
    # that step excluded — the honest steady-state throughput
    compile_time: float = 0.0
    steady_examples_per_s: float = 0.0


class _Boundary(NamedTuple):
    """A step whose losses the loop reads once the next step is enqueued."""
    step: int
    log: bool               # print and set the gauges, besides the move
    n_losses: int           # buffered losses up to and including ``step``
    grad_norm: object       # the step's device grad norm, or None
    n_examples: int         # examples enqueued up to and including ``step``


def _host_lr(lr_fn, step: int, c_drains) -> float:
    """``lr_fn(step)`` computed on the host's CPU device, so that the gauge
    queues no transfer or program behind the accelerator's steps. Without
    a CPU backend it runs on the default device and waits for every step
    enqueued: a queue drain."""
    try:
        host = jax.local_devices(backend="cpu")[:1]
    except RuntimeError:
        host = []
    if not host:
        c_drains.inc()
        return float(lr_fn(step))
    with jax.set_mesh(jax.sharding.Mesh(host, ("host",))):
        return float(lr_fn(step))


def _batch_counts(batch) -> tuple[int, int]:
    """(examples, tokens) in one global batch. Token-shaped leading leaf
    (B, S) counts B*S tokens; image/label-only batches count examples."""
    first = jax.tree.leaves(batch)[0]
    b = int(first.shape[0])
    toks = batch.get("tokens") if isinstance(batch, dict) else None
    if toks is not None and len(toks.shape) >= 2:
        return b, int(toks.shape[0]) * int(toks.shape[1])
    return b, b


def _device_mem_bytes():
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:  # noqa: BLE001 — backend without memory introspection
        return None
    if not stats:
        return None
    return stats.get("bytes_in_use")


def train(model: Model, optimizer: Optimizer, lr_fn, mesh, batches, *,
          plan: TrainPlan | None = None, algo: str = "bsp",
          exchanger: str = "asa", scheme: str = "subgd",
          data_axes=("data",), num_steps: int = 100, seed: int = 0,
          log_every: int = 10, ckpt_path: str | None = None,
          ckpt_every: int = 0, ckpt_keep: int = 3,
          resume_from: str | None = None,
          state=None, sum_fn=None, microbatches: int = 1,
          bucket_bytes: int = 0, sharded_update: bool = False,
          overlap: str | None = None, tau: int = 1,
          alpha: float | None = None, mode: str = "zero1",
          print_fn=print) -> tuple[dict, TrainReport]:
    """``batches``: iterable of device-ready batches (e.g. ParallelLoader).

    Pass ``plan`` to pick the algorithm explicitly; the remaining algo
    keywords (``exchanger``/``scheme``/``tau``/``alpha``/``mode``/...) are
    the flat legacy surface and are folded into a plan when ``plan`` is
    None. ``resume_from`` restores a checkpoint written by the same plan
    (state + step + rng fold offset) and continues to ``num_steps``."""
    if plan is None:
        plan = TrainPlan(algo=algo, exchanger=exchanger, scheme=scheme,
                         data_axes=tuple(data_axes),
                         microbatches=microbatches,
                         bucket_bytes=bucket_bytes,
                         sharded_update=sharded_update, overlap=overlap,
                         tau=tau, alpha=alpha, mode=mode)
    engine = build_engine(plan, model, optimizer, lr_fn, mesh,
                          sum_fn=sum_fn)
    if state is None:
        state = engine.init_state(jax.random.key(seed))
    start_step = 0
    if resume_from:
        # restore onto the engine-initialized state: structure, dtypes AND
        # placement (sharded opt-state shards land back on their ranks)
        state, start_step = restore_for_resume(resume_from, state,
                                               expect_algo=plan.algo)
    rng = jax.random.key(seed + 1)

    # -- telemetry handles (all no-ops when REPRO_TELEMETRY=0) --------------
    c_steps = metrics.counter("train/steps")
    c_examples = metrics.counter("train/examples")
    c_tokens = metrics.counter("train/tokens")
    h_data = metrics.histogram("train/data_time_s")
    h_step = metrics.histogram("train/step_time_s")
    h_flush = metrics.histogram("train/flush_time_s")
    g_loss = metrics.gauge("train/loss")
    g_lr = metrics.gauge("train/lr")
    g_exps = metrics.gauge("train/examples_per_s")
    c_drains = metrics.counter("train/queue_drains")
    metrics.info("train/plan", algo=plan.algo, exchanger=plan.exchanger,
                 scheme=plan.scheme, arch=getattr(model.cfg, "name", ""))
    wire = engine.wire
    c_wire = metrics.counter("exchange/bytes_wire")
    if wire:
        metrics.info("exchange/config",
                     **{k: wire[k] for k in ("strategy", "wire_dtype",
                                             "ag_dtype", "k", "num_buckets",
                                             "sync_every")})
        metrics.gauge("exchange/bytes_per_step").set(wire["bytes_per_step"])

    report = TrainReport()
    report.steps = start_step
    n_examples = 0
    t0 = time.perf_counter()
    it = iter(batches)
    try:
        for _ in range(start_step):   # batches the checkpointed run saw
            next(it)
    except StopIteration:
        return state, report
    # losses stay on device between boundaries, each copied to the host
    # as it is enqueued; a boundary's read waits until the next step is
    # enqueued (see the module docstring)
    device_losses = []
    pending = None
    saved_at = None
    t_steady0 = t0
    steady_base_ex = 0

    def read(b: _Boundary, wait):
        """Moves boundary ``b``'s losses to the report and logs it; ``wait``
        spans the blocking read."""
        with wait:
            t_f = time.perf_counter()
            losses = [float(l) for l in device_losses[:b.n_losses]]
            h_flush.observe(time.perf_counter() - t_f)
        del device_losses[:b.n_losses]
        report.losses.extend(losses)
        if not b.log:
            return
        print_fn(f"step {b.step:5d}  loss {losses[-1]:.4f}")
        g_loss.set(losses[-1])
        g_lr.set(_host_lr(lr_fn, b.step, c_drains))
        if b.grad_norm is not None:
            metrics.gauge("train/grad_norm").set(float(b.grad_norm))
        steady_t = time.perf_counter() - t_steady0
        if steady_t > 0 and b.n_examples > steady_base_ex:
            g_exps.set((b.n_examples - steady_base_ex) / steady_t)
        mem = _device_mem_bytes()
        if mem is not None:
            metrics.gauge("train/device_mem_bytes").set(mem)
        telemetry.flush(force=False)

    for i in range(start_step, num_steps):
        with trace.step("train", i):
            t_iter0 = time.perf_counter()
            with trace.span("train/data"):
                try:
                    batch = next(it)
                except StopIteration:
                    break
            t_step0 = time.perf_counter()
            with trace.span("train/step", step=i):
                state, step_metrics = engine.step(
                    state, batch, jax.random.fold_in(rng, i), step_idx=i)
            loss = step_metrics["loss"]
            loss.copy_to_host_async()
            device_losses.append(loss)
            grad_norm = step_metrics.get("grad_norm")
            if grad_norm is not None:
                grad_norm.copy_to_host_async()
            b_ex, b_tok = _batch_counts(batch)
            n_examples += b_ex
            first_step = i == start_step
            if first_step:
                # the first step carries compilation: block so its cost
                # lands here (one extra sync for the whole run) and keep it
                # out of the steady-state histograms/rates
                with trace.span("train/compile_block"):
                    jax.block_until_ready(loss)
                report.compile_time = time.perf_counter() - t_step0
                t_steady0 = time.perf_counter()
                steady_base_ex = n_examples
            c_steps.inc()
            c_examples.inc(b_ex)
            c_tokens.inc(b_tok)
            if wire:
                c_wire.inc(wire["bytes_per_step"])
            h_data.observe(t_step0 - t_iter0)
            if not first_step:
                h_step.observe(time.perf_counter() - t_iter0)
            if pending is not None:
                # step i is queued behind the one read
                read(pending, trace.span("train/flush", step=pending.step))
                pending = None
            log = bool(log_every) and (i % log_every == 0
                                       or i == num_steps - 1)
            if log or len(device_losses) >= _FLUSH_CAP:
                pending = _Boundary(i, log, len(device_losses), grad_norm,
                                    n_examples)
            if ckpt_path and ckpt_every and (i + 1) % ckpt_every == 0:
                c_drains.inc()       # the save reads the newest state
                with trace.span("train/checkpoint", step=i + 1):
                    save_checkpoint(ckpt_path, state, step=i + 1,
                                    algo=plan.algo, keep=ckpt_keep)
                saved_at = i + 1
            report.steps = i + 1
    with trace.span("train/final_block"):
        jax.block_until_ready(state)
    report.wall_time = time.perf_counter() - t0
    if pending is not None:      # the device is done: nothing to wait on
        read(pending, contextlib.nullcontext())
    report.losses.extend(float(l) for l in device_losses)
    report.examples_per_s = n_examples / max(report.wall_time, 1e-9)
    steady_t = time.perf_counter() - t_steady0
    if n_examples > steady_base_ex and steady_t > 0:
        report.steady_examples_per_s = ((n_examples - steady_base_ex)
                                        / steady_t)
    if ckpt_path and report.steps != saved_at:
        # the in-loop save already covered the final step when ckpt_every
        # divides it — don't write the same step twice
        save_checkpoint(ckpt_path, state, step=report.steps, algo=plan.algo,
                        keep=ckpt_keep)
    telemetry.flush(force=True)
    return state, report
