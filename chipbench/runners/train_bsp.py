"""Synchronous data-parallel training through ``repro.train.loop.train``.

One call of ``train()`` with the cell's ``TrainPlan`` over a data mesh of
the cell's chips is the whole run: set-up and window alike go through it,
fed by one iterator (``Feed``) over a pool of batches made on the devices
from the seed.

- Set-up: the process start, the program's build, the first step's
  compile, and the first ``setup_steps`` steps, which the reference
  follows. While the loop asks for batch ``i`` it holds the state after
  ``i`` steps: the feed copies the momentum after one step and the
  parameters after ``setup_steps`` steps to the host there.
- Window: opens when the loop asks for batch ``setup_steps``, once that
  state is ready on the devices; the feed stops yielding ``seconds`` later;
  it closes when ``train()`` has returned, after its final
  ``block_until_ready``. The work counted is what the feed handed over in
  the window.
- With a tracer, the feed records a stretch of ``trace.steps`` steps in
  the window, from one finished state to another.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import sys
import time

import jax
import numpy as np

import compare
import pool
import sgd_reference
from spec import SpecError

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


def _held_state(loop):
    """The state ``train()`` holds while it asks its iterator for a batch."""
    if "state" not in loop.f_locals:
        raise RuntimeError(f"the feed's caller ({loop.f_code.co_name}) holds "
                           f"no 'state'")
    return loop.f_locals["state"]


class CompileCounter:
    """Counts programs compiled, or loaded from the cache, while on."""

    def __init__(self):
        self.on = False
        self.count = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **kw):
        if self.on and name in COMPILE_EVENTS:
            self.count += 1

    def _duration(self, name, secs, **kw):
        self._event(name)


_COMPILES = None


def compile_counter() -> CompileCounter:
    global _COMPILES
    if _COMPILES is None:
        _COMPILES = CompileCounter()
    return _COMPILES


class Feed:
    def __init__(self, batches, setup_steps, seconds, snapshot, tracer=None,
                 trace=None):
        self.batches = batches
        self.setup_steps = setup_steps
        self.seconds = seconds
        self.snapshot = snapshot
        self.tracer = tracer
        self.trace = trace or {}
        self.fed = 0
        self.t_open = None
        self.trace_from = self.trace_to = None

    def __iter__(self):
        return self

    def __next__(self):
        i = self.fed
        loop = sys._getframe(1)
        if i <= self.setup_steps:
            self.snapshot(i, _held_state(loop))
            if i == self.setup_steps:
                jax.block_until_ready(_held_state(loop))
                self.t_open = time.perf_counter()
        elif time.perf_counter() - self.t_open >= self.seconds:
            raise StopIteration
        if self.tracer is not None and self.t_open is not None:
            self._trace(i, loop)
        self.fed += 1
        return self.batches[i % len(self.batches)]

    def _trace(self, i, loop):
        if self.trace_from is None:
            if time.perf_counter() - self.t_open >= self.trace["after_s"]:
                jax.block_until_ready(_held_state(loop))
                self.tracer.start()
                self.trace_from = i
        elif (self.trace_to is None
              and i - self.trace_from == self.trace["steps"]):
            jax.block_until_ready(_held_state(loop))
            self.tracer.stop()
            self.trace_to = i

    def finish_trace(self):
        """Closes a stretch that the window's end cut short (``train()``
        has blocked on its last step by then)."""
        if self.trace_from is not None and self.trace_to is None:
            self.tracer.stop()
            self.trace_to = self.fed

    def window_steps(self) -> int:
        return max(self.fed - self.setup_steps, 0)


def program_config(conf: dict):
    """The registry's configuration with the keys ``reduced`` names set
    from the file; any other difference is an error."""
    from repro.configs import get_config
    cfg = get_config(conf["arch"])
    changes = {}
    for key in ("image_size", "num_classes"):
        if getattr(cfg, key) != conf[key]:
            if key not in conf["reduced"]:
                raise SpecError(f"{conf['name']}: {key} is {conf[key]} in the "
                                f"file, {getattr(cfg, key)} in the registry")
            changes[key] = conf[key]
    return dataclasses.replace(cfg, **changes)


def _check_params(program_init, bench_init, conf):
    """The benchmark's weights must fill the program's parameter tree."""
    key = jax.random.key(0)
    want = jax.eval_shape(program_init, key)
    got = jax.eval_shape(bench_init, key)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            a.shape != b.shape or a.dtype != b.dtype
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise SpecError(f"{conf['name']}: the configuration's parameters do "
                        f"not match the program's")
    count = sum(math.prod(l.shape) for l in jax.tree.leaves(want))
    if count != conf["params"]:
        raise SpecError(f"{conf['name']}: the program has {count} parameters,"
                        f" the configuration {conf['params']}")
    return want


def state_leaves(state, part: str, params_abs, k: int, bucket_bytes: int):
    """Host copies, in tree order, of the momentum (``part="m"``) or the
    float32 parameters (``part="p"``) from the sharded BSP state: flat
    buckets with their master copy, and the small leaves beside them."""
    opt = state["opt"]
    if "master" not in opt:
        raise SpecError("the plan keeps a replicated state; the benchmark "
                        "reads only the sharded layout")
    from repro.core.exchanger import make_rs_plan
    plan = make_rs_plan(params_abs, k, bucket_bytes)
    out = [None] * len(plan.shapes)
    for bi, b in enumerate(plan.buckets):
        flat = np.asarray(opt["buckets"][bi]["m"] if part == "m"
                          else opt["master"][bi])
        off = 0
        for i, size in zip(b.leaves, b.sizes):
            out[i] = flat[off:off + size].reshape(plan.shapes[i])
            off += size
    params = jax.tree.leaves(state["params"])
    for si, i in enumerate(plan.small):
        leaf = opt["small"][si]["m"] if part == "m" else params[i]
        out[i] = np.asarray(leaf).reshape(plan.shapes[i])
    return out


def run_seed(seed: int) -> int:
    """The seed the program and the reference take: 30 bits of ``--seed``,
    so that ``seed + 1`` is a valid key too."""
    return seed % (1 << 30)


def run(cell: dict, conf: dict, ref, seed: int, seconds: float, devices,
        t_start: float, tracer=None) -> dict:
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.optim import constant, sgd_momentum
    from repro.train.engine import TrainPlan
    from repro.train.loop import train
    from jax.sharding import NamedSharding, PartitionSpec as P

    k = cell["chips"]
    s = run_seed(seed)
    plan = TrainPlan(**cell["plan"])
    opt_spec = cell["optimizer"]
    optimizer = sgd_momentum(momentum=opt_spec["momentum"],
                             weight_decay=opt_spec["weight_decay"])
    model = build_model(program_config(conf))
    bench_init = jax.jit(lambda key: ref.init_params(key, conf))
    params_abs = _check_params(model.init, bench_init, conf)
    model = dataclasses.replace(model, init=bench_init)
    mesh = make_mesh((k,), ("data",), devices=devices)
    batches = pool.make_pool(jax.random.key(s), cell["pool_batches"],
                             cell["images_per_chip"] * k, conf["image_size"],
                             conf["num_classes"],
                             NamedSharding(mesh, P("data")))
    setup_steps = cell["setup_steps"]
    snaps = {}

    def snapshot(i, state):
        if i == 1:
            snaps["m1"] = state_leaves(state, "m", params_abs, k,
                                       plan.bucket_bytes)
        if i == setup_steps:
            snaps["p_last"] = state_leaves(state, "p", params_abs, k,
                                           plan.bucket_bytes)
            compiles.count = 0
            compiles.on = True

    compiles = compile_counter()
    feed = Feed(batches, setup_steps, seconds, snapshot, tracer,
                cell.get("trace"))
    with jax.set_mesh(mesh):
        state, report = train(model, optimizer, constant(opt_spec["lr"]),
                              mesh, feed, plan=plan, num_steps=1 << 40,
                              seed=s, log_every=cell["log_every"],
                              print_fn=lambda *a: None)
    t_close = time.perf_counter()
    compiles.on = False
    if tracer is not None:
        feed.finish_trace()
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]
    window_losses = np.asarray(report.losses[setup_steps:], np.float64)
    prog = {"losses": report.losses[:setup_steps], **snaps}
    window_s = t_close - feed.t_open
    setup_s = feed.t_open - t_start
    steps = feed.window_steps()
    traced_steps = (None if feed.trace_from is None
                    else feed.trace_to - feed.trace_from)
    global_batch = cell["images_per_chip"] * k
    del state, report, feed, batches
    gc.collect()

    want = sgd_reference.follow(ref, conf, cell, s, steps=setup_steps,
                                device=devices[0])
    correct, checks = compare.judge(compare.readings(prog, want),
                                    cell["limits"])
    return {
        "correct": correct,
        "checks": checks,
        "attempted": steps,
        "failed": int(np.sum(~np.isfinite(window_losses))),
        "memory_peak_bytes": (max(peak) if all(p is not None for p in peak)
                              else None),
        "compiles_in_window": compiles.count,
        "metrics": {"train_images_per_s": steps * global_batch / window_s / k,
                    "setup_s": setup_s},
        "context": {"cell": cell, "conf": conf, "ref": ref,
                    "global_batch": global_batch,
                    "traced_steps": traced_steps},
    }
