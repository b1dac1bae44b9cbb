"""Bring-up smoke run on a TPU: the paper's BSP training path and the serve
engine, each at published widths, through the entry points a user calls.

    python chip_smoke.py               # one chip: AlexNet BSP, then serving
    python chip_smoke.py --four-chips  # four chips: AlexNet BSP asa16 vs ar

One chip runs two phases, one after the other, in this one process:

- train: AlexNet (227 px, 1000 classes, 128 images per worker) through
  ``repro.train.loop.train`` with the sync plan (asa16 exchange, 4
  microbatches, overlapped buckets), so the fused reduce-scatter update
  kernel runs compiled; every loss must be finite;
- serve: llama3.2-1b at full width through ``repro.serve.Engine`` with its
  defaults (paged cache, prefix cache, flash attention); every request
  must finish, decode must compile once, and the flash path's last-position
  logits must match the einsum reference within a bf16 tolerance.

``--four-chips`` runs only AlexNet BSP over a (4,) data mesh, once with
asa16 plus overlapped buckets and once with ar, on the same batches and
rng, and checks the loss trajectories, the batch placement, the replicated
state and the step's collectives.

Weights and data are random, made from fixed seeds. The script exits
nonzero, and prints no result line, when JAX finds no TPU, when a Pallas
kernel would run in interpret mode, or when any check fails. The last line
of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import re
import sys
import time
from pathlib import Path

PER_WORKER_BATCH = 128          # the paper's AlexNet batch per worker
TRAIN_STEPS = 6                 # the first compiles; 5 steady steps
SERVE_REQUESTS = 8
PROMPT_LEN = 128
NEW_TOKENS = 16
# bf16 weights and activations through 16 layers: the flash path (fp32
# online softmax) and the einsum reference (bf16 softmax) may differ by a
# few bf16 ulps of the largest logit
LOGITS_RTOL = 3e-2
# the fp16-wire exchange against the fp32 one (tests/test_rs_update.py)
ASA16_RTOL = 3e-3


class SmokeFailure(Exception):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _bsp_train(devices, plan, batches, cfg, steps, *, compiled_hlo=False):
    """Train AlexNet with ``plan`` over a data mesh on ``devices``; returns
    (state, report, the step's HLO text: compiled, or lowered StableHLO)."""
    import jax

    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.optim import constant, sgd_momentum
    from repro.train.engine import build_engine
    from repro.train.loop import train

    model = build_model(cfg)
    mesh = make_mesh((len(devices),), ("data",), devices=devices)
    opt, lr = sgd_momentum(), constant(0.01)
    with jax.set_mesh(mesh):
        state, report = train(model, opt, lr, mesh, batches, plan=plan,
                              num_steps=steps, log_every=0)
        jstep = build_engine(plan, model, opt, lr, mesh).jitted["train/step"]
        lowered = jstep.lower(state, batches[0], jax.random.key(0))
        hlo = (lowered.compile() if compiled_hlo else lowered).as_text()
    return state, report, hlo


def _alexnet_batches(cfg, global_batch, steps, sharding=None):
    import jax

    from repro.data.synthetic import ImageSource
    src = ImageSource(cfg.image_size, cfg.num_classes)
    out = [src.batch(global_batch, i) for i in range(steps)]
    if sharding is not None:
        out = [jax.device_put(b, sharding) for b in out]
    return out


def _alexnet_config():
    from repro.configs import get_config
    cfg = get_config("alexnet")
    _check(cfg.image_size == 227 and cfg.num_classes == 1000,
           f"alexnet is not at published width: {cfg.image_size} px, "
           f"{cfg.num_classes} classes")
    return cfg


def train_phase(dev, steps: int = TRAIN_STEPS) -> None:
    from repro.train.engine import TrainPlan

    cfg = _alexnet_config()
    plan = TrainPlan(algo="bsp", exchanger="asa16", microbatches=4,
                     overlap="buckets")
    t0 = time.perf_counter()
    batches = _alexnet_batches(cfg, PER_WORKER_BATCH, steps)
    print(f"train: alexnet {cfg.image_size}px/{cfg.num_classes} classes, "
          f"batch {PER_WORKER_BATCH}, plan bsp/asa16/microbatches=4/"
          f"overlap=buckets, {steps} steps (data made in "
          f"{time.perf_counter() - t0:.2f} s)")
    state, report, hlo = _bsp_train([dev], plan, batches, cfg, steps)
    losses = [float(l) for l in report.losses]
    print(f"train: losses {' '.join(f'{l:.4f}' for l in losses)}")
    _check(len(losses) == steps, f"{len(losses)} losses for {steps} steps")
    _check(all(math.isfinite(l) for l in losses), "non-finite train loss")
    step_s = PER_WORKER_BATCH / report.steady_examples_per_s
    print(f"train: compile+first step {report.compile_time:.2f} s, steady "
          f"step {step_s * 1e3:.1f} ms ({report.steady_examples_per_s:.1f} "
          f"images/s over {steps - 1} steps)")
    has_kernel = "tpu_custom_call" in hlo
    print(f"train: step StableHLO has tpu_custom_call (fused_rs_update): "
          f"{has_kernel}")
    _check(has_kernel, "train step runs no compiled Pallas kernel")
    print(f"train: peak_bytes_in_use {_peak_bytes(dev)}")


def _last_logits(cfg, params, prompt, attn_impl, page_size):
    """Last-position logits of ``prompt`` through the paged serve path:
    prefill all but the last token, then one decode step."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import with_attn_impl
    from repro.models import build_model

    model = build_model(with_attn_impl(cfg, attn_impl))
    n = len(prompt)
    pages = -(-n // page_size)
    seq = pages * page_size
    tables = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    cache = model.init_paged_cache(1, page_size, pages + 1)

    @jax.jit
    def run(params, cache, head, last):
        _, cache = model.chunk_prefill(params, cache, head, 0, n - 1,
                                       seq_len=seq, block_tables=tables,
                                       page_size=page_size)
        logits, _ = model.decode_step(params, cache, {"tokens": last},
                                      jnp.asarray([n - 1], jnp.int32),
                                      seq_len=seq, block_tables=tables,
                                      page_size=page_size)
        return logits[0, -1].astype(jnp.float32)

    head = jnp.asarray([prompt[:-1]], jnp.int32)
    last = jnp.asarray([[prompt[-1]]], jnp.int32)
    return run(params, cache, head, last)


def serve_phase(dev) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models import build_model
    from repro.models.attention import resolve_attn_impl
    from repro.serve import Engine, SamplingParams
    from repro.telemetry import profile

    cfg = get_config("llama3.2-1b")
    a = cfg.attention
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.jit(model.init)(jax.random.key(0))
    jax.block_until_ready(params)
    print(f"serve: llama3.2-1b d_model {cfg.d_model}, {cfg.num_layers} "
          f"layers, {a.num_heads}/{a.num_kv_heads} heads x {a.head_dim}, "
          f"vocab {cfg.vocab_size}, attention {resolve_attn_impl(a)} "
          f"(params made in {time.perf_counter() - t0:.2f} s)")
    _check(resolve_attn_impl(a) == "flash", "attention is not flash on TPU")

    rng = np.random.RandomState(0)
    lens = rng.randint(PROMPT_LEN - 8, PROMPT_LEN + 9, SERVE_REQUESTS)
    prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in lens]
    eng = Engine(model, params)
    rids = [eng.submit(p, NEW_TOKENS, SamplingParams()) for p in prompts]
    t0 = time.perf_counter()
    results = eng.run()
    wall = time.perf_counter() - t0
    done = [len(results.get(r, [])) for r in rids]
    st = eng.stats
    print(f"serve: {len(rids)} requests, prompts {min(lens)}-{max(lens)} "
          f"tokens, {sum(done)} tokens out in {wall:.2f} s "
          f"({sum(done) / wall:.1f} tok/s); engine stats: decode "
          f"{st.decode_tok_s():.1f} tok/s, prefill {st.prefill_tok_s():.1f} "
          f"tok/s (both with compiles included)")
    _check(all(d == NEW_TOKENS for d in done),
           f"unfinished requests: tokens out per request {done}")
    for name in ("serve/prefill_chunk", "serve/decode_step"):
        p = profile.get(name)
        if p is not None:
            print(f"serve: {name} compile+first call {p.compile_time_s:.2f}"
                  f" s, steady {p.mean_time_s * 1e3:.2f} ms over "
                  f"{p.calls} calls")
    print(f"serve: decode compiled {eng.trace_counts['decode']}x, prefill "
          f"{eng.trace_counts['prefill']}x; page size {eng.page_size}, "
          f"{eng.num_pages} pages, {eng.max_slots} slots")
    _check(eng.trace_counts["decode"] == 1, "decode compiled more than once")

    slots = eng.max_slots
    txt = eng._decode.jitted.lower(
        eng.params, eng.pool, jnp.zeros((slots, 1), jnp.int32),
        jnp.zeros((slots,), jnp.int32), jnp.asarray(eng._temps),
        jnp.asarray(eng._top_ks), jnp.asarray(eng._top_ps), eng._keys,
        eng._tables()).as_text()
    has_kernel = "tpu_custom_call" in txt
    print(f"serve: decode StableHLO has tpu_custom_call (flash_decode_paged): "
          f"{has_kernel}")
    _check(has_kernel, "decode runs no compiled Pallas kernel")

    flash = _last_logits(cfg, params, prompts[0], "flash", eng.page_size)
    ref = _last_logits(cfg, params, prompts[0], "ref", eng.page_size)
    err = float(jnp.max(jnp.abs(flash - ref)))
    scale = float(jnp.max(jnp.abs(ref)))
    same_top = int(jnp.argmax(flash)) == int(jnp.argmax(ref))
    print(f"serve: last-position logits flash vs ref: max |diff| "
          f"{err:.4g}, max |ref| {scale:.4g}, tolerance {LOGITS_RTOL} x "
          f"max |ref|, same argmax {same_top}")
    _check(bool(jnp.all(jnp.isfinite(flash))), "non-finite flash logits")
    _check(err <= LOGITS_RTOL * scale, "flash logits differ from ref")
    print(f"serve: peak_bytes_in_use {_peak_bytes(dev)}")


def _group_sizes(hlo: str, op: str) -> list:
    """Replica-group sizes of every ``op`` in compiled HLO text."""
    sizes = []
    for line in hlo.splitlines():
        if not re.search(rf"\s{op}(-start)?\(", line):
            continue
        g = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
        if g:
            sizes.append(len(g.group(1).split(",")))
            continue
        g = re.search(r"replica_groups=\[\d+,(\d+)\]", line)
        if g:
            sizes.append(int(g.group(1)))
    return sizes


def four_chip_phase(devices, steps: int = TRAIN_STEPS) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.train.engine import TrainPlan

    k = len(devices)
    cfg = _alexnet_config()
    mesh = make_mesh((k,), ("data",), devices=devices)
    batches = _alexnet_batches(cfg, PER_WORKER_BATCH * k, steps,
                               NamedSharding(mesh, P("data")))
    host0 = jax.device_get(batches[0]["images"])
    shards = sorted(batches[0]["images"].addressable_shards,
                    key=lambda s: s.index[0].start)
    _check(len({s.device for s in shards}) == k
           and all(s.data.shape[0] == PER_WORKER_BATCH for s in shards),
           "batch is not split one shard per device")
    for i, s in enumerate(shards):
        lo = i * PER_WORKER_BATCH
        _check(np.array_equal(np.asarray(s.data),
                              host0[lo:lo + PER_WORKER_BATCH]),
               f"device {s.device} does not hold batch shard {i}")
    print(f"four-chip: {k} devices, each holds its own "
          f"{PER_WORKER_BATCH}-image batch shard")

    runs = {}
    for name, plan in (
            ("asa16", TrainPlan(algo="bsp", exchanger="asa16",
                                microbatches=4, overlap="buckets")),
            ("ar", TrainPlan(algo="bsp", exchanger="ar", microbatches=4))):
        state, report, hlo = _bsp_train(devices, plan, batches, cfg, steps,
                                        compiled_hlo=True)
        losses = [float(l) for l in report.losses]
        step_s = PER_WORKER_BATCH * k / report.steady_examples_per_s
        print(f"four-chip: {name}: losses "
              f"{' '.join(f'{l:.4f}' for l in losses)}; compile+first step "
              f"{report.compile_time:.2f} s, steady step "
              f"{step_s * 1e3:.1f} ms")
        _check(len(losses) == steps and all(map(math.isfinite, losses)),
               f"{name}: bad losses {losses}")
        for leaf in jax.tree.leaves(state["params"]):
            copies = [np.asarray(s.data) for s in leaf.addressable_shards]
            _check(len(copies) == k and all(np.array_equal(copies[0], c)
                                            for c in copies[1:]),
                   f"{name}: parameters differ across devices")
        groups = {op: _group_sizes(hlo, op)
                  for op in ("all-to-all", "all-gather", "all-reduce")}
        print(f"four-chip: {name}: parameters identical on all {k} "
              f"devices; step collectives (replica-group sizes) {groups}")
        runs[name] = (losses, groups)
        del state
        gc.collect()

    l16, lar = runs["asa16"][0], runs["ar"][0]
    rel = max(abs(a - b) / max(abs(b), 1e-12) for a, b in zip(l16, lar))
    print(f"four-chip: asa16 vs ar loss max relative difference {rel:.3g} "
          f"(tolerance {ASA16_RTOL})")
    _check(rel <= ASA16_RTOL, "asa16 and ar loss trajectories disagree")
    g16 = runs["asa16"][1]
    _check(k in g16["all-to-all"] and k in g16["all-gather"],
           f"asa16 step lacks an all-to-all and all-gather over {k} devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only AlexNet BSP over 4 chips, asa16 vs ar")
    args = ap.parse_args(argv)
    try:
        for var in ("REPRO_PALLAS_INTERPRET", "REPRO_ATTN_IMPL"):
            _check(var not in os.environ,
                   f"{var} is set: it could hide the device path")
        sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
        try:
            from repro.launch.compile_cache import enable_compile_cache
        except ImportError as e:
            raise SmokeFailure(f"the repro package is not next to this "
                               f"script: {e}") from e
        cache_dir = enable_compile_cache()
        import jax

        from repro import telemetry
        from repro.kernels import default_interpret

        devices = jax.devices()
        dev = devices[0]
        print(f"device: platform {dev.platform}, kind {dev.device_kind}, "
              f"count {len(devices)}; compile cache {cache_dir}")
        _check(dev.platform == "tpu", f"no TPU: JAX found {dev.platform}")
        _check(not default_interpret(), "Pallas kernels would interpret")
        if args.four_chips:
            _check(len(devices) >= 4, f"{len(devices)} chips, need 4")
            four_chip_phase(devices[:4])
        else:
            train_phase(dev)
            gc.collect()
            serve_phase(dev)
        reg = telemetry.default_registry()
        errors = (reg["profile/capture_errors"].value
                  if "profile/capture_errors" in reg else 0)
        print(f"profile/capture_errors {errors}")
        _check(not errors, "cost capture failed during the run")
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
