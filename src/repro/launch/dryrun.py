"""Multi-pod dry-run: lower + compile every (arch x input-shape) on the
production meshes and record memory/cost/collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
        --shape train_4k --mesh single --exchanger asa --out experiments/dryrun

The XLA_FLAGS assignment below MUST run before jax initializes a backend
(the host device count locks at first backend init, not at import — merely
importing jax is safe; touching ``jax.devices()`` earlier is not). Do
not import this module from processes that need 1 device.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config, get_shape
from repro.core.bsp import make_bsp_step
from repro.core.exchanger import get_exchanger
from repro.core.gspmd import (fsdp_state_shardings, make_gspmd_step)
from repro.dist import act
from repro.dist.sharding import (batch_shardings, cache_shardings,
                                 dp_axes_of, dp_size_of, param_shardings,
                                 state_shardings)
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (abstract_cache, abstract_state, decode_batch_specs,
                                sds, train_batch_specs)
from repro.models.registry import build_model
from repro.optim.optimizers import sgd_momentum
from repro.optim.schedule import constant
from repro.roofline.analysis import analyze, model_flops_6nd

# replicated-DP (paper-faithful BSP) is infeasible above this per-chip bound;
# larger archs use the GSPMD/ZeRO-1 path (see core/gspmd.py and DESIGN.md).
FSDP_THRESHOLD_BYTES = 12e9


def _bf16_params(params):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.bfloat16 if s.dtype == jnp.float32 and len(s.shape) >= 2
            else s.dtype),
        params)


def needs_fsdp(cfg, mesh) -> bool:
    tp = mesh.shape.get("model", 1)
    per_chip = cfg.param_count() * 4 * 3 / tp  # params+momentum+grads fp32
    return per_chip > FSDP_THRESHOLD_BYTES


def build_train(cfg, shape, mesh, exchanger_name: str, mode_override=None, unroll=True):
    model = build_model(cfg)
    opt = sgd_momentum(weight_decay=0.0)
    state = abstract_state(model, opt)
    batch = train_batch_specs(cfg, shape)
    dp = dp_axes_of(mesh)
    rng = sds((2,), jnp.uint32)

    def with_rng(fn):
        def wrapped(state, batch, seed):
            return fn(state, batch, jax.random.wrap_key_data(seed))
        return wrapped

    mode = mode_override or ("fsdp" if needs_fsdp(cfg, mesh) else "bsp")
    if mode == "bsp":
        step = make_bsp_step(model, opt, get_exchanger(exchanger_name),
                             constant(0.01), mesh, data_axes=dp,
                             unroll=unroll)
        state_sh = state_shardings(mesh, state)
    else:
        step = make_gspmd_step(model, opt, constant(0.01), mesh,
                               mode="zero1" if mode in ("fsdp", "zero1")
                               else "ar", unroll=unroll)
        state_sh = fsdp_state_shardings(mesh, state)

    fn = with_rng(step)
    in_sh = (state_sh, batch_shardings(mesh, batch),
             NamedSharding(mesh, P()))
    args = (state, batch, rng)
    return fn, args, in_sh, mode


def build_prefill(cfg, shape, mesh, unroll=True):
    model = build_model(cfg)
    params = _bf16_params(jax.eval_shape(model.init, jax.random.key(0)))
    batch = train_batch_specs(cfg, shape)
    batch.pop("labels", None)

    def fn(params, batch):
        return model.forward(params, batch, unroll=unroll)

    in_sh = (param_shardings(mesh, params), batch_shardings(mesh, batch))
    return fn, (params, batch), in_sh, "prefill"


def build_decode(cfg, shape, mesh, unroll=True):
    model = build_model(cfg)
    params = _bf16_params(jax.eval_shape(model.init, jax.random.key(0)))
    cache = abstract_cache(model, cfg, shape)
    batch = decode_batch_specs(cfg, shape)
    pos = sds((), jnp.int32)

    def fn(params, cache, batch, pos):
        logits, new_cache = model.decode_step(params, cache, batch, pos,
                                              seq_len=shape.seq_len,
                                              unroll=unroll)
        return jnp.argmax(logits[:, -1, :], axis=-1), new_cache

    in_sh = (param_shardings(mesh, params),
             cache_shardings(mesh, cache, shape.global_batch),
             batch_shardings(mesh, batch), NamedSharding(mesh, P()))
    return fn, (params, cache, batch, pos), in_sh, "decode"


def _scan_seg_lengths(cfg) -> list[int]:
    """Lengths of the lax.scan'ed layer segments (for cost extrapolation)."""
    from repro.models.transformer import segments
    if cfg.family == "encdec":
        return [cfg.num_encoder_layers, cfg.num_layers]
    if cfg.family == "conv":
        return []
    return [c for _, c in segments(cfg) if c > 1]


def _extrapolate(res1: dict, res2: dict, lstar: int) -> dict:
    """Roofline terms from unroll=1 and unroll=2 compiles.

    XLA costs a while-loop body once, so cost(u) = outside + u*body for
    equal-length scanned segments; total = c1 + (L-1)*(c2-c1)."""
    out = json.loads(json.dumps(res1))
    r1, r2 = res1["roofline"], res2["roofline"]
    for key in ("flops", "hbm_bytes", "coll_bytes", "model_flops"):
        body = max(r2[key] - r1[key], 0.0)
        out["roofline"][key] = r1[key] + (lstar - 1) * body
    rl = out["roofline"]
    from repro.roofline.analysis import PEAK_FLOPS, HBM_BW, ICI_BW
    rl["t_compute_s"] = rl["flops"] / PEAK_FLOPS
    rl["t_memory_s"] = rl["hbm_bytes"] / HBM_BW
    rl["t_collective_s"] = rl["coll_bytes"] / ICI_BW
    terms = {"compute": rl["t_compute_s"], "memory": rl["t_memory_s"],
             "collective": rl["t_collective_s"]}
    rl["dominant"] = max(terms, key=terms.get)
    rl["model_flops"] = res1["roofline"]["model_flops"]  # analytic, not scaled
    rl["useful_ratio"] = (rl["model_flops"] / rl["flops"]
                          if rl["flops"] else 0.0)
    c1, c2 = res1["collectives"], res2["collectives"]
    for kind, v1 in c1["counts"].items():
        v2 = c2["counts"].get(kind, v1)
        out["collectives"]["counts"][kind] = v1 + (lstar - 1) * max(v2 - v1, 0)
    for kind, v1 in c1["bytes_by_kind"].items():
        v2 = c2["bytes_by_kind"].get(kind, v1)
        out["collectives"]["bytes_by_kind"][kind] = (
            v1 + (lstar - 1) * max(v2 - v1, 0))
    out["extrapolated_from_unroll12"] = True
    return out


def run_one(arch: str, shape_name: str, multi_pod: bool,
            exchanger: str = "asa", seq_shard: bool = True,
            mode_override=None, unroll: bool | None = None,
            block_kv: int = 0, replicate_attn: bool = False) -> dict:
    from repro.dist.sharding import set_replicate_attn
    set_replicate_attn(replicate_attn)
    cfg = get_config(arch)
    if block_kv and cfg.attention is not None:
        import dataclasses
        cfg = cfg.with_overrides(
            attention=dataclasses.replace(cfg.attention, block_kv=block_kv,
                                          block_unroll=True))
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    jax.set_mesh(mesh)
    result = {"arch": arch, "shape": shape_name,
              "mesh": "2x16x16" if multi_pod else "16x16",
              "exchanger": exchanger, "unrolled": bool(unroll),
              "block_kv": block_kv}
    t0 = time.time()

    # sequence-parallel activation constraint (memory): residual stream's
    # feature dim sharded over 'model' between layers.
    spec = P(None, None, "model") if seq_shard else None

    if shape.kind == "decode":
        spec = None  # single-token residual: no constraint

    try:
        def build(u):
            if shape.kind == "train":
                return build_train(cfg, shape, mesh, exchanger,
                                   mode_override, unroll=u)
            if shape.kind == "prefill":
                return build_prefill(cfg, shape, mesh, unroll=u)
            return build_decode(cfg, shape, mesh, unroll=u)

        chips = 512 if multi_pod else 256
        tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                       else 1)
        mf = model_flops_6nd(cfg.active_param_count(), tokens,
                             "train" if shape.kind == "train" else "infer")

        def compile_once(u):
            fn, args, in_sh, mode = build(u)
            with act.activation_spec(spec):
                lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
            compiled = lowered.compile()
            return analyze(compiled, model_flops_per_device=mf / chips), mode

        res1, mode = compile_once(1)
        result["mode"] = mode
        segs = _scan_seg_lengths(cfg)
        # single-pod roofline pass: second compile at unroll=2, extrapolate
        # per-layer costs (scan bodies are costed once by XLA)
        if (not multi_pod) and segs and all(s == segs[0] for s in segs) \
                and segs[0] > 1 and cfg.scan_layers:
            res2, _ = compile_once(2)
            result.update(_extrapolate(res1, res2, segs[0]))
        else:
            result.update(res1)
        result["compile_s"] = round(time.time() - t0, 1)
        result["ok"] = True
    except Exception as e:  # noqa: BLE001 - report, don't crash the sweep
        result["ok"] = False
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-2000:]
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all' (assigned archs)")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--exchanger", default="asa")
    ap.add_argument("--mode", default=None,
                    help="override train mode: bsp|zero1|ar")
    ap.add_argument("--no-seq-shard", action="store_true")
    ap.add_argument("--block-kv", type=int, default=0,
                    help="blockwise attention KV block (0=naive baseline)")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--tag", default="", help="extra tag suffix for output")
    ap.add_argument("--replicate-attn", action="store_true",
                    help="replicate attention/SSM params (no TP on them)")
    args = ap.parse_args()

    archs = ASSIGNED_ARCHS if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
                if args.mode:
                    tag += f"__{args.mode}"
                if args.exchanger != "asa":
                    tag += f"__{args.exchanger}"
                if args.block_kv:
                    tag += f"__bkv{args.block_kv}"
                if args.no_seq_shard:
                    tag += "__noseq"
                if args.replicate_attn:
                    tag += "__repattn"
                if args.tag:
                    tag += f"__{args.tag}"
                res = run_one(arch, shape, mp, args.exchanger,
                              seq_shard=not args.no_seq_shard,
                              mode_override=args.mode,
                              block_kv=args.block_kv,
                              replicate_attn=args.replicate_attn)
                path = os.path.join(args.out, tag + ".json")
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
                if res["ok"]:
                    rl = res["roofline"]
                    print(f"OK  {tag:60s} mode={res.get('mode','-'):7s} "
                          f"compile={res['compile_s']:6.1f}s "
                          f"t_comp={rl['t_compute_s']:.3e} "
                          f"t_mem={rl['t_memory_s']:.3e} "
                          f"t_coll={rl['t_collective_s']:.3e} "
                          f"dom={rl['dominant']}", flush=True)
                else:
                    print(f"FAIL {tag}: {res['error']}", flush=True)


if __name__ == "__main__":
    main()
