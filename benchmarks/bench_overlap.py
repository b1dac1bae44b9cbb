"""Exposed-communication benchmark: overlapped vs serialized exchange.

The paper's §3.2 claim is that *when* gradients move matters as much as
how many bytes move. This benchmark trains an MLP at AlexNet/VGG
FC-parameter scale on 8 host devices with gradient accumulation
(microbatches) and measures, per strategy x bucket size:

- ``none``    : compute-only baseline (identity exchanger)
- ``serial``  : RS->update->AG issued once after the full accumulation
- ``overlap`` : ``overlap="buckets"`` — microbatch i-1's bucket
                reduce-scatters issued while microbatch i's backprop runs

Exposed (non-overlapped) comm time = mode wall time - compute baseline.
(The baseline updates *replicated* params while the sharded modes update
1/k per rank, so their exposed figure is understated by the update
savings and can go negative on CPU hosts; compare serial vs overlap rows
directly for the overlap effect. On CPU, XLA has no async collectives —
overlap wall time includes the m× wire volume un-hidden; the compiled-HLO
evidence is the schedule signal, the TPU scheduler does the hiding.)
The derived column also reports the compiled-HLO overlap evidence
(``roofline.analysis.overlap_evidence``): the loop body must contain a
collective that is independent of (hence issuable before) the backward
dots. One subprocess per scale so the large stacked buffers are freed
between runs (single-host memory).
"""
import json
import os
import subprocess
import sys

_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import jax, jax.numpy as jnp, numpy as np
from repro.core import (get_exchanger, init_sharded_train_state,
                        init_train_state, make_bsp_step)
from repro.launch.mesh import make_mesh
from repro.models.registry import Model
from repro.optim import constant, sgd_momentum
from repro.roofline.analysis import overlap_evidence, parse_collectives

SCALES = {
    # FC stacks with the paper models' dominant parameter counts
    "mlp-quick":  [(256, 1024), (1024, 1024), (1024, 512)],       # ~1.8M
    "alexnet-fc": [(9216, 4096), (4096, 4096), (4096, 1000)],     # ~58M
    "vgg-fc":     [(25088, 4096), (4096, 4096), (4096, 1000)],    # ~123M
}

scale = sys.argv[1]
strategies = sys.argv[2].split(",")
bucket_list = [int(b) for b in sys.argv[3].split(",")]
widths = SCALES[scale]
MICRO = 4
BATCH = 32                       # global; 4 rows/rank, 1 per microbatch


def build_model():
    def init(key):
        return {f"w{i}": jax.random.normal(jax.random.fold_in(key, i), s)
                * 0.02 for i, s in enumerate(widths)}

    def loss_fn(params, batch, rng=None, unroll=False):
        h = batch["x"]
        for i in range(len(widths)):
            h = jnp.tanh(h @ params[f"w{i}"])
        loss = 0.5 * jnp.mean(jnp.square(h))
        return loss, {"loss": loss, "aux": jnp.zeros(())}

    return Model(cfg=None, init=init, loss_fn=loss_fn, forward=None)


model = build_model()
mesh = make_mesh((8,), ("data",))
jax.set_mesh(mesh)
opt = sgd_momentum(weight_decay=0.0)
batch = {"x": np.random.default_rng(0).normal(
    0, 1, (BATCH, widths[0][0])).astype(np.float32)}
rng = jax.random.key(1)
nparams = sum(int(np.prod(s)) for s in widths)


def timed(step_fn, state):
    s, _ = step_fn(state, batch, rng)
    jax.block_until_ready(s)        # warm (compile)
    reps = 2
    t0 = time.perf_counter()
    for _ in range(reps):
        s, _ = step_fn(s, batch, rng)
    jax.block_until_ready(s)
    return (time.perf_counter() - t0) / reps * 1e6


rows = []
base = jax.jit(make_bsp_step(model, opt, get_exchanger("none"), constant(0.01),
                             mesh, microbatches=MICRO))
t_none = timed(base, init_train_state(model, opt, jax.random.key(0)))
rows.append({"name": f"overlap/{scale}/none", "us": t_none,
             "derived": f"params={nparams}"})

for strat in strategies:
    ex = get_exchanger(strat)
    for bb in bucket_list:
        sstate = init_sharded_train_state(model, opt, jax.random.key(0),
                                          mesh, bucket_bytes=bb)
        serial = jax.jit(make_bsp_step(
            model, opt, ex, constant(0.01), mesh, microbatches=MICRO,
            bucket_bytes=bb, sharded_update=True))
        over = jax.jit(make_bsp_step(
            model, opt, ex, constant(0.01), mesh, microbatches=MICRO,
            bucket_bytes=bb, overlap="buckets"))
        t_serial = timed(serial, sstate)
        t_over = timed(over, sstate)
        txt = over.lower(sstate, batch, rng).compile().as_text()
        ev = overlap_evidence(txt)
        colls = parse_collectives(txt)
        tag = f"overlap/{scale}/{strat}/b{bb}"
        rows.append({"name": f"{tag}/serial", "us": t_serial,
                     "derived": f"exposed_us={t_serial - t_none:.1f}"})
        rows.append({
            "name": f"{tag}/overlap", "us": t_over,
            "derived": (f"exposed_us={t_over - t_none:.1f};"
                        f"rs_before_last_dot={ev['rs_before_last_dot']};"
                        f"comm_independent_of_dots="
                        f"{ev['comm_independent_of_dots']};"
                        f"loop_wire_bytes={colls.total_bytes}")})
print("RESULTS_JSON:" + json.dumps(rows))
"""


def run(quick: bool = False):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    configs = ([("mlp-quick", "asa16", "0,1048576")] if quick else
               [("alexnet-fc", "asa16,asa8", "0,33554432"),
                ("vgg-fc", "asa16", "0,33554432")])
    out = []
    for scale, strats, buckets in configs:
        proc = subprocess.run(
            [sys.executable, "-c", _SCRIPT, scale, strats, buckets],
            env=env, capture_output=True, text=True, timeout=3000)
        if proc.returncode != 0:
            out.append((f"overlap/{scale}/FAILED", 0.0,
                        f"rc={proc.returncode}"))
            sys.stderr.write(proc.stderr[-2000:])
            continue
        rows = None
        for line in proc.stdout.splitlines():
            if line.startswith("RESULTS_JSON:"):
                rows = json.loads(line[len("RESULTS_JSON:"):])
        for r in rows:
            out.append((r["name"], r["us"], r["derived"]))
    return out


if __name__ == "__main__":
    for name, us, derived in run(quick="--quick" in sys.argv):
        print(f"{name},{us:.1f},{derived}")
