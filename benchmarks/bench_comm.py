"""Fig 3 / Table 3: communication overhead of exchange strategies.

Exchanges gradient pytrees with the exact parameter counts of the paper's
models (AlexNet 61M / GoogLeNet 13.4M / VGG 138M) across 8 workers,
measuring (a) wall-clock per exchange on 8 host devices and (b) modeled
wire bytes parsed from the compiled HLO. One subprocess per model so the
8x-stacked gradients are freed between models (single-host memory).

Derived column: modeled-bytes speedup vs the AR baseline (the paper's
Table 3 reports 3x for ASA, ~6x for ASA16 vs Allreduce).

The ``asa16+{exchupd,updexch,rsupd}`` rows compare full *step* pipelines
(exchange + parameter update) for the sharded fused-update work:

- ``exchupd``: exchange gradients, update replicated (subgd);
- ``updexch``: update locally, exchange weights AND momentum (awagd /
  Krizhevsky — what Synkhronos fuses away);
- ``rsupd``  : RS -> shard update -> AG of updated params (fused path).

``rsupd`` matches ``exchupd``'s wire bytes (its win is the eliminated
full-gradient materialization, the 1/k update compute/state, and overlap
eligibility) and halves ``updexch``'s — momentum never touches the wire.
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
from jax.sharding import PartitionSpec as P
from repro.core.exchanger import get_exchanger
from repro.launch.mesh import make_mesh
from repro.roofline.analysis import parse_collectives

MODELS = {
    # name -> parameter tensor shapes approximating the paper's models
    "alexnet": [(11*11*3, 96), (5*5*48, 256), (3*3*256, 384), (3*3*192, 384),
                (3*3*192, 256), (9216, 4096), (4096, 4096), (4096, 1000)],
    "googlenet": [(1024, 1000)] + [(480, 512)] * 24,
    "vggnet": [(3*3*64, 64), (3*3*128, 128), (3*3*256, 256), (3*3*512, 512),
               (25088, 4096), (4096, 4096), (4096, 1000)],
}

mname = sys.argv[1]
shapes = MODELS[mname]
mesh = make_mesh((8,), ("data",))
jax.set_mesh(mesh)
key = jax.random.key(0)
rows = []
# split big tensors into <=8M-element pieces (DDP-style bucketing): XLA's
# CPU all-reduce materializes O(k^2) copies of each buffer, so >100MB
# leaves OOM the single-host 8-device simulation. Wire bytes unchanged.
MAX_ELEMS = 2 << 20
grads = {}
for i, s in enumerate(shapes):
    n = int(np.prod(s))
    pieces = max(1, -(-n // MAX_ELEMS))
    rows_per = s[0] // pieces if s[0] >= pieces else s[0]
    start = 0
    j = 0
    while start < s[0]:
        r = min(rows_per, s[0] - start)
        grads[f"p{i}_{j}"] = jax.random.normal(
            jax.random.fold_in(key, i * 100 + j),
            (8, r, *s[1:])).astype(jnp.float32)
        start += r
        j += 1
nparams = sum(int(np.prod(s)) for s in shapes)
base_bytes = None
strategies = ["ar", "asa", "asa16", "asa8"]
if nparams < 20e6:
    strategies.append("ring")   # unrolled 2(k-1) ppermute steps: too many
                                # live fp32 buffers for the 61M/138M models
                                # on a single-host 8-device CPU sim
for strat in strategies:
    ex = get_exchanger(strat)
    def f(gs):
        per = {n: v[0] for n, v in gs.items()}
        out = ex.exchange(per, "data")
        return {n: v[None] for n, v in out.items()}
    fn = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                               out_specs=P("data"),
                               axis_names=frozenset({"data"}),
                               check_vma=False))
    compiled = fn.lower(grads).compile()
    st = parse_collectives(compiled.as_text())
    wire = st.total_bytes
    out = fn(grads); jax.block_until_ready(out)  # warm
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        out = fn(grads)
    jax.block_until_ready(out)
    us = (time.perf_counter() - t0) / reps * 1e6
    del out, fn, compiled
    if strat == "ar":
        base_bytes = wire or 1
    rows.append({"model": mname, "strategy": strat, "params": nparams,
                 "us_per_call": us, "wire_bytes": wire,
                 "modeled_speedup_vs_ar": base_bytes / max(wire, 1)})

# --- full-step pipelines: exchange-then-update vs update-then-exchange
# (awagd) vs the fused RS->update->AG path, for asa16 -----------------------
from repro.core.exchanger import Exchanger as _Ex, param_wire_dtype

ex = get_exchanger("asa16")
LR = jnp.float32(0.01)

def _upd(p, g):
    return p - LR * g            # momentum-SGD first step (m0 = 0 -> m1 = g)

def f_exchupd(gs):
    per = {n: v[0] for n, v in gs.items()}
    red = ex.exchange(per, "data")
    return {n: _upd(jnp.zeros_like(v), red[n])[None] for n, v in per.items()}

def f_updexch(gs):
    per = {n: v[0] for n, v in gs.items()}
    newp = ex.exchange({n: _upd(jnp.zeros_like(v), v)
                        for n, v in per.items()}, "data")
    newm = ex.exchange(per, "data")          # momentum after step 1 == grads
    return ({n: v[None] for n, v in newp.items()},
            {n: v[None] for n, v in newm.items()})

def f_rsupd(gs):
    per = {n: v[0] for n, v in gs.items()}
    plan = ex.plan_for(per, "data")
    res, _ = ex.reduce_scatter(per, "data", plan=plan)
    idx = jax.lax.axis_index("data")
    p_flats, p_smalls, _ = _Ex.pack(
        {n: jnp.zeros_like(v) for n, v in per.items()}, plan)
    new_flats = []
    for bi, b in enumerate(plan.buckets):
        p_sh = jax.lax.dynamic_slice(p_flats[bi], (idx * b.shard_len,),
                                     (b.shard_len,))
        new_flats.append(ex.all_gather(
            [_upd(p_sh, res["shards"][bi])], plan, "data",
            wire_dtype=param_wire_dtype(ex))[0])
    smalls = [_upd(s.astype(jnp.float32), g)
              for s, g in zip(p_smalls, res["full"])]
    out = _Ex.unpack(new_flats, smalls, plan)
    return {n: v[None] for n, v in out.items()}

vbytes = {}
for vname, vf, ospec in [("exchupd", f_exchupd, P("data")),
                         ("updexch", f_updexch, (P("data"), P("data"))),
                         ("rsupd", f_rsupd, P("data"))]:
    fn = jax.jit(jax.shard_map(vf, mesh=mesh, in_specs=P("data"),
                               out_specs=ospec,
                               axis_names=frozenset({"data"}),
                               check_vma=False))
    compiled = fn.lower(grads).compile()
    wire = parse_collectives(compiled.as_text()).total_bytes
    out = fn(grads); jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(3):
        out = fn(grads)
    jax.block_until_ready(out)
    us = (time.perf_counter() - t0) / 3 * 1e6
    del out, fn, compiled
    vbytes[vname] = wire
    extra = ""
    if vname == "rsupd":
        extra = (f";rsupd_over_exchupd="
                 f"{wire / max(vbytes['exchupd'], 1):.2f}"
                 f";rsupd_over_updexch="
                 f"{wire / max(vbytes['updexch'], 1):.2f}")
    rows.append({"model": mname, "strategy": f"asa16+{vname}",
                 "params": nparams, "us_per_call": us, "wire_bytes": wire,
                 "modeled_speedup_vs_ar": base_bytes / max(wire, 1),
                 "extra": extra})
print("RESULTS_JSON:" + json.dumps(rows))
"""


def run(quick: bool = False):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = []
    models = ["alexnet"] if quick else ["alexnet", "googlenet", "vggnet"]
    for mname in models:
        proc = subprocess.run([sys.executable, "-c", _SCRIPT, mname],
                              env=env, capture_output=True, text=True,
                              timeout=1800)
        if proc.returncode != 0:
            out.append((f"comm/{mname}/FAILED", 0.0,
                        f"rc={proc.returncode}"))
            continue
        rows = None
        for line in proc.stdout.splitlines():
            if line.startswith("RESULTS_JSON:"):
                rows = json.loads(line[len("RESULTS_JSON:"):])
        for r in rows:
            out.append((f"comm/{r['model']}/{r['strategy']}",
                        r["us_per_call"],
                        f"wire_bytes={r['wire_bytes']};"
                        f"modeled_speedup_vs_ar="
                        f"{r['modeled_speedup_vs_ar']:.2f}"
                        + r.get("extra", "")))
    return out


if __name__ == "__main__":
    for name, us, derived in run(quick="--quick" in sys.argv):
        print(f"{name},{us:.1f},{derived}")
