"""Table 1: data-throughput speedup vs number of workers.

Trains the reduced AlexNet (paper's main model) with a fixed per-worker
batch on k = 1, 2, 4, 8 host devices and reports examples/s and speedup
vs k=1 (the paper reports 6.7x at 8 GPUs for AlexNet-128b).
"""
import json
import os
import subprocess
import sys

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import jax, numpy as np
from repro.configs import get_smoke_config
from repro.core import get_exchanger, init_train_state, make_bsp_step
from repro.data.synthetic import ImageSource, LMTokenSource
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.optim import constant, sgd_momentum

rows = []
for arch in ["alexnet", "llama3.2-1b"]:
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    opt = sgd_momentum(weight_decay=0.0)
    per_worker = 8
    base = None
    for k in [1, 2, 4, 8]:
        mesh = make_mesh((k,), ("data",),
                             devices=np.array(jax.devices()[:k]))
        jax.set_mesh(mesh)
        step = jax.jit(make_bsp_step(model, opt, get_exchanger("asa"),
                                     constant(0.01), mesh))
        state = init_train_state(model, opt, jax.random.key(0))
        B = per_worker * k
        if cfg.family == "conv":
            src = ImageSource(cfg.image_size, cfg.num_classes)
            batch = src.batch(B, 0)
        else:
            src = LMTokenSource(cfg.vocab_size, 64)
            batch = src.batch(B, 0)
        state, _ = step(state, batch, jax.random.key(1))  # compile
        jax.block_until_ready(state)
        t0 = time.perf_counter()
        reps = 3
        for i in range(reps):
            state, _ = step(state, batch, jax.random.key(i))
        jax.block_until_ready(state)
        dt = (time.perf_counter() - t0) / reps
        eps = B / dt
        if k == 1:
            base = eps
            base_dt = dt
        # this host has ONE core: k virtual workers timeshare it, so ideal
        # wall time is k*dt_1 (serialized compute). efficiency_vs_serial
        # isolates the parallelization (comm+sync) overhead the paper's
        # Table 1 measures on real parallel hardware.
        rows.append({"arch": arch, "k": k, "us_per_step": dt * 1e6,
                     "examples_per_s": eps, "speedup": eps / base,
                     "efficiency_vs_serial": (k * base_dt) / dt})
print("RESULTS_JSON:" + json.dumps(rows))
"""


def run():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    rows = None
    for line in proc.stdout.splitlines():
        if line.startswith("RESULTS_JSON:"):
            rows = json.loads(line[len("RESULTS_JSON:"):])
    out = []
    for r in rows:
        out.append((f"scaling/{r['arch']}/k={r['k']}",
                    r["us_per_step"],
                    f"examples_per_s={r['examples_per_s']:.1f};"
                    f"speedup={r['speedup']:.2f};"
                    f"efficiency_vs_serial={r['efficiency_vs_serial']:.2f}"))
    return out


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
