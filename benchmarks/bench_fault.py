"""Fault-tolerance overhead: the elastic harness vs the fixed engine.

Three questions, all on 8 virtual CPU workers:

- what does the elastic loop cost when nothing fails? (``elastic_clean``
  vs the fixed-engine easgd row: same algo/tau, but per-step membership
  bookkeeping + the weighted quorum sync program). Elastic per-step cost
  uses the two-length diff method — (T(long) - T(short)) / extra steps —
  so the program-build/compile cost cancels instead of polluting the row;
- what does one kill cost at the round boundary? (``rebuild_on_kill``:
  re-jit the programs for k-1 on a fresh mesh + reshard replica rows —
  read from the loop's own ``fault/rebuild`` telemetry span);
- what does an averaging round cost vs a local step? (``sync_round``:
  the ``fault/round`` span vs the amortized per-step cost; below-quorum
  rounds degrade to the local path, so this brackets the skip savings).

The wall numbers are CPU-host timings (workers timeshare the host); the
derived columns — overhead %%, rebuild latency, round/step ratio — are
the transferable shape.
"""
import json
import os
import subprocess
import sys

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
QUICK = %(quick)d
import json, time
import jax, numpy as np
from repro import telemetry
from repro.launch.mesh import make_mesh
from repro.telemetry import trace
from repro.configs import get_smoke_config
from repro.data.synthetic import LMTokenSource
from repro.models import build_model
from repro.optim import constant, sgd_momentum
from repro.train.engine import TrainPlan, build_engine
from repro.fault.elastic import elastic_train

cfg = get_smoke_config("llama3.2-1b").with_overrides(vocab_size=128)
model = build_model(cfg)
opt = sgd_momentum(weight_decay=0.0)
src = LMTokenSource(cfg.vocab_size, 32)
batch_fn = lambda step, k: src.batch(4 * k, step)
tau = 4
short, long = (2 * tau, 6 * tau) if QUICK else (4 * tau, 12 * tau)
rows = []

# fixed-engine reference: same algo/tau, warmed, no membership machinery
mesh = make_mesh((8,), ("data",))
jax.set_mesh(mesh)
plan_f = TrainPlan(algo="easgd", exchanger="ar", tau=tau, alpha=0.5)
eng = build_engine(plan_f, model, opt, constant(0.02), mesh)
state = eng.init_state(jax.random.key(0))
_ = eng.step(state, batch_fn(0, 8), jax.random.key(0), step_idx=0)
_ = eng.step(state, batch_fn(0, 8), jax.random.key(0), step_idx=tau - 1)
jax.block_until_ready(_[0])
n = long - short
t0 = time.perf_counter()
for i in range(n):
    state, m = eng.step(state, batch_fn(i, 8), jax.random.key(i),
                        step_idx=i)
jax.block_until_ready(state)
base = (time.perf_counter() - t0) / n * 1e6
rows.append({"name": "fixed_easgd_tau4", "us": base})

plan = TrainPlan(algo="easgd", exchanger="ar", tau=tau, alpha=0.5,
                 quorum=2)

def wall(num_steps, fault_plan=None):
    t0 = time.perf_counter()
    _, rep = elastic_train(model, opt, constant(0.02), batch_fn,
                           plan=plan, num_workers=8, num_steps=num_steps,
                           fault_plan=fault_plan, print_fn=None)
    return time.perf_counter() - t0, rep

# steady elastic per-step cost: build/compile cancels in the difference
t_short, _ = wall(short)
t_long, _ = wall(long)
us = (t_long - t_short) / (long - short) * 1e6
rows.append({"name": "elastic_clean_tau4", "us": us,
             "overhead_vs_fixed": us / base - 1.0})

# one kill: rebuild+reshard latency from the loop's own telemetry spans
telemetry.set_enabled(True)
trace.reset()
_, rep = wall(long, fault_plan="kill:7@%%d" %% (tau + 1))
spans = {name: dur for kind, name, t0_, dur, tid, attrs in trace.events()
         if kind == "X"}
telemetry.set_enabled(False)
assert rep.rebuilds == 1, rep
rows.append({"name": "rebuild_on_kill", "us": spans["fault/rebuild"] * 1e6,
             "reshard_us": spans["fault/reshard"] * 1e6,
             "note": "k=8->7 re-jit + row reshard at one round boundary"})

# a synced averaging round vs the amortized step: the fault/round span
telemetry.set_enabled(True)
trace.reset()
wall(long)
round_durs = [dur for kind, name, t0_, dur, tid, attrs in trace.events()
              if kind == "X" and name == "fault/round"]
telemetry.set_enabled(False)
round_us = float(np.median(round_durs)) * 1e6
rows.append({"name": "sync_round_dispatch", "us": round_us,
             "round_over_step": round_us / us,
             "note": "host-side dispatch window of the quorum sync "
                     "(async dispatch; below-quorum rounds take the "
                     "local path instead)"})
print("RESULTS_JSON:" + json.dumps(rows))
"""


def run(quick: bool = False):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    script = _SCRIPT % {"quick": int(quick)}
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    rows = None
    for line in proc.stdout.splitlines():
        if line.startswith("RESULTS_JSON:"):
            rows = json.loads(line[len("RESULTS_JSON:"):])
    out = []
    for r in rows:
        derived = []
        if "overhead_vs_fixed" in r:
            derived.append(f"overhead_vs_fixed={r['overhead_vs_fixed']:+.1%}")
        if "reshard_us" in r:
            derived.append(f"reshard_us={r['reshard_us']:.0f}")
        if "round_over_step" in r:
            derived.append(f"round_over_step={r['round_over_step']:.2f}x")
        if "note" in r:
            derived.append(r["note"])
        out.append((f"fault/{r['name']}", r["us"], ";".join(derived)))
    return out


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name},{us:.1f},{derived}")
