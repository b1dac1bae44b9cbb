"""Async training through the unified engine (paper §4): EASGD and ASGD.

Sweeps the averaging period tau — reproducing the paper's observation that
larger tau behaves like a larger effective batch (slower initial
convergence, less communication) — with the elastic center exchange
routed through the shared exchanger layer at fp16 wire (``asa16``). The
sync/async switch is one field on the TrainPlan; the loop, checkpointing
and metrics are identical to the BSP examples.

    PYTHONPATH=src python examples/easgd_async.py --steps 60
"""
import argparse

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.data.synthetic import LMTokenSource
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.optim import constant, sgd_momentum
from repro.train.engine import TrainPlan
from repro.train.loop import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--alpha", type=float, default=0.5)
    ap.add_argument("--exchanger", default="asa16",
                    help="wire format of the center exchange")
    args = ap.parse_args()

    cfg = get_smoke_config("llama3.2-1b").with_overrides(vocab_size=256)
    model = build_model(cfg)
    k = len(jax.devices())
    mesh = make_mesh((k,), ("data",))
    jax.set_mesh(mesh)
    src = LMTokenSource(cfg.vocab_size, 64)
    opt = sgd_momentum(weight_decay=0.0)
    batches = lambda: (src.batch(8 * k, i) for i in range(args.steps))

    for tau in (1, 2, 4):
        plan = TrainPlan(algo="easgd", exchanger=args.exchanger,
                         alpha=args.alpha, tau=tau)
        _, report = train(model, opt, constant(0.02), mesh, batches(),
                          plan=plan, num_steps=args.steps, log_every=0,
                          print_fn=lambda *_: None)
        print(f"easgd tau={tau}: loss {report.losses[0]:.3f} -> "
              f"{np.mean(report.losses[-5:]):.3f}  "
              f"(center exchange every {tau} steps at "
              f"{args.exchanger}, alpha={args.alpha})")

    # asgd: the alpha=1 point — center applies summed worker deltas, so
    # the lr scales down by k
    plan = TrainPlan(algo="asgd", exchanger=args.exchanger, tau=2)
    _, report = train(model, opt, constant(0.02 / k), mesh, batches(),
                      plan=plan, num_steps=args.steps, log_every=0,
                      print_fn=lambda *_: None)
    print(f"asgd  tau=2: loss {report.losses[0]:.3f} -> "
          f"{np.mean(report.losses[-5:]):.3f}  "
          f"(workers re-fetch the center each sync)")


if __name__ == "__main__":
    main()
