"""Paper-faithful experiment: AlexNet trained with BSP + configurable
exchange strategy and the Alg-1 parallel loader, on synthetic ImageNet-like
batch files. Reproduces the paper's training-loop structure end to end
(reduced image size by default — pass --full for 227x227 AlexNet).

    PYTHONPATH=src python examples/train_alexnet_bsp.py \
        --exchanger asa16 --steps 30
"""
import argparse
import tempfile

import numpy as np
import jax

from repro.configs import get_config, get_smoke_config
from repro.data.prefetch import ParallelLoader
from repro.data.synthetic import ImageSource, materialize_batch_files
from repro.launch.mesh import make_mesh
from repro.models import build_model, count_params
from repro.optim import sgd_momentum, step_decay
from repro.train.loop import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--exchanger", default="asa",
                    help="ar | asa | asa16 | asa8 | ring | hier")
    ap.add_argument("--scheme", default="subgd", choices=["subgd", "awagd"])
    ap.add_argument("--full", action="store_true",
                    help="full 227x227 AlexNet (slow on CPU)")
    args = ap.parse_args()

    cfg = get_config("alexnet") if args.full else get_smoke_config("alexnet")
    model = build_model(cfg)
    n = count_params(jax.eval_shape(model.init, jax.random.key(0)))
    print(f"AlexNet ({'full' if args.full else 'reduced'}): {n:,} params, "
          f"exchanger={args.exchanger}, scheme={args.scheme}")

    mesh = make_mesh((len(jax.devices()),), ("data",))
    jax.set_mesh(mesh)

    with tempfile.TemporaryDirectory() as td:
        src = ImageSource(cfg.image_size, cfg.num_classes)
        files = materialize_batch_files(src, td, min(args.steps, 32),
                                        args.batch)
        mean = np.zeros((cfg.image_size, cfg.image_size, 3), np.float32)
        loader = ParallelLoader(files, image_mean=mean,
                                crop=cfg.image_size - 8, depth=2,
                                epochs=args.steps // len(files) + 1)
        # the paper's AlexNet LR policy: /10 every "20 epochs"
        lr = step_decay(0.01, steps_per_drop=max(args.steps // 3, 1))
        opt = sgd_momentum(momentum=0.9, weight_decay=5e-4)
        state, report = train(model, opt, lr, mesh, loader,
                              exchanger=args.exchanger, scheme=args.scheme,
                              num_steps=args.steps, log_every=5)
        loader.stop()
    print(f"\n{report.steps} steps, {report.examples_per_s:.1f} images/s, "
          f"loss {report.losses[0]:.3f} -> {report.losses[-1]:.3f}")


if __name__ == "__main__":
    main()
