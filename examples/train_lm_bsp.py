"""End-to-end driver: train a ~100M-parameter llama-family model for a few
hundred steps with BSP data parallelism, the ASA exchanger, the parallel
data loader (paper Alg 1), LR schedule, and checkpointing.

    PYTHONPATH=src python examples/train_lm_bsp.py [--steps 300]

Note: pure CPU — a ~100M model at seq 256 runs a few steps/minute; lower
--steps for a quick pass.
"""
import argparse
import tempfile

import jax

from repro.configs import get_config
from repro.data.prefetch import ParallelLoader
from repro.data.synthetic import LMTokenSource, materialize_batch_files
from repro.launch.mesh import make_mesh
from repro.models import build_model, count_params
from repro.optim import sgd_momentum, warmup_cosine
from repro.train.loop import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()

    # ~100M llama-family config (derived from llama3.2-1b)
    cfg = get_config("llama3.2-1b").with_overrides(
        num_layers=6, d_model=768, d_ff=2048, vocab_size=32768,
        attention=get_config("llama3.2-1b").attention.__class__(
            num_heads=12, num_kv_heads=4, head_dim=64),
        tie_embeddings=True, scan_layers=True, remat=False)
    model = build_model(cfg)
    print(f"model: {cfg.name}-100M derivative, "
          f"{count_params(jax.eval_shape(model.init, jax.random.key(0))):,}"
          " params")

    mesh = make_mesh((len(jax.devices()),), ("data",))
    jax.set_mesh(mesh)

    with tempfile.TemporaryDirectory() as td:
        # paper layout: batch files on disk + Alg 1 background loader
        src = LMTokenSource(cfg.vocab_size, args.seq)
        files = materialize_batch_files(src, td, min(args.steps, 64),
                                        args.batch)
        epochs = args.steps // len(files) + 1
        loader = ParallelLoader(files, depth=2, epochs=epochs)

        opt = sgd_momentum(weight_decay=1e-4)
        lr = warmup_cosine(0.01, 20, args.steps)
        state, report = train(model, opt, lr, mesh, loader,
                              exchanger="asa", num_steps=args.steps,
                              log_every=10, ckpt_path=args.ckpt)
        loader.stop()
    print(f"\n{report.steps} steps, {report.examples_per_s:.1f} ex/s, "
          f"loss {report.losses[0]:.3f} -> {report.losses[-1]:.3f}")


if __name__ == "__main__":
    main()
