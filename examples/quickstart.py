"""Quickstart: train a tiny llama-style LM with the paper's BSP + ASA
exchange on the host devices, then greedy-decode from it.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.data.synthetic import LMTokenSource
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.optim import sgd_momentum, warmup_cosine
from repro.train.loop import train
from repro.train.serve import generate


def main():
    cfg = get_smoke_config("llama3.2-1b").with_overrides(vocab_size=256)
    model = build_model(cfg)
    mesh = make_mesh((len(jax.devices()),), ("data",))
    jax.set_mesh(mesh)

    src = LMTokenSource(cfg.vocab_size, seq_len=64)
    batches = (src.batch(16, i) for i in range(100))
    opt = sgd_momentum(weight_decay=0.0)

    state, report = train(model, opt, warmup_cosine(0.02, 10, 100), mesh,
                          batches, exchanger="asa", num_steps=100,
                          log_every=20)
    print(f"\ntrained {report.steps} steps "
          f"({report.examples_per_s:.0f} examples/s); "
          f"loss {report.losses[0]:.3f} -> {report.losses[-1]:.3f}")

    prompt = jnp.ones((2, 4), jnp.int32)
    out = generate(model, state["params"], prompt, max_new=12, seq_len=16)
    print("greedy sample:", out[0].tolist())


if __name__ == "__main__":
    main()
