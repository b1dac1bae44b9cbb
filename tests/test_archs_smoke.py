"""Per-assigned-architecture smoke tests: reduced variant (2 layers,
d_model<=512, <=4 experts), one forward/train step on CPU, asserting output
shapes and no NaNs. Decode smoke for decoder/encdec families."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (ASSIGNED_ARCHS, PAPER_ARCHS, get_smoke_config)
from repro.core import get_exchanger, init_train_state, make_bsp_step
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.optim import constant, sgd_momentum

ALL = ASSIGNED_ARCHS + PAPER_ARCHS


def make_batch(cfg, B=2, S=32):
    key = jax.random.key(7)
    if cfg.family == "conv":
        return {"images": jax.random.normal(
                    key, (B, cfg.image_size, cfg.image_size, 3)),
                "labels": jnp.zeros((B,), jnp.int32)}
    if cfg.family == "encdec":
        return {"frames": jax.random.normal(
                    key, (B, cfg.encoder_seq_len, cfg.d_model)),
                "tokens": jnp.ones((B, S), jnp.int32),
                "labels": jnp.ones((B, S), jnp.int32)}
    b = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
         "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    if cfg.modality == "vlm":
        b["image_embeds"] = jax.random.normal(
            key, (B, cfg.num_image_tokens, cfg.d_model)) * 0.02
    return b


@pytest.mark.parametrize("arch", ALL)
def test_forward_and_loss(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = make_batch(cfg)
    loss, metrics = model.loss_fn(params, batch, rng=jax.random.key(1))
    assert jnp.isfinite(loss), f"{arch}: loss not finite"
    logits = model.forward(params, batch)
    assert logits.ndim in (2, 3) and not bool(jnp.isnan(logits).any())
    if cfg.family != "conv":
        B, S = batch["tokens"].shape
        assert logits.shape == (B, S, cfg.vocab_size)


@pytest.mark.parametrize("arch", ALL)
def test_one_train_step(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    mesh = make_mesh((1,), ("data",))
    jax.set_mesh(mesh)
    opt = sgd_momentum(weight_decay=0.0)
    state = init_train_state(model, opt, jax.random.key(0))
    step = jax.jit(make_bsp_step(model, opt, get_exchanger("asa"),
                                 constant(0.05), mesh))
    batch = make_batch(cfg)
    new_state, metrics = step(state, batch, jax.random.key(1))
    assert jnp.isfinite(metrics["loss"])
    assert int(new_state["step"]) == 1
    # parameters changed and stayed finite
    moved = 0
    for old, new in zip(jax.tree.leaves(state["params"]),
                        jax.tree.leaves(new_state["params"])):
        assert bool(jnp.isfinite(new).all()), f"{arch}: non-finite params"
        if not np.array_equal(np.asarray(old), np.asarray(new)):
            moved += 1
    assert moved > 0, f"{arch}: no parameter moved"


@pytest.mark.parametrize("arch", [a for a in ASSIGNED_ARCHS])
def test_decode_step_smoke(arch):
    cfg = get_smoke_config(arch)
    if cfg.family == "conv":
        pytest.skip("no decode for conv")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    B, S = 2, 16
    cache = model.init_cache(B, S)
    if cfg.family == "encdec":
        frames = jax.random.normal(jax.random.key(1),
                                   (B, cfg.encoder_seq_len, cfg.d_model))
        cache = model.prefill(params, frames, cache)
    tokens = jnp.ones((B, 1), jnp.int32)
    logits, cache2 = model.decode_step(params, cache, {"tokens": tokens},
                                       jnp.int32(0), seq_len=S)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all()), f"{arch}: decode NaN/inf"
    # second step with updated cache
    logits2, _ = model.decode_step(params, cache2, {"tokens": tokens},
                                   jnp.int32(1), seq_len=S)
    assert bool(jnp.isfinite(logits2).all())
