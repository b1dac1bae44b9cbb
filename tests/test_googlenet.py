"""GoogLeNet at its published size: Caffe's rounded-up max pools give the
maps of Szegedy et al.'s Table 1 at 224 px, the auxiliary heads are sized
from the maps they take, and each module and head has its name scope."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.bsp import _loss_and_grad
from repro.models import build_model, vision

MODULES = [f"inception_{n}" for n in vision._INCEPTION] + ["aux0", "aux1"]


def _model(side=224, classes=1000):
    return build_model(dataclasses.replace(
        get_config("googlenet"), image_size=side, num_classes=classes))


def _batch(b, side):
    return {"images": jax.ShapeDtypeStruct((b, side, side, 3), jnp.float32),
            "labels": jax.ShapeDtypeStruct((b,), jnp.int32)}


def test_published_maps_and_heads_at_224px():
    model = _model()
    params = jax.eval_shape(model.init, jax.random.key(0))
    images = _batch(2, 224)["images"]
    out, taps = jax.eval_shape(vision._googlenet_trunk, params, images)
    assert out.shape == (2, 7, 7, 1024)                      # 5b
    assert [t.shape for t in taps] == [(2, 14, 14, 512),     # 4a
                                       (2, 14, 14, 528)]     # 4d
    assert params["aux0_fc1"]["w"].shape == (128 * 4 * 4, 1024)
    assert params["aux1_fc1"]["w"].shape == (128 * 4 * 4, 1024)
    logits, aux = jax.eval_shape(
        lambda p, x: vision.googlenet_forward(p, x, True, jax.random.key(1)),
        params, images)
    assert logits.shape == (2, 1000)
    assert [a.shape for a in aux] == [(2, 1000), (2, 1000)]
    assert jax.eval_shape(model.forward, params,
                          {"images": images}).shape == (2, 1000)
    loss, metrics = jax.eval_shape(
        lambda p, b: model.loss_fn(p, b, jax.random.key(1)), params,
        _batch(2, 224))
    assert loss.shape == metrics["aux"].shape == ()


def test_loss_compiles_at_224px():
    model = _model()
    params = jax.eval_shape(model.init, jax.random.key(0))
    assert jax.jit(model.loss_fn).lower(params, _batch(1, 224)).compile()


def test_ceil_pool_pads_only_where_it_adds_a_window():
    """Where rounding up and down agree (AlexNet's 55, 27, 13) the pool
    lowers as the VALID one; at 112 it gives 56 windows, the last taking
    the maximum of the two columns it covers."""
    for side in (55, 27, 13):
        x = jax.ShapeDtypeStruct((1, side, side, 8), jnp.float32)
        assert (jax.jit(lambda v: vision._maxpool(v, ceil=True)).lower(x)
                .as_text() == jax.jit(lambda v: vision._maxpool(v)).lower(x)
                .as_text())
    x = jax.random.normal(jax.random.key(0), (1, 112, 112, 2))
    y = vision._maxpool(x, ceil=True)
    assert y.shape == (1, 56, 56, 2)
    np.testing.assert_array_equal(y[:, :55, :55], vision._maxpool(x))
    np.testing.assert_array_equal(y[:, -1, -1],
                                  jnp.max(x[:, 110:, 110:], axis=(1, 2)))


def test_too_small_a_map_for_the_heads_fails_at_init():
    assert jax.eval_shape(_model(79, 10).init, jax.random.key(0))
    with pytest.raises(ValueError, match="inception 4a's 4x4 map"):
        jax.eval_shape(_model(64, 10).init, jax.random.key(0))


def test_modules_and_heads_are_named_in_the_step():
    """Each inception module and auxiliary head keeps its name scope in the
    compiled step's op metadata, under ``forward`` and ``backward``."""
    model = _model(96, 10)
    params = jax.eval_shape(model.init, jax.random.key(0))
    text = jax.jit(lambda p, b: _loss_and_grad(
        model, p, b, jax.random.key(1), False)).lower(
            params, _batch(2, 96)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in MODULES:
        assert any(f"forward/jvp({scope})" in n for n in names), scope
        assert any(f"backward/transpose(jvp({scope}))" in n
                   for n in names), scope

