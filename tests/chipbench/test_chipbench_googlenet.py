"""GoogLeNet's plain reference (``configs/googlenet.py``) against the
program on seeded random weights, at 96 px and 10 classes, and its FLOP
count at 224 px."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cbtiny

cbtiny.use_harness()

import spec  # noqa: E402
from refops import Ops  # noqa: E402

CONF = json.loads((cbtiny.CHIPBENCH / "configs" / "googlenet.json")
                  .read_text())
TINY = dict(CONF, name="googlenet-tiny", image_size=96, num_classes=10,
            reduced=["image_size", "num_classes", "params"])


def _reference():
    return spec._load_module(cbtiny.CHIPBENCH / "configs" / "googlenet.py",
                             "googlenet_ref")


def _program(conf):
    from repro.configs import get_config
    from repro.models import build_model
    return build_model(dataclasses.replace(
        get_config("googlenet"), image_size=conf["image_size"],
        num_classes=conf["num_classes"]))


@pytest.mark.parametrize("conf", [CONF, TINY], ids=["224px", "96px"])
def test_reference_fills_the_programs_parameter_tree(conf):
    ref = _reference()
    want = jax.eval_shape(_program(conf).init, jax.random.key(0))
    got = jax.eval_shape(lambda k: ref.init_params(k, conf),
                         jax.random.key(0))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert [l.shape for l in jax.tree.leaves(got)] == [
        l.shape for l in jax.tree.leaves(want)]
    if conf is CONF:
        assert sum(l.size for l in jax.tree.leaves(got)) == 13_378_280


@pytest.fixture(scope="module")
def tiny_outputs():
    """The program's and the reference's logits, loss and gradients on one
    seeded batch of 4 images, dropout on, both at float32."""
    from repro.models import vision
    ref = _reference()
    model = _program(TINY)
    params = ref.init_params(jax.random.key(3), TINY)
    key = jax.random.key(5)
    x = jax.random.normal(jax.random.fold_in(key, 0), (4, 96, 96, 3))
    y = jax.random.randint(jax.random.fold_in(key, 1), (4,), 0, 10)
    rng = jax.random.key(9)
    with jax.default_matmul_precision("highest"):
        (loss, metrics), grads = jax.jit(jax.value_and_grad(
            lambda p: model.loss_fn(p, {"images": x, "labels": y}, rng),
            has_aux=True))(params)
        logits = jax.jit(lambda p: vision.googlenet_forward(
            p, x, train=True, rng=rng))(params)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, x, y, Ops(), TINY, rng)))(params)
        ref_logits = jax.jit(lambda p: ref.forward(p, x, Ops(), TINY,
                                                   rng))(params)
    return {"loss": (loss, ref_loss), "aux": metrics["aux"],
            "logits": ([logits[0]] + logits[1],
                       [ref_logits[0]] + ref_logits[1]),
            "grads": (grads, ref_grads), "labels": y}


def test_program_matches_the_reference(tiny_outputs):
    """Both compute in float32 with the same weights, batch and dropout
    keys; they differ only in the order of their sums (LRN's window, the
    convolutions' algorithms), a few float32 roundings: measured gaps are
    under 1e-6 relative. A dropped head or dropout moves them by order 1."""
    got, want = tiny_outputs["logits"]
    assert len(got) == len(want) == 3
    for a, b in zip(got, want):
        assert a.shape == b.shape == (4, 10)
        # 1e-5 of the largest logit: ten times the float32 gaps measured
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * float(
            jnp.max(jnp.abs(b)))
    loss, ref_loss = tiny_outputs["loss"]
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    grads, ref_grads = tiny_outputs["grads"]
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(ref_grads)):
        # the norm of each leaf's gap, relative to the leaf's norm: 1e-4
        # leaves a hundredfold room over the measured 1e-6
        gap = float(jnp.linalg.norm(g - r))
        assert gap <= 1e-4 * float(jnp.linalg.norm(r)), (
            jax.tree_util.keystr(path), gap)


def test_loss_reports_the_weighted_aux_losses(tiny_outputs):
    """``aux`` is 0.3 times each head's loss, summed, and the loss holds
    it; the heads are not the classifier."""
    from repro.models.common import softmax_xent
    logits = tiny_outputs["logits"][0]
    y = tiny_outputs["labels"]
    heads = [float(softmax_xent(a, y)) for a in logits[1:]]
    np.testing.assert_allclose(float(tiny_outputs["aux"]),
                               0.3 * sum(heads), rtol=1e-5)
    loss = float(tiny_outputs["loss"][0])
    np.testing.assert_allclose(
        loss, float(softmax_xent(logits[0], y)) + 0.3 * sum(heads),
        rtol=1e-5)
    assert not np.allclose(np.asarray(logits[1]), np.asarray(logits[2]))


def test_flops_match_xla_cost_analysis():
    """The configuration's count from its layer shapes, auxiliary heads
    included, against XLA's own count of the program's training forward
    (the loss, heads and all) at batch 1: they differ only by the
    elementwise operations, pools and LRN, which the FLOP function leaves
    out (0.8% at 224 px)."""
    ref = _reference()
    model = _program(CONF)
    params = jax.eval_shape(model.init, jax.random.key(0))
    side = CONF["image_size"]
    batch = {"images": jax.ShapeDtypeStruct((1, side, side, 3), jnp.float32),
             "labels": jax.ShapeDtypeStruct((1,), jnp.int32)}
    xla = jax.jit(model.loss_fn).lower(params, batch).cost_analysis()["flops"]
    ours = ref.forward_flops_per_image(CONF)
    assert ours <= xla <= 1.01 * ours
    assert ref.train_flops_per_image(CONF) == 3 * ours
