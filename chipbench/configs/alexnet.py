"""Plain reference of AlexNet as the benchmark runs it (``alexnet.json``).

The grouped topology of Krizhevsky et al. (2012) with local response
normalisation and dropout on f6 and f7: 60,965,224 parameters at 227 px and
1000 classes, the count in Table 2 of the Theano-MPI paper. Everything is
read from the configuration file; nothing is imported from the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from refops import (Ops, dropout, he_normal, lrn, maxpool, relu,
                    softmax_xent)


def _fc_width(conf, fc):
    return conf["num_classes"] if fc["out"] == "num_classes" else fc["out"]


def _features(params, x, ops: Ops, conf):
    pool, norm = conf["pool"], conf["lrn"]
    for c in conf["conv"]:
        x = relu(ops.conv(params[c["name"]], x, c["stride"], c["padding"],
                          c["groups"]))
        for what in c["after"]:
            if what == "lrn":
                x = lrn(x, **norm)
            else:
                x = maxpool(x, pool["kernel"], pool["stride"])
    return x


def forward(params, images, ops: Ops, conf, key=None):
    """Logits; ``key`` (the worker's step key) turns dropout on."""
    x = _features(params, ops.cast(images), ops, conf)
    x = x.reshape(x.shape[0], -1)
    for fc in conf["fc"][:-1]:
        x = relu(ops.dense(params[fc["name"]], x))
        if key is not None:
            x = dropout(x, jax.random.fold_in(key, fc["dropout_fold"]),
                        conf["dropout"])
    return ops.dense(params[conf["fc"][-1]["name"]], x)


def loss(params, images, labels, ops: Ops, conf, key=None):
    return softmax_xent(forward(params, images, ops, conf, key), labels)


def init_params(key, conf):
    """He-normal weights, zero biases, in the program's parameter layout."""
    keys = iter(jax.random.split(key, len(conf["conv"]) + len(conf["fc"])))
    params = {}
    for c in conf["conv"]:
        k, cin_g = c["kernel"], c["in"] // c["groups"]
        params[c["name"]] = {
            "w": he_normal(next(keys), (k, k, cin_g, c["out"]), k * k * cin_g),
            "b": jnp.zeros((c["out"],), jnp.float32)}
    side = conf["image_size"]
    feat = jax.eval_shape(
        lambda p: _features(p, jnp.zeros((1, side, side, 3)), Ops(), conf),
        params)
    cin = int(feat.shape[1] * feat.shape[2] * feat.shape[3])
    for fc in conf["fc"]:
        cout = _fc_width(conf, fc)
        params[fc["name"]] = {"w": he_normal(next(keys), (cin, cout), cin),
                              "b": jnp.zeros((cout,), jnp.float32)}
        cin = cout
    return params


def forward_flops_per_image(conf) -> int:
    """Forward operations for one image, two per multiply-accumulate of the
    convolutions and dense layers, counted from the layer shapes."""
    side = conf["image_size"]
    params = jax.eval_shape(lambda k: init_params(k, conf), jax.random.key(0))
    ops = Ops(count=True)
    jax.eval_shape(lambda p, x: forward(p, x, ops, conf), params,
                   jax.ShapeDtypeStruct((1, side, side, 3), jnp.float32))
    return 2 * ops.macs


def train_flops_per_image(conf) -> int:
    """Forward plus backward, the backward counted as twice the forward;
    nothing recomputed is counted."""
    return 3 * forward_flops_per_image(conf)
