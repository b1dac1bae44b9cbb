"""Plain reference of GoogLeNet as the benchmark runs it (``googlenet.json``).

Inception v1 of Szegedy et al. (2014), Table 1, with both auxiliary
classifiers: 13,378,280 parameters at 224 px and 1000 classes, the count in
Table 2 of the Theano-MPI paper. Max pools round their output size up, as
BVLC's Caffe model does, so the maps are 112, 56, 28, 14 and 7; the
training loss is the classifier's plus each auxiliary head's times its
weight, with dropout in all three. Everything is read from the
configuration file; nothing is imported from the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from refops import (Ops, avgpool, dropout, he_normal, lrn, maxpool, relu,
                    softmax_xent)

# Table 1's columns, by the name each branch's layer has in the program's
# parameter layout
BRANCHES = (("b1", "1x1"), ("b3r", "3x3_reduce"), ("b3", "3x3"),
            ("b5r", "5x5_reduce"), ("b5", "5x5"), ("bp", "pool_proj"))


def maxpool_up(x, k, s):
    """Max pooling whose output size rounds up: -inf rows and columns
    below and to the right complete the last window."""
    pads = [-(-(n - k) // s) * s + k - n for n in x.shape[1:3]]
    x = jnp.pad(x, ((0, 0), (0, pads[0]), (0, pads[1]), (0, 0)),
                constant_values=-jnp.inf)
    return maxpool(x, k, s)


def _inception(p, x, ops: Ops, conf):
    bp = conf["branch_pool"]
    one = relu(ops.conv(p["b1"], x))
    three = relu(ops.conv(p["b3"], relu(ops.conv(p["b3r"], x))))
    five = relu(ops.conv(p["b5"], relu(ops.conv(p["b5r"], x))))
    pooled = maxpool(x, bp["kernel"], bp["stride"], bp["padding"])
    proj = relu(ops.conv(p["bp"], pooled))
    return jnp.concatenate([one, three, five, proj], axis=-1)


def _trunk(params, x, ops: Ops, conf):
    """The map after the last inception module, and the map each auxiliary
    head takes, by the head's name."""
    pool, norm = conf["pool"], conf["lrn"]
    for c in conf["stem"]:
        x = relu(ops.conv(params[c["name"]], x, c["stride"], c["padding"]))
        for what in c["after"]:
            x = (lrn(x, **norm) if what == "lrn"
                 else maxpool_up(x, pool["kernel"], pool["stride"]))
    taps = {}
    for m in conf["inception"]:
        x = _inception(params[m["name"]], x, ops, conf)
        for what in m["after"]:
            if what == "pool":
                x = maxpool_up(x, pool["kernel"], pool["stride"])
            else:
                taps[what] = x
    return x, taps


def _aux_input(x, head):
    return avgpool(x, head["pool"]["kernel"], head["pool"]["stride"])


def _aux(params, x, ops: Ops, head, key):
    name = head["name"]
    x = relu(ops.conv(params[f"{name}_conv"], _aux_input(x, head)))
    x = relu(ops.dense(params[f"{name}_fc1"], x.reshape(x.shape[0], -1)))
    if key is not None:
        x = dropout(x, jax.random.fold_in(key, head["dropout_fold"]),
                    head["dropout"])
    return ops.dense(params[f"{name}_fc2"], x)


def forward(params, images, ops: Ops, conf, key=None):
    """(logits, [each auxiliary head's logits]), as in training; ``key``
    (the worker's step key) turns dropout on."""
    x, taps = _trunk(params, ops.cast(images), ops, conf)
    aux = [_aux(params, taps[h["name"]], ops, h, key) for h in conf["aux"]]
    x = jnp.mean(x, axis=(1, 2))
    cls = conf["classifier"]
    if key is not None:
        x = dropout(x, jax.random.fold_in(key, cls["dropout_fold"]),
                    cls["dropout"])
    return ops.dense(params[cls["name"]], x), aux


def loss(params, images, labels, ops: Ops, conf, key=None):
    logits, aux = forward(params, images, ops, conf, key)
    total = softmax_xent(logits, labels)
    for head, a in zip(conf["aux"], aux):
        total = total + head["loss_weight"] * softmax_xent(a, labels)
    return total


def _conv_param(key, k, cin, cout):
    return {"w": he_normal(key, (k, k, cin, cout), k * k * cin),
            "b": jnp.zeros((cout,), jnp.float32)}


def _dense_param(key, cin, cout):
    return {"w": he_normal(key, (cin, cout), cin),
            "b": jnp.zeros((cout,), jnp.float32)}


def init_params(key, conf):
    """He-normal weights, zero biases, in the program's parameter layout."""
    n = (len(conf["stem"]) + len(BRANCHES) * len(conf["inception"]) + 1
         + 3 * len(conf["aux"]))
    keys = iter(jax.random.split(key, n))
    params = {c["name"]: _conv_param(next(keys), c["kernel"], c["in"],
                                     c["out"]) for c in conf["stem"]}
    for m in conf["inception"]:
        cin = m["in"]
        width = {"b1": (1, cin, m["1x1"]),
                 "b3r": (1, cin, m["3x3_reduce"]),
                 "b3": (3, m["3x3_reduce"], m["3x3"]),
                 "b5r": (1, cin, m["5x5_reduce"]),
                 "b5": (5, m["5x5_reduce"], m["5x5"]),
                 "bp": (1, cin, m["pool_proj"])}
        params[m["name"]] = {b: _conv_param(next(keys), *width[b])
                             for b, _ in BRANCHES}
    side = conf["image_size"]
    feat, taps = jax.eval_shape(
        lambda p: _trunk(p, jnp.zeros((1, side, side, 3)), Ops(), conf),
        params)
    params[conf["classifier"]["name"]] = _dense_param(
        next(keys), feat.shape[-1], conf["num_classes"])
    for head in conf["aux"]:
        name = head["name"]
        pooled = jax.eval_shape(lambda t: _aux_input(t, head),
                                taps[name]).shape
        if pooled[1] * pooled[2] == 0:
            raise ValueError(f"{conf['name']}: the map {name} takes, "
                             f"{taps[name].shape[1:3]}, is smaller than its "
                             f"pool")
        params[f"{name}_conv"] = _conv_param(next(keys), 1, pooled[3],
                                             head["conv"])
        params[f"{name}_fc1"] = _dense_param(
            next(keys), head["conv"] * pooled[1] * pooled[2], head["fc"])
        params[f"{name}_fc2"] = _dense_param(next(keys), head["fc"],
                                             conf["num_classes"])
    return params


def forward_flops_per_image(conf) -> int:
    """Forward operations for one image, two per multiply-accumulate of the
    convolutions and dense layers, counted from the layer shapes; the
    auxiliary heads run in every training step and are counted."""
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
