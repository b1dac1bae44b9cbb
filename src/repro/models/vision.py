"""The paper's own benchmark convnets in pure JAX: AlexNet (grouped, to match
Table 2's 60,965,224 params), VGG-16 (138,357,544), GoogLeNet + both aux
classifiers (~13.38M). Used by the paper-faithful BSP experiments.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import softmax_xent


def _conv_init(key, kh, kw, cin, cout, groups=1):
    fan_in = kh * kw * cin // groups
    std = math.sqrt(2.0 / fan_in)
    w = jax.random.normal(key, (kh, kw, cin // groups, cout),
                          jnp.float32) * std
    return {"w": w, "b": jnp.zeros((cout,), jnp.float32)}


def _fc_init(key, cin, cout):
    std = math.sqrt(2.0 / cin)
    return {"w": jax.random.normal(key, (cin, cout), jnp.float32) * std,
            "b": jnp.zeros((cout,), jnp.float32)}


def _conv(p, x, stride=1, padding="SAME", groups=1):
    y = jax.lax.conv_general_dilated(
        x, p["w"], (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)
    return y + p["b"]


def _maxpool(x, k=3, s=2, padding="VALID", ceil=False):
    """``ceil``: Caffe's pooling, whose output size rounds up; the bottom and
    right get -inf padding only where that adds a window, so a map on which
    the two agree lowers to the plain VALID pool."""
    if ceil:
        padding = [(0, 0)] + [(0, -(-(n - k) // s) * s + k - n)
                              for n in x.shape[1:3]] + [(0, 0)]
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, k, k, 1), (1, s, s, 1), padding)


def _avgpool(x, k, s, padding="VALID"):
    y = jax.lax.reduce_window(x, 0.0, jax.lax.add,
                              (1, k, k, 1), (1, s, s, 1), padding)
    return y / (k * k)


def _gap(x):
    return jnp.mean(x, axis=(1, 2))


def _lrn(x, n=5, alpha=1e-4, beta=0.75, k=2.0):
    """Local response normalization (AlexNet)."""
    sq = jnp.square(x)
    # sum over a window of n channels
    pad = jnp.pad(sq, ((0, 0), (0, 0), (0, 0), (n // 2, n // 2)))
    acc = jnp.zeros_like(x)
    for i in range(n):
        acc = acc + pad[..., i:i + x.shape[-1]]
    return x / jnp.power(k + alpha * acc, beta)


# ---------------------------------------------------------------------------
# AlexNet (original grouped topology -> 60,965,224 params at 1000 classes)
# ---------------------------------------------------------------------------

def init_alexnet(key, cfg: ArchConfig):
    ks = jax.random.split(key, 8)
    C = cfg.num_classes
    p = {
        "c1": _conv_init(ks[0], 11, 11, 3, 96),
        "c2": _conv_init(ks[1], 5, 5, 96, 256, groups=2),
        "c3": _conv_init(ks[2], 3, 3, 256, 384),
        "c4": _conv_init(ks[3], 3, 3, 384, 384, groups=2),
        "c5": _conv_init(ks[4], 3, 3, 384, 256, groups=2),
    }
    feat = jax.eval_shape(
        lambda q: _alexnet_features(q, jnp.zeros(
            (1, cfg.image_size, cfg.image_size, 3), jnp.float32)), p)
    fdim = int(feat.shape[1] * feat.shape[2] * feat.shape[3])
    p["f6"] = _fc_init(ks[5], fdim, 4096)
    p["f7"] = _fc_init(ks[6], 4096, 4096)
    p["f8"] = _fc_init(ks[7], 4096, C)
    return p


def _alexnet_features(p, x):
    x = jax.nn.relu(_conv(p["c1"], x, stride=4, padding="VALID"))
    x = _maxpool(_lrn(x))
    x = jax.nn.relu(_conv(p["c2"], x, groups=2))
    x = _maxpool(_lrn(x))
    x = jax.nn.relu(_conv(p["c3"], x))
    x = jax.nn.relu(_conv(p["c4"], x, groups=2))
    x = jax.nn.relu(_conv(p["c5"], x, groups=2))
    return _maxpool(x)


def alexnet_forward(p, x, train: bool = False, rng=None):
    x = _alexnet_features(p, x)
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ p["f6"]["w"] + p["f6"]["b"])
    if train and rng is not None:
        x = x * jax.random.bernoulli(jax.random.fold_in(rng, 6), 0.5,
                                     x.shape) * 2.0
    x = jax.nn.relu(x @ p["f7"]["w"] + p["f7"]["b"])
    if train and rng is not None:
        x = x * jax.random.bernoulli(jax.random.fold_in(rng, 7), 0.5,
                                     x.shape) * 2.0
    return x @ p["f8"]["w"] + p["f8"]["b"]


# ---------------------------------------------------------------------------
# VGG-16 (138,357,544 params at 1000 classes)
# ---------------------------------------------------------------------------

_VGG16 = [(64, 2), (128, 2), (256, 3), (512, 3), (512, 3)]


def init_vgg16(key, cfg: ArchConfig):
    p = {}
    cin = 3
    i = 0
    for cout, reps in _VGG16:
        for r in range(reps):
            p[f"c{i}"] = _conv_init(jax.random.fold_in(key, i), 3, 3, cin,
                                    cout)
            cin = cout
            i += 1
    side = cfg.image_size // 32
    p["f0"] = _fc_init(jax.random.fold_in(key, 100), cin * side * side, 4096)
    p["f1"] = _fc_init(jax.random.fold_in(key, 101), 4096, 4096)
    p["f2"] = _fc_init(jax.random.fold_in(key, 102), 4096, cfg.num_classes)
    return p


def vgg16_forward(p, x, train: bool = False, rng=None):
    i = 0
    for cout, reps in _VGG16:
        for r in range(reps):
            x = jax.nn.relu(_conv(p[f"c{i}"], x))
            i += 1
        x = _maxpool(x, k=2, s=2)
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ p["f0"]["w"] + p["f0"]["b"])
    x = jax.nn.relu(x @ p["f1"]["w"] + p["f1"]["b"])
    return x @ p["f2"]["w"] + p["f2"]["b"]


# ---------------------------------------------------------------------------
# GoogLeNet (Inception v1, with both aux classifiers; Szegedy et al. 2014,
# Table 1 -> 13,378,280 params at 224 px and 1000 classes). What the paper
# leaves out follows BVLC's bvlc_googlenet: max pools round their output size
# up (maps 112, 56, 28, 14, 7 at 224 px), LRN over 5 channels with alpha 1e-4
# spread over the window and k = 1, dropout 0.4 before the classifier and 0.7
# in each auxiliary head, whose losses weigh 0.3.
# ---------------------------------------------------------------------------

# (1x1, 3x3red, 3x3, 5x5red, 5x5, pool_proj)
_INCEPTION = {
    "3a": (64, 96, 128, 16, 32, 32),
    "3b": (128, 128, 192, 32, 96, 64),
    "4a": (192, 96, 208, 16, 48, 64),
    "4b": (160, 112, 224, 24, 64, 64),
    "4c": (128, 128, 256, 24, 64, 64),
    "4d": (112, 144, 288, 32, 64, 64),
    "4e": (256, 160, 320, 32, 128, 128),
    "5a": (256, 160, 320, 32, 128, 128),
    "5b": (384, 192, 384, 48, 128, 128),
}
_POOL_AFTER = ("3b", "4e")
_AUX_AFTER = ("4a", "4d")           # heads aux0, aux1
_AUX_WEIGHT = 0.3
_LRN = {"alpha": 1e-4 / 5, "k": 1.0}
# (rate, N): a dropout draws its mask from fold_in(worker key, N), N being
# the number of Caffe's loss the layer feeds (loss1, loss2: aux0, aux1)
_DROPOUT = {"aux0": (0.7, 1), "aux1": (0.7, 2), "fc": (0.4, 3)}


def _init_inception(key, cin, spec):
    c1, r3, c3, r5, c5, pp = spec
    ks = jax.random.split(key, 6)
    return {
        "b1": _conv_init(ks[0], 1, 1, cin, c1),
        "b3r": _conv_init(ks[1], 1, 1, cin, r3),
        "b3": _conv_init(ks[2], 3, 3, r3, c3),
        "b5r": _conv_init(ks[3], 1, 1, cin, r5),
        "b5": _conv_init(ks[4], 5, 5, r5, c5),
        "bp": _conv_init(ks[5], 1, 1, cin, pp),
    }


def _inception(p, x):
    b1 = jax.nn.relu(_conv(p["b1"], x))
    b3 = jax.nn.relu(_conv(p["b3"], jax.nn.relu(_conv(p["b3r"], x))))
    b5 = jax.nn.relu(_conv(p["b5"], jax.nn.relu(_conv(p["b5r"], x))))
    bp = jax.nn.relu(_conv(p["bp"], _maxpool(x, k=3, s=1, padding="SAME")))
    return jnp.concatenate([b1, b3, b5, bp], axis=-1)


def _out_ch(spec):
    return spec[0] + spec[2] + spec[4] + spec[5]


def _dropout(x, rng, name):
    rate, fold = _DROPOUT[name]
    keep = 1.0 - rate
    mask = jax.random.bernoulli(jax.random.fold_in(rng, fold), keep, x.shape)
    return x * mask / keep


def _googlenet_trunk(p, x):
    """The map after inception 5b, and the maps the auxiliary heads take."""
    x = jax.nn.relu(_conv(p["c1"], x, stride=2))
    x = _lrn(_maxpool(x, ceil=True), **_LRN)
    x = jax.nn.relu(_conv(p["c2r"], x))
    x = jax.nn.relu(_conv(p["c2"], x))
    x = _maxpool(_lrn(x, **_LRN), ceil=True)
    taps = []
    for name in _INCEPTION:
        with jax.named_scope(f"inception_{name}"):
            x = _inception(p[f"i{name}"], x)
        if name in _POOL_AFTER:
            x = _maxpool(x, ceil=True)
        if name in _AUX_AFTER:
            taps.append(x)
    return x, taps


def init_googlenet(key, cfg: ArchConfig):
    C = cfg.num_classes
    p = {
        "c1": _conv_init(jax.random.fold_in(key, 0), 7, 7, 3, 64),
        "c2r": _conv_init(jax.random.fold_in(key, 1), 1, 1, 64, 64),
        "c2": _conv_init(jax.random.fold_in(key, 2), 3, 3, 64, 192),
    }
    cin = 192
    for i, (name, spec) in enumerate(_INCEPTION.items()):
        p[f"i{name}"] = _init_inception(jax.random.fold_in(key, 10 + i),
                                        cin, spec)
        cin = _out_ch(spec)
    p["fc"] = _fc_init(jax.random.fold_in(key, 50), cin, C)
    # each head's width from the map the trunk gives it (512 channels at
    # 4a, 528 at 4d; 14x14, pooled to 4x4, at 224 px)
    side = cfg.image_size
    _, taps = jax.eval_shape(lambda q: _googlenet_trunk(
        q, jnp.zeros((1, side, side, 3), jnp.float32)), p)
    for j, tap in enumerate(taps):
        _, h, w, cin_aux = jax.eval_shape(lambda t: _avgpool(t, 5, 3),
                                          tap).shape
        if h * w == 0:
            raise ValueError(
                f"googlenet at {side} px: inception {_AUX_AFTER[j]}'s "
                f"{tap.shape[1]}x{tap.shape[2]} map is smaller than the "
                f"auxiliary head's 5x5 pool")
        p[f"aux{j}_conv"] = _conv_init(jax.random.fold_in(key, 60 + j),
                                       1, 1, cin_aux, 128)
        p[f"aux{j}_fc1"] = _fc_init(jax.random.fold_in(key, 62 + j),
                                    128 * h * w, 1024)
        p[f"aux{j}_fc2"] = _fc_init(jax.random.fold_in(key, 64 + j), 1024, C)
    return p


def _aux_head(p, j, x, rng):
    x = _avgpool(x, 5, 3)
    x = jax.nn.relu(_conv(p[f"aux{j}_conv"], x))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ p[f"aux{j}_fc1"]["w"] + p[f"aux{j}_fc1"]["b"])
    if rng is not None:
        x = _dropout(x, rng, f"aux{j}")
    return x @ p[f"aux{j}_fc2"]["w"] + p[f"aux{j}_fc2"]["b"]


def googlenet_forward(p, x, train: bool = False, rng=None):
    """Returns (logits, [aux0_logits, aux1_logits]); the heads run only in
    training, and ``rng`` (with ``train``) turns dropout on."""
    rng = rng if train else None
    x, taps = _googlenet_trunk(p, x)
    aux = []
    if train:
        for j, tap in enumerate(taps):
            with jax.named_scope(f"aux{j}"):
                aux.append(_aux_head(p, j, tap, rng))
    x = _gap(x)
    if rng is not None:
        x = _dropout(x, rng, "fc")
    logits = x @ p["fc"]["w"] + p["fc"]["b"]
    return logits, aux


# ---------------------------------------------------------------------------
# unified interface
# ---------------------------------------------------------------------------

def init_conv(key, cfg: ArchConfig):
    return {"alexnet": init_alexnet, "vgg16": init_vgg16,
            "googlenet": init_googlenet}[cfg.conv_arch](key, cfg)


def conv_loss(params, batch, cfg: ArchConfig, rng=None, *, unroll=False):
    """batch: {images: (B,H,W,3), labels: (B,)}."""
    x, labels = batch["images"], batch["labels"]
    aux = jnp.zeros((), jnp.float32)
    if cfg.conv_arch == "googlenet":
        logits, aux_logits = googlenet_forward(params, x, train=True, rng=rng)
        aux = _AUX_WEIGHT * sum(softmax_xent(a, labels) for a in aux_logits)
        loss = softmax_xent(logits, labels) + aux
    elif cfg.conv_arch == "alexnet":
        logits = alexnet_forward(params, x, train=True, rng=rng)
        loss = softmax_xent(logits, labels)
    else:
        logits = vgg16_forward(params, x, train=True, rng=rng)
        loss = softmax_xent(logits, labels)
    return loss, {"loss": loss, "aux": aux}


def conv_predict(params, x, cfg: ArchConfig):
    if cfg.conv_arch == "googlenet":
        return googlenet_forward(params, x)[0]
    if cfg.conv_arch == "alexnet":
        return alexnet_forward(params, x)
    return vgg16_forward(params, x)
