"""Plain jax.numpy layers for the benchmark's reference models.

Nothing here imports the program. ``Ops`` computes every layer at one
precision: ``float32`` (float32 arrays at ``Precision.HIGHEST``) for the
reference, or ``float8_e4m3fn`` (bfloat16 arrays whose convolution and
matrix operands are rounded to float8 first, at the default precision)
for a control that stands below it.
When ``count`` is on, each convolution and dense layer adds its
multiply-accumulates to ``macs`` (a convolution's taps on padding are not
counted), so running a forward under ``jax.eval_shape`` counts the model's
operations from its layer shapes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


class Ops:
    def __init__(self, compute: str = "float32", count: bool = False):
        if compute not in ("float32", "float8_e4m3fn"):
            raise ValueError(f"unknown precision {compute!r}")
        self.dtype = jnp.dtype("float32" if compute == "float32"
                               else "bfloat16")
        self.operand = jnp.dtype(compute)
        self.precision = (lax.Precision.HIGHEST if compute == "float32"
                          else lax.Precision.DEFAULT)
        self.count = count
        self.macs = 0

    def cast(self, x):
        return x.astype(self.dtype)

    def operands(self, *xs):
        """Matrix operands at the operand precision, held in ``dtype``."""
        return [self.cast(x.astype(self.operand)) for x in xs]

    def conv(self, p, x, stride=1, padding="SAME", groups=1):
        x, w = self.operands(x, p["w"])
        y = lax.conv_general_dilated(
            x, w, (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, precision=self.precision)
        if self.count:
            kh, kw, cin_g, cout = w.shape
            taps = (_taps(x.shape[1], y.shape[1], kh, stride, padding)
                    * _taps(x.shape[2], y.shape[2], kw, stride, padding))
            self.macs += int(x.shape[0]) * cout * cin_g * taps
        return y + self.cast(p["b"])

    def dense(self, p, x):
        x, w = self.operands(x, p["w"])
        y = jnp.matmul(x, w, precision=self.precision)
        if self.count:
            self.macs += int(x.shape[0]) * int(w.shape[0]) * int(w.shape[1])
        return y + self.cast(p["b"])


def _taps(size: int, out: int, k: int, stride: int, padding: str) -> int:
    """Kernel taps that fall inside the input, summed over the output
    positions of one spatial dimension: taps on padding multiply zeros and
    are not counted."""
    pad = max((out - 1) * stride + k - size, 0) // 2 if padding == "SAME" else 0
    return sum(min(o * stride - pad + k, size) - max(o * stride - pad, 0)
               for o in range(out))


def relu(x):
    return jnp.maximum(x, 0)


def maxpool(x, k=3, s=2, padding="VALID"):
    return lax.reduce_window(x, -jnp.inf, lax.max,
                             (1, k, k, 1), (1, s, s, 1), padding)


def avgpool(x, k, s):
    total = lax.reduce_window(x, 0.0, lax.add,
                              (1, k, k, 1), (1, s, s, 1), "VALID")
    return total / (k * k)


def lrn(x, n=5, alpha=1e-4, beta=0.75, k=2.0):
    """Krizhevsky et al. (2012), section 3.3: each activation is divided by
    (k + alpha * sum of squares over n adjacent channels) ** beta."""
    window = lax.reduce_window(jnp.square(x), 0.0, lax.add,
                               (1, 1, 1, n), (1, 1, 1, 1),
                               ((0, 0), (0, 0), (0, 0), (n // 2, n // 2)))
    return x / jnp.power(k + alpha * window, beta)


def dropout(x, key, rate=0.5):
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return x * keep.astype(x.dtype) / jnp.asarray(1.0 - rate, x.dtype)


def softmax_xent(logits, labels):
    """Mean cross-entropy, computed in float32 whatever the logits' dtype."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


def he_normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * jnp.sqrt(2.0 / fan_in)
