"""Plain reference of the bottleneck ResNet's training-mode loss.

Straightforward ``jax.numpy`` in float32 at the highest matmul precision:
no ``torchmpi_tpu`` import, no flax module, no bf16.  It reads the same
parameter tree the library's model owns (flax names: ``conv_init``,
``bn_init``, ``BottleneckBlock_<n>``, ``Dense_0``) and follows He et al.
(arXiv:1512.03385) with the stride on the 3x3 convolution, as
fb.resnet.torch and ``models/resnet.py`` have it.  BatchNorm normalises
with the statistics of the batch it is given, so a batch that the step
splits over ``shards`` devices is ``shards`` forward passes whose losses
are averaged.
"""

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-5
HIGHEST = lax.Precision.HIGHEST


def _conv(x, w, stride=1):
    return lax.conv_general_dilated(
        x, w.astype(jnp.float32), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)


def _bn(x, p):
    mean = x.mean((0, 1, 2))
    var = jnp.square(x - mean).mean((0, 1, 2))
    return (x - mean) * lax.rsqrt(var + EPS) * p["scale"] + p["bias"]


def _bottleneck(x, p, stride):
    y = jax.nn.relu(_bn(_conv(x, p["Conv_0"]["kernel"]), p["BatchNorm_0"]))
    y = jax.nn.relu(_bn(_conv(y, p["Conv_1"]["kernel"], stride),
                        p["BatchNorm_1"]))
    y = _bn(_conv(y, p["Conv_2"]["kernel"]), p["BatchNorm_2"])
    if "conv_proj" in p:
        x = _bn(_conv(x, p["conv_proj"]["kernel"], stride), p["norm_proj"])
    return jax.nn.relu(x + y)


def logits(params, images, stage_sizes):
    x = _conv(images.astype(jnp.float32), params["conv_init"]["kernel"], 2)
    x = jax.nn.relu(_bn(x, params["bn_init"]))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    n = 0
    for i, blocks in enumerate(stage_sizes):
        for j in range(blocks):
            x = _bottleneck(x, params[f"BottleneckBlock_{n}"],
                            2 if i > 0 and j == 0 else 1)
            n += 1
    x = x.mean((1, 2))
    head = params["Dense_0"]
    return jnp.dot(x, head["kernel"], precision=HIGHEST) + head["bias"]


def loss(params, images, labels, *, stage_sizes, shards=1):
    """Mean softmax cross-entropy of the first training step, averaged
    over ``shards`` equal slices of the batch (one a device)."""

    def one(batch):
        im, lb = batch
        lg = logits(params, im, stage_sizes)
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        return (lse - jnp.take_along_axis(lg, lb[:, None], 1)[:, 0]).mean()

    split = lambda a: a.reshape((shards, -1) + a.shape[1:])  # noqa: E731
    return lax.map(one, (split(images), split(labels))).mean()
