"""Plain float32 reference of a bottleneck ResNet's training-mode
forward pass and loss.

Written from He et al., arXiv:1512.03385 (section 3.4 and Table 1: a 7x7
stride-2 stem, BatchNorm and ReLU, a 3x3 stride-2 max pool, stages of
bottleneck blocks 1x1 -> 3x3 -> 1x1 with four times the width at the
exit and a 1x1 projection on the shortcut where the shape changes,
global average pool, one dense classifier), with the departures the
configuration file lists: the stride of a down-sampling block sits on
its 3x3 convolution (v1.5), and BatchNorm normalises by the statistics
of the batch it is given (training mode, one chip's shard; variance
without Bessel's correction, epsilon 1e-5).

Straightforward ``jax.lax`` in float32 at ``Precision.HIGHEST`` (on a
TPU a float32 convolution runs as bfloat16 passes unless told
otherwise): no flax, no mixed precision, no fusion hints.

The parameter tree is the program's (``models/resnet.py``, flax's
names): ``conv_init.kernel [7,7,3,f]``, ``bn_init.{scale,bias}``,
``BottleneckBlock_<k>`` with ``Conv_0..2.kernel``,
``BatchNorm_0..2.{scale,bias}`` and, where the shape changes,
``conv_proj.kernel`` and ``norm_proj.{scale,bias}``, ``head.{kernel,
bias}``. Kernels are ``[h, w, in, out]``, images ``[n, h, w, c]``.
"""

import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5


def conv(x, kernel, stride: int, padding="SAME"):
    return lax.conv_general_dilated(
        x, kernel.astype(jnp.float32), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)


def batch_norm(x, p):
    mean = x.mean(axis=(0, 1, 2))
    var = jnp.square(x - mean).mean(axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + BN_EPS) * p["scale"] + p["bias"]


def relu(x):
    return jnp.maximum(x, 0.0)


def max_pool_3x3_stride_2(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                             (1, 2, 2, 1),
                             ((0, 0), (1, 1), (1, 1), (0, 0)))


def bottleneck(x, p, stride: int):
    y = relu(batch_norm(conv(x, p["Conv_0"]["kernel"], 1), p["BatchNorm_0"]))
    y = relu(batch_norm(conv(y, p["Conv_1"]["kernel"], stride),
                        p["BatchNorm_1"]))
    y = batch_norm(conv(y, p["Conv_2"]["kernel"], 1), p["BatchNorm_2"])
    if "conv_proj" in p:
        x = batch_norm(conv(x, p["conv_proj"]["kernel"], stride),
                       p["norm_proj"])
    return relu(x + y)


def logits(params, images, stage_sizes):
    x = conv(images.astype(jnp.float32), params["conv_init"]["kernel"], 2,
             [(3, 3), (3, 3)])
    x = max_pool_3x3_stride_2(relu(batch_norm(x, params["bn_init"])))
    k = 0
    for stage, blocks in enumerate(stage_sizes):
        for j in range(blocks):
            x = bottleneck(x, params[f"BottleneckBlock_{k}"],
                           2 if stage > 0 and j == 0 else 1)
            k += 1
    head = params["head"]
    return jnp.dot(x.mean(axis=(1, 2)), head["kernel"],
                   precision=lax.Precision.HIGHEST) + head["bias"]


def loss_and_logits(params, images, labels, stage_sizes):
    """Mean softmax cross-entropy of integer ``labels``, and the logits."""
    out = logits(params, images, stage_sizes)
    shifted = out - out.max(axis=-1, keepdims=True)
    logp = shifted - jnp.log(jnp.exp(shifted).sum(axis=-1, keepdims=True))
    picked = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return -picked.mean(), out
