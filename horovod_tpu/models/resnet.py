"""ResNet v1.5 family — the benchmark workhorse.

The reference benchmarks Horovod with ResNet-50/101 synthetic throughput
(/root/reference/docs/benchmarks.rst:31-41,
examples/tensorflow2/tensorflow2_synthetic_benchmark.py). This is a fresh
flax implementation tuned for TPU:

- compute dtype bfloat16 (MXU-native), params float32;
- NHWC layout (XLA/TPU conv-friendly);
- BatchNorm stats are per-chip by default, matching Horovod's per-GPU BN;
  pass ``axis_name`` to synchronize them cross-chip (SyncBatchNorm,
  reference tensorflow/sync_batch_norm.py / torch/sync_batch_norm.py).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Sequence

import flax.linen as nn
import jax.numpy as jnp

ModuleDef = Any


def _same_pads(size: int, k: int, s: int) -> tuple:
    """TF-'SAME' padding for one spatial dim."""
    out = -(-size // s)
    total = max((out - 1) * s + k - size, 0)
    return total // 2, total - total // 2


class Im2ColConv(nn.Module):
    """2-D convolution as shifted-slice stacking + ONE matmul.

    Conv-free lowering for platforms whose native ``conv_general_dilated``
    path underperforms (benchmarks/probe_conv.py compares the two). Patch
    extraction is pure data movement: for each kernel tap (di, dj), a
    strided slice of the padded input; taps concatenate on the channel
    axis in (kh, kw, cin) order so the flattened kernel matches
    ``nn.Conv``'s ``(kh, kw, cin, cout)`` parameter exactly — state dicts
    interchange between the two implementations.
    """

    features: int
    kernel_size: tuple
    strides: tuple = (1, 1)
    padding: Any = "SAME"
    use_bias: bool = True
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        kh, kw = self.kernel_size
        sh, sw = self.strides if isinstance(self.strides, tuple) \
            else (self.strides, self.strides)
        cin = x.shape[-1]
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (kh, kw, cin, self.features), jnp.float32)
        x = x.astype(self.dtype)
        kernel = kernel.astype(self.dtype)

        n, h, w, _ = x.shape
        if self.padding == "SAME":
            ph, pw = _same_pads(h, kh, sh), _same_pads(w, kw, sw)
        elif self.padding == "VALID":
            ph = pw = (0, 0)
        else:
            ph, pw = self.padding
        x = jnp.pad(x, ((0, 0), ph, pw, (0, 0)))
        hp, wp = x.shape[1], x.shape[2]
        ho = (hp - kh) // sh + 1
        wo = (wp - kw) // sw + 1

        taps = []
        for di in range(kh):
            for dj in range(kw):
                taps.append(x[:, di:di + (ho - 1) * sh + 1:sh,
                              dj:dj + (wo - 1) * sw + 1:sw, :])
        patches = jnp.concatenate(taps, axis=-1)  # (n, ho, wo, kh*kw*cin)
        out = patches.reshape(n * ho * wo, kh * kw * cin) \
            @ kernel.reshape(kh * kw * cin, self.features)
        out = out.reshape(n, ho, wo, self.features)
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros,
                              (self.features,), jnp.float32)
            out = out + bias.astype(self.dtype)
        return out


# flax auto-names submodule scopes by class __name__; sharing nn.Conv's
# makes native and im2col param trees byte-interchangeable
Im2ColConv.__name__ = "Conv"


class BottleneckBlock(nn.Module):
    filters: int
    strides: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3), strides=(self.strides, self.strides))(y)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1),
                                 strides=(self.strides, self.strides),
                                 name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    axis_name: Optional[str] = None  # set to sync BN stats across chips
    # MLPerf-style TPU stem: 2x2 space-to-depth turns the MXU-hostile
    # 7x7/s2 conv on 3 channels (3 of 128 MXU lanes live) into a 4x4/s1
    # conv on 12 channels at half resolution — same downstream dims,
    # ~equal FLOPs, far better systolic-array utilization
    space_to_depth: bool = False
    # "native" = nn.Conv (XLA conv_general_dilated); "im2col" = Im2ColConv
    # (shifted-slice + matmul — for platforms with a degenerate native
    # conv path; parameters interchange between the two)
    conv_impl: str = "native"

    @nn.compact
    def __call__(self, x, train: bool = True):
        impls = {"native": nn.Conv, "im2col": Im2ColConv}
        if self.conv_impl not in impls:
            raise ValueError(
                f"conv_impl={self.conv_impl!r}; valid: {sorted(impls)}")
        conv = partial(impls[self.conv_impl], use_bias=False,
                       dtype=self.dtype)
        norm = partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, epsilon=1e-5, dtype=self.dtype,
                       axis_name=self.axis_name)
        x = x.astype(self.dtype)
        if self.space_to_depth:
            n, h, w, c = x.shape
            if h % 2 or w % 2:
                raise ValueError(
                    f"space_to_depth needs even spatial dims, got {h}x{w}")
            x = x.reshape(n, h // 2, 2, w // 2, 2, c)
            x = x.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // 2, w // 2,
                                                      4 * c)
            x = conv(self.num_filters, (4, 4), strides=(1, 1),
                     padding="SAME", name="conv_init_s2d")(x)
        else:
            x = conv(self.num_filters, (7, 7), strides=(2, 2),
                     padding=[(3, 3), (3, 3)], name="conv_init")(x)
        x = norm(name="bn_init")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = 2 if i > 0 and j == 0 else 1
                x = BottleneckBlock(self.num_filters * 2 ** i, strides,
                                    conv=conv, norm=norm, act=nn.relu)(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(x)
        return x.astype(jnp.float32)


def ResNet50(**kw) -> ResNet:
    return ResNet(stage_sizes=[3, 4, 6, 3], **kw)


def ResNet101(**kw) -> ResNet:
    return ResNet(stage_sizes=[3, 4, 23, 3], **kw)


def ResNet152(**kw) -> ResNet:
    return ResNet(stage_sizes=[3, 8, 36, 3], **kw)
