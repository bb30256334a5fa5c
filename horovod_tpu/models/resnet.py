"""ResNet v1.5 in flax, for the reference benchmark protocol.

The reference benchmarks ResNet-50 via torchvision
(``examples/pytorch_synthetic_benchmark.py:14-49``) and ResNet-101 via
tf_cnn_benchmarks (``docs/benchmarks.md:19-38``). This is the TPU-side
equivalent model: NHWC layout (TPU-native), bf16 compute with f32 params
(MXU-friendly mixed precision), BatchNorm with axis-name-aware cross-replica
statistics off by default (the reference, like most DP setups, uses
per-replica BN statistics).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from . import scopes

ModuleDef = Any


def _normed(norm: ModuleDef, x, **fields):
    """``norm(**fields)(x)``, the call under the component scope."""
    with jax.named_scope(scopes.NORM):
        return norm(**fields)(x)


class ResNetBlock(nn.Module):
    """Basic two-conv residual block (ResNet-18/34)."""

    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), self.strides)(x)
        y = _normed(self.norm, y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3))(y)
        y = _normed(self.norm, y, scale_init=nn.initializers.zeros_init())
        if residual.shape != y.shape:
            residual = self.conv(self.filters, (1, 1), self.strides,
                                 name="conv_proj")(residual)
            residual = _normed(self.norm, residual, name="norm_proj")
        return self.act(residual + y)


class BottleneckResNetBlock(nn.Module):
    """1x1 -> 3x3 -> 1x1 bottleneck (ResNet-50/101/152)."""

    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = _normed(self.norm, y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = _normed(self.norm, y)
        y = self.act(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = _normed(self.norm, y, scale_init=nn.initializers.zeros_init())
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1), self.strides,
                                 name="conv_proj")(residual)
            residual = _normed(self.norm, residual, name="norm_proj")
        return self.act(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    act: Callable = nn.relu

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = partial(nn.BatchNorm, use_running_average=not train,
                       momentum=0.9, epsilon=1e-5, dtype=self.dtype)
        x = x.astype(self.dtype)
        x = conv(self.num_filters, (7, 7), (2, 2),
                 padding=[(3, 3), (3, 3)], name="conv_init")(x)
        x = _normed(norm, x, name="bn_init")
        x = self.act(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, block_size in enumerate(self.stage_sizes):
            for j in range(block_size):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = self.block_cls(self.num_filters * 2 ** i,
                                   strides=strides, conv=conv, norm=norm,
                                   act=self.act)(x)
        with jax.named_scope(scopes.HEAD):
            x = jnp.mean(x, axis=(1, 2))
            x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
            return x.astype(jnp.float32)


ResNet18 = partial(ResNet, stage_sizes=[2, 2, 2, 2], block_cls=ResNetBlock)
ResNet34 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=ResNetBlock)
ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3],
                   block_cls=BottleneckResNetBlock)
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3],
                    block_cls=BottleneckResNetBlock)
ResNet152 = partial(ResNet, stage_sizes=[3, 8, 36, 3],
                    block_cls=BottleneckResNetBlock)
