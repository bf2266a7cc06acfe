"""ResNet family for ImageNet — BASELINE.json config 2.

The reference trains ResNet-50/ImageNet-1k through a Spark RDD image pipeline
on CUDA (SURVEY.md §2 'Models: ResNet-50'); its headline metric is
images/sec/chip and the north star is ≥50% MFU on a v4-32 pod.

TPU-first design decisions (vs. a torch translation):

- **NHWC layout** end to end — channels-last is what XLA:TPU tiles onto the
  MXU without relayout transposes (torch is NCHW).
- **bfloat16 compute, float32 state**: conv/matmul inputs and activations in
  bf16 feed the MXU at full rate; params, BN statistics and the final logits
  stay f32 for stable training. This is the standard TPU mixed-precision
  recipe — no loss-scaling machinery needed (unlike fp16 on GPU).
- **BatchNorm compute follows the activation dtype** (``norm_dtype=None`` →
  ``self.dtype``): flax upcasts the mean/var *statistics* to f32 internally
  and keeps scale/bias params f32 regardless, so only the normalize/affine
  elementwise math runs in bf16: an f32 BN sandwiched between bf16 convs
  pays a convert and double the bandwidth on every activation tensor.
  Set ``norm_dtype=jnp.float32`` to reproduce torch-default numerics; the
  weight-import parity tests get this implicitly by running the whole model
  at ``dtype=float32``, which the norm dtype follows.
- **v1.5 stride placement** (stride on the 3×3, not the 1×1) — the variant
  every published ResNet-50 benchmark uses.
- **Distributed BN for free**: under GSPMD the batch axis is sharded over the
  (data, fsdp) mesh axes, so BatchNorm's batch-mean lowers to a per-chip
  partial sum + an XLA all-reduce — the cross-replica sync-BN the reference
  would need explicit hooks for is just how the compiler partitions the mean.
"""

from __future__ import annotations

import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn


def _norm_dtype(norm_dtype, dtype):
    """BN compute dtype: explicit override, else follow the activation dtype."""
    return norm_dtype if norm_dtype is not None else dtype


class BottleneckBlock(nn.Module):
    """1×1 → 3×3 → 1×1 bottleneck with projection shortcut when needed."""

    filters: int  # bottleneck width; output channels = 4 * filters
    strides: int = 1
    dtype: Any = jnp.bfloat16
    norm_dtype: Any = None  # None → follow self.dtype (see module docstring)

    @nn.compact
    def __call__(self, x: jax.Array, *, train: bool) -> jax.Array:
        conv = functools.partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = functools.partial(
            nn.BatchNorm, use_running_average=not train, momentum=0.9,
            epsilon=1e-5, dtype=_norm_dtype(self.norm_dtype, self.dtype),
        )

        residual = x
        y = conv(self.filters, (1, 1))(x)
        y = nn.relu(norm()(y))
        # explicit (1,1) padding = torch semantics; flax SAME pads (0,1) on
        # stride-2, which would break pretrained-weight parity (resnet_io)
        y = conv(self.filters, (3, 3), strides=(self.strides, self.strides),
                 padding=[(1, 1), (1, 1)])(y)
        y = nn.relu(norm()(y))
        # zero-init gamma on the last BN: each block starts as identity,
        # the standard large-batch trick (Goyal et al.) — free accuracy.
        y = conv(4 * self.filters, (1, 1))(y)
        y = norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = conv(4 * self.filters, (1, 1), strides=(self.strides, self.strides),
                            name="shortcut_conv")(residual)
            residual = norm(name="shortcut_bn")(residual)
        return nn.relu(residual + y.astype(residual.dtype))


class BasicBlock(nn.Module):
    """3×3 → 3×3 block (ResNet-18/34)."""

    filters: int
    strides: int = 1
    dtype: Any = jnp.bfloat16
    norm_dtype: Any = None  # None → follow self.dtype (see module docstring)

    @nn.compact
    def __call__(self, x: jax.Array, *, train: bool) -> jax.Array:
        conv = functools.partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = functools.partial(
            nn.BatchNorm, use_running_average=not train, momentum=0.9,
            epsilon=1e-5, dtype=_norm_dtype(self.norm_dtype, self.dtype),
        )
        residual = x
        # explicit (1,1) padding = torch semantics (see BottleneckBlock)
        y = conv(self.filters, (3, 3), strides=(self.strides, self.strides),
                 padding=[(1, 1), (1, 1)])(x)
        y = nn.relu(norm()(y))
        y = conv(self.filters, (3, 3), padding=[(1, 1), (1, 1)])(y)
        y = norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = conv(self.filters, (1, 1), strides=(self.strides, self.strides),
                            name="shortcut_conv")(residual)
            residual = norm(name="shortcut_bn")(residual)
        return nn.relu(residual + y.astype(residual.dtype))


class ResNet(nn.Module):
    """Input: batch dict with ``image`` [B,H,W,3] float; returns logits f32.

    ``stage_sizes`` counts blocks per stage; stage widths are the classic
    64/128/256/512.
    """

    stage_sizes: Sequence[int]
    block_cls: type = BottleneckBlock
    num_classes: int = 1000
    width: int = 64
    dtype: Any = jnp.bfloat16
    norm_dtype: Any = None  # None → follow self.dtype (see module docstring)

    @nn.compact
    def __call__(self, batch: dict[str, jax.Array], *, train: bool = False) -> jax.Array:
        ndtype = _norm_dtype(self.norm_dtype, self.dtype)
        x = batch["image"].astype(self.dtype)
        x = nn.Conv(self.width, (7, 7), strides=(2, 2), padding=[(3, 3), (3, 3)],
                    use_bias=False, dtype=self.dtype, name="stem_conv")(x)
        x = nn.BatchNorm(use_running_average=not train, momentum=0.9, epsilon=1e-5,
                         dtype=ndtype, name="stem_bn")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)])
        for stage, n_blocks in enumerate(self.stage_sizes):
            for block in range(n_blocks):
                x = self.block_cls(
                    filters=self.width * 2**stage,
                    strides=2 if stage > 0 and block == 0 else 1,
                    dtype=self.dtype,
                    norm_dtype=self.norm_dtype,
                )(x, train=train)
        x = jnp.mean(x, axis=(1, 2))  # global average pool
        return nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(x)


def ResNet18(**kw) -> ResNet:
    return ResNet(stage_sizes=(2, 2, 2, 2), block_cls=BasicBlock, **kw)


def ResNet34(**kw) -> ResNet:
    return ResNet(stage_sizes=(3, 4, 6, 3), block_cls=BasicBlock, **kw)


def ResNet50(**kw) -> ResNet:
    return ResNet(stage_sizes=(3, 4, 6, 3), block_cls=BottleneckBlock, **kw)


def ResNet101(**kw) -> ResNet:
    return ResNet(stage_sizes=(3, 4, 23, 3), block_cls=BottleneckBlock, **kw)


def ResNet152(**kw) -> ResNet:
    return ResNet(stage_sizes=(3, 8, 36, 3), block_cls=BottleneckBlock, **kw)
