"""ResNet-V1.5 (ResNet-50 and friends) in Flax — the flagship benchmark model.

Named in BASELINE.json's configs ("ResNet-50 JAX pod, google.com/tpu: 4").
TPU-first choices: NHWC, bfloat16 compute with float32 BatchNorm statistics
and float32 logits, stride-2 placed on the 3x3 (the V1.5 variant every
images/sec baseline uses), static shapes throughout so XLA tiles the convs
onto the MXU.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Sequence

import flax.linen as nn
import jax.numpy as jnp


class BottleneckBlock(nn.Module):
    features: int
    strides: tuple[int, int] = (1, 1)
    dtype: Any = jnp.bfloat16
    norm_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, *, train: bool):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        # norm_dtype is the BatchNorm OUTPUT dtype; flax computes the
        # batch statistics in float32 regardless (and scale/bias params
        # stay float32), so bf16 here only narrows the normalized
        # activations — halving the conv->BN->conv HBM traffic that
        # dominates the early high-resolution stages.
        norm = partial(
            nn.BatchNorm,
            use_running_average=not train,
            momentum=0.9,
            epsilon=1e-5,
            dtype=self.norm_dtype,
        )
        residual = x
        y = conv(self.features, (1, 1))(x)
        y = nn.relu(norm()(y))
        y = conv(self.features, (3, 3), strides=self.strides)(y)  # V1.5: stride here
        y = nn.relu(norm()(y))
        y = conv(self.features * 4, (1, 1))(y)
        y = norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = conv(self.features * 4, (1, 1), strides=self.strides)(residual)
            residual = norm()(residual)
        return nn.relu(y + residual.astype(y.dtype))


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    num_classes: int = 1000
    width: int = 64
    dtype: Any = jnp.bfloat16
    # BatchNorm OUTPUT dtype (batch statistics are float32 either way —
    # flax computes them upcast).  bf16 halves the conv->BN->conv
    # activation traffic; an A/B measured 2630 vs 2071 images/sec at b128
    # (+27%; builder session 2026-08-01, record deleted in PR 21, not
    # re-measured), so bf16 is the default.  Set float32 to reproduce
    # the old headline config.
    norm_dtype: Any = jnp.bfloat16
    # "conv7" (the standard 7x7/s2 stem) or "space_to_depth": pack 2x2
    # pixel blocks into channels ([H,W,3] -> [H/2,W/2,12]) and run a
    # 4x4/s1 conv — the same receptive-field geometry (a zero-padded 7x7
    # kernel maps onto it exactly; tests/test_models.py pins the
    # equivalence), but the MXU sees 12 input channels instead of 3 and
    # a quarter the spatial positions, so the stem tiles instead of
    # running ~3/8ths empty.  Opt-in pending a hardware A/B.
    stem: str = "conv7"

    @nn.compact
    def __call__(self, images, *, train: bool = False):
        x = images.astype(self.dtype)
        if self.stem == "space_to_depth":
            n, h, w, c = x.shape
            if h % 2 or w % 2:
                raise ValueError(
                    f"stem='space_to_depth' packs 2x2 pixel blocks and "
                    f"needs even spatial dims, got {h}x{w}; use stem="
                    f"'conv7' for odd sizes"
                )
            x = x.reshape(n, h // 2, 2, w // 2, 2, c)
            x = x.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // 2, w // 2, 4 * c)
            x = nn.Conv(
                self.width, (4, 4), strides=(1, 1), use_bias=False,
                dtype=self.dtype, name="Conv_stem",
            )(x)
        elif self.stem == "conv7":
            x = nn.Conv(
                self.width, (7, 7), strides=(2, 2), use_bias=False,
                dtype=self.dtype, name="Conv_stem",
            )(x)
        else:
            raise ValueError(
                f"stem must be 'conv7' or 'space_to_depth', got {self.stem!r}"
            )
        x = nn.BatchNorm(
            use_running_average=not train, momentum=0.9, epsilon=1e-5,
            dtype=self.norm_dtype,
        )(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for stage, n_blocks in enumerate(self.stage_sizes):
            for block in range(n_blocks):
                strides = (2, 2) if stage > 0 and block == 0 else (1, 1)
                x = BottleneckBlock(
                    self.width * 2**stage, strides=strides, dtype=self.dtype,
                    norm_dtype=self.norm_dtype,
                )(x, train=train)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(self.num_classes, dtype=jnp.float32)(x)


def ResNet50(**kwargs) -> ResNet:
    return ResNet(stage_sizes=(3, 4, 6, 3), **kwargs)


def ResNet18Thin(**kwargs) -> ResNet:
    """Tiny structural stand-in for CPU tests (same code paths, ~1000x fewer FLOPs)."""
    kwargs.setdefault("width", 8)
    return ResNet(stage_sizes=(1, 1, 1, 1), **kwargs)
