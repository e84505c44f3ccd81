"""ResNet-20 for CIFAR-10 — the reference's ghost second workload.

The reference never committed its CIFAR-10 experiments (reference
.gitignore:1-4 lists `cifar10.py`, `cifar10_train.py`), but BASELINE.md
names "CIFAR-10 ResNet-20, -m centralized -cs async" as a benchmark config.
Classic He et al. CIFAR variant: 3 stages × 3 basic blocks, widths 16/32/64.

TPU notes: BatchNorm is replaced by GroupNorm so the step function stays a
pure params→params map with no mutable batch-stats collection — no
cross-device batch-stat sync needed (the usual BN-under-DP footgun), and the
engines' single-pytree TrainState stays uniform across models.
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp


class BasicBlock(nn.Module):
    filters: int
    strides: int = 1
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        residual = x
        y = nn.Conv(self.filters, (3, 3), strides=(self.strides, self.strides),
                    padding="SAME", use_bias=False, dtype=self.dtype)(x)
        y = nn.GroupNorm(num_groups=8, dtype=self.dtype)(y)
        y = nn.relu(y)
        y = nn.Conv(self.filters, (3, 3), padding="SAME", use_bias=False,
                    dtype=self.dtype)(y)
        y = nn.GroupNorm(num_groups=8, dtype=self.dtype)(y)
        if residual.shape != y.shape:
            residual = nn.Conv(self.filters, (1, 1),
                               strides=(self.strides, self.strides),
                               use_bias=False, dtype=self.dtype)(residual)
            residual = nn.GroupNorm(num_groups=8, dtype=self.dtype)(residual)
        return nn.relu(y + residual)


class ResNet20(nn.Module):
    num_classes: int = 10
    widths: tuple[int, ...] = (16, 32, 64)
    blocks_per_stage: int = 3
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.astype(self.dtype)
        if x.ndim == 3:
            x = x[..., None]
        x = nn.Conv(self.widths[0], (3, 3), padding="SAME", use_bias=False,
                    dtype=self.dtype)(x)
        x = nn.GroupNorm(num_groups=8, dtype=self.dtype)(x)
        x = nn.relu(x)
        for stage, width in enumerate(self.widths):
            for block in range(self.blocks_per_stage):
                strides = 2 if stage > 0 and block == 0 else 1
                x = BasicBlock(width, strides, dtype=self.dtype)(x)
        x = x.mean(axis=(1, 2))  # global average pool
        x = nn.Dense(self.num_classes, dtype=self.dtype)(x)
        return x.astype(jnp.float32)
