"""Small convnet for MNIST-class workloads.

The BASELINE.md headline config is "MNIST CNN"; the reference itself ships
only the MLP (reference initializer.py:14-19) and hints at uncommitted
CIFAR-10 experiments (reference .gitignore:1-4).  Conv layers map directly
onto the MXU; keep channel counts multiples of 8 for good tiling.
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp


class CNN(nn.Module):
    num_classes: int = 10
    features: tuple[int, ...] = (32, 64)
    dense: int = 128
    dropout_rate: float = 0.25
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.astype(self.dtype)
        if x.ndim == 3:  # (B, H, W) → add channel dim
            x = x[..., None]
        for feat in self.features:
            x = nn.Conv(feat, (3, 3), padding="SAME", dtype=self.dtype)(x)
            x = nn.relu(x)
            x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(self.dense, dtype=self.dtype)(x)
        x = nn.relu(x)
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        x = nn.Dense(self.num_classes, dtype=self.dtype)(x)
        return x.astype(jnp.float32)
