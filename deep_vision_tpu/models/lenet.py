"""LeNet-5 — parity with LeNet/pytorch/models/lenet5.py:14-67 and
LeNet/tensorflow/models/lenet5.py:7-34.

C1 conv6@5×5 → tanh → S2 avgpool2 → tanh → C3 conv16@5×5 → tanh →
S4 avgpool2 → tanh → C5 conv120@5×5 → tanh → F6 dense84 → tanh → dense10.
Input: 32×32×1 NHWC (MNIST padded 28→32).  61,706 params.
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp
from flax import linen as nn


class LeNet5Big(nn.Module):
    """A deliberately heavy MNIST-shape classifier — the cascade's BIG
    tier opposite LeNet-5 (serve/cascade.py, tests/cascade_smoke.py).

    Same 32×32×1 input and class count as LeNet-5 so the two tiers are
    interchangeable on the wire, but VGG-style doubled-conv blocks with
    ``width``× the channels and a wide head: ~50× the FLOPs/params of
    LeNet-5 at width 32 — the compute ratio the reference zoo spans
    between its mobile and server models, reproduced at a size CPU
    hosts can still bench."""

    num_classes: int = 10
    width: int = 32
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.astype(self.dtype)
        for mult in (1, 2, 4):  # 32→16→8→4 after the pools
            ch = self.width * mult
            x = nn.Conv(ch, (3, 3), padding="SAME", dtype=self.dtype)(x)
            x = nn.relu(x)
            x = nn.Conv(ch, (3, 3), padding="SAME", dtype=self.dtype)(x)
            x = nn.relu(x)
            x = nn.max_pool(x, (2, 2), (2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(8 * self.width, dtype=self.dtype)(x)
        x = nn.relu(x)
        x = nn.Dense(self.num_classes, dtype=self.dtype)(x)
        return x.astype(jnp.float32)


class LeNet5Nano(nn.Module):
    """A deliberately tiny MNIST-shape classifier — the N-tier
    cascade's tier-0 below LeNet-5 (serve/cascade.py).

    Same 32×32×1 input and class count as the other two so all three
    tiers are interchangeable on the wire: one strided conv8@5×5 →
    pool → dense, ~5K params (~12× fewer than LeNet-5) — the
    mobile-below-mobile end of the reference zoo's compute span."""

    num_classes: int = 10
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.astype(self.dtype)
        x = nn.Conv(8, (5, 5), strides=(2, 2), padding="VALID",
                    dtype=self.dtype)(x)                               # 32→14
        x = nn.relu(x)
        x = nn.avg_pool(x, (2, 2), (2, 2))                             # 14→7
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(self.num_classes, dtype=self.dtype)(x)
        return x.astype(jnp.float32)


class LeNet5(nn.Module):
    num_classes: int = 10
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.astype(self.dtype)
        x = nn.Conv(6, (5, 5), padding="VALID", dtype=self.dtype)(x)   # 32→28
        x = nn.tanh(x)
        x = nn.avg_pool(x, (2, 2), (2, 2))                             # 28→14
        x = nn.tanh(x)
        x = nn.Conv(16, (5, 5), padding="VALID", dtype=self.dtype)(x)  # 14→10
        x = nn.tanh(x)
        x = nn.avg_pool(x, (2, 2), (2, 2))                             # 10→5
        x = nn.tanh(x)
        x = nn.Conv(120, (5, 5), padding="VALID", dtype=self.dtype)(x)  # 5→1
        x = nn.tanh(x)
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(84, dtype=self.dtype)(x)
        x = nn.tanh(x)
        x = nn.Dense(self.num_classes, dtype=self.dtype)(x)
        return x.astype(jnp.float32)
