"""Plain reference for the `resnet50` configuration.

ResNet-50 v1 (He et al., arXiv:1512.03385, Table 1, 50-layer): 7x7/2 stem,
3x3/2 max pool, four stages of bottleneck blocks (1x1 reduce, 3x3, 1x1
expand x4) with a projection shortcut on each stage's first block, global
average pool, 1000-way dense layer; batch normalisation after every
convolution, in training mode; softmax cross-entropy.

Departures from the paper, both the upstream recipe's (sayanmutd/deep-vision
`ResNet/pytorch/models/resnet50.py`): the stride of a stage's first block
sits on its 3x3 convolution, not on the first 1x1; a stride-2 3x3 pads one
pixel on each side.  The input is the recipe's ColorJitter(0.2, 0.2, 0.2)
in the fixed order brightness, contrast, saturation, then ImageNet
normalisation, on the uint8 wire.

Parameters arrive as one flat dict keyed by the program's leaf paths
(`BottleneckBlock_3/Conv_1/kernel`); that naming is the interface, the
layers behind it are written here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import refnn

MEAN = np.asarray([0.485, 0.456, 0.406], np.float32)
STD = np.asarray([0.229, 0.224, 0.225], np.float32)
GRAY = np.asarray([0.299, 0.587, 0.114], np.float32)


def color_jitter(x, key, amount: float = 0.2):
    """x in [0, 1], (B, H, W, 3).  One factor per image and per operation,
    uniform in [1 - amount, 1 + amount], drawn as (B, 1, 1, 1) from the three
    halves of ``key`` in the order brightness, contrast, saturation."""
    b = x.shape[0]
    kb, kc, ks = jax.random.split(key, 3)
    lo, hi = max(0.0, 1 - amount), 1 + amount
    fb = jax.random.uniform(kb, (b, 1, 1, 1), minval=lo, maxval=hi)
    fc = jax.random.uniform(kc, (b, 1, 1, 1), minval=lo, maxval=hi)
    fs = jax.random.uniform(ks, (b, 1, 1, 1), minval=lo, maxval=hi)
    x = x * fb
    m = x.mean(axis=(1, 2, 3), keepdims=True)
    x = (x - m) * fc + m
    gray = (x * GRAY).sum(-1, keepdims=True)
    x = gray + (x - gray) * fs
    return jnp.clip(x, 0.0, 1.0)


class Reference:
    def __init__(self, config: dict):
        self.stage_sizes = tuple(config["stage_sizes"])
        self.jitter = float(config.get("color_jitter", 0.2))

    def prologue(self, batch, key, step):
        """The step's key is the state's folded with the step counter, the
        prologue's that folded with 1 (the trainer's derivation)."""
        key = jax.random.fold_in(jax.random.fold_in(key, step), 1)
        x = batch["image"].astype(jnp.float32) / 255.0
        x = color_jitter(x, key, self.jitter)
        return (x - MEAN) / STD

    def forward(self, params, x, operands="float32"):
        def conv(name, x, stride=1, padding="SAME"):
            return refnn.conv2d(x, params[name + "/kernel"], stride, padding,
                                operands)

        def bn(name, x):
            return refnn.batchnorm_train(x, params[name + "/scale"],
                                         params[name + "/bias"])

        @jax.checkpoint
        def stem(x):
            x = conv("Conv_0", x, 2, ((3, 3), (3, 3)))
            x = jax.nn.relu(bn("BatchNorm_0", x))
            return refnn.max_pool(x, 3, 2, 1)

        def block(prefix, x, stride, project):
            def c(i, x, stride=1, padding="SAME"):
                return conv(f"{prefix}/Conv_{i}", x, stride, padding)

            def b(i, x):
                return bn(f"{prefix}/BatchNorm_{i}", x)

            y = jax.nn.relu(b(0, c(0, x)))
            y = jax.nn.relu(b(1, c(1, y, stride, ((1, 1), (1, 1)))))
            y = b(2, c(2, y))
            shortcut = b(3, c(3, x, stride)) if project else x
            return jax.nn.relu(y + shortcut)

        x = stem(x)
        index = 0
        for stage, blocks in enumerate(self.stage_sizes):
            for i in range(blocks):
                stride = 2 if stage > 0 and i == 0 else 1
                # remat block by block: the float32 activations of 256
                # images would not fit beside their gradients otherwise
                x = jax.checkpoint(
                    lambda x, p=f"BottleneckBlock_{index}", s=stride,
                    proj=(i == 0): block(p, x, s, proj))(x)
                index += 1
        x = jnp.mean(x, axis=(1, 2))
        return refnn.dense(x, params["Dense_0/kernel"], params["Dense_0/bias"],
                           operands)

    def loss(self, logits, batch):
        return refnn.softmax_xent(logits, batch["label"])
