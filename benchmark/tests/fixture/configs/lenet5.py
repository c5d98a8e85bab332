"""Plain reference for the test fixture: LeNet-5 (LeCun et al. 1998) as the
zoo's `lenet5` lays it out: 5x5 valid convolutions of 6, 16 and 120 maps
with tanh, 2x2 average pools each followed by tanh, dense 84 with tanh,
dense 10; MNIST standardisation on the uint8 wire; softmax cross-entropy."""

from __future__ import annotations

import jax.numpy as jnp

from benchmark import refnn


class Reference:
    def __init__(self, config: dict):
        pass

    def prologue(self, batch, key, step):
        return (batch["image"].astype(jnp.float32) / 255.0 - 0.1307) / 0.3081

    def forward(self, params, x, operands="float32"):
        def conv(name, x):
            return refnn.conv2d(x, params[name + "/kernel"], 1, "VALID",
                                operands) + params[name + "/bias"]

        x = jnp.tanh(refnn.avg_pool(jnp.tanh(conv("Conv_0", x)), 2))
        x = jnp.tanh(refnn.avg_pool(jnp.tanh(conv("Conv_1", x)), 2))
        x = jnp.tanh(conv("Conv_2", x)).reshape(x.shape[0], -1)
        x = jnp.tanh(refnn.dense(x, params["Dense_0/kernel"],
                                 params["Dense_0/bias"], operands))
        return refnn.dense(x, params["Dense_1/kernel"], params["Dense_1/bias"],
                           operands)

    def loss(self, logits, batch):
        return refnn.softmax_xent(logits, batch["label"])
