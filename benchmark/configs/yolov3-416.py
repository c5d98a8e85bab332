"""Plain reference for the `yolov3-416` configuration.

YOLOv3 (Redmon & Farhadi, arXiv:1804.02767): Darknet-53 (Table 1: a 3x3 of
32, then five stride-2 3x3 convolutions of 64..1024 maps followed by 1, 2,
8, 8, 4 residual pairs of 1x1 half-width and 3x3 full-width), every
convolution followed by batch normalisation (training mode) and leaky ReLU
0.1; three detection scales (13, 26, 52 at 416), each a 1-3-1-3-1 neck, a
3x3 and a linear 1x1 to 3 x (5 + classes) channels; the coarser neck feeds
the next through a 1x1, a nearest x2 upsample and a concatenation with the
backbone's route.  A stride-2 convolution pads one pixel top and left
(darknet's).

Loss, the upstream recipe's (sayanmutd/deep-vision
`YOLO/tensorflow/yolov3.py` YoloLoss): per image and scale, squared error
on sigmoid(t_xy) against the cell offset and on t_wh against
log(wh / anchor), both x5 and x(2 - w h), on cells that hold an object;
logistic objectness, cells without an object x0.5 and left out where their
predicted box overlaps some true box of that image by IoU >= 0.5; logistic
class loss on cells that hold an object.  Summed over cells, averaged over
the batch, added over the scales.  The input is uint8 / 255.

Parameters arrive as one flat dict keyed by the program's leaf paths; the
layers behind the names are written here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import refnn


def iou_with_truth(pred, truth, mask):
    """pred (B, N, 4), truth (B, M, 4) corners, mask (B, M): best IoU of each
    prediction with a true box of its image, (B, N)."""
    p, t = pred[:, :, None, :], truth[:, None, :, :]
    wh = jnp.maximum(jnp.minimum(p[..., 2:], t[..., 2:])
                     - jnp.maximum(p[..., :2], t[..., :2]), 0.0)
    inter = wh[..., 0] * wh[..., 1]

    def area(b):
        return (jnp.maximum(b[..., 2] - b[..., 0], 0.0)
                * jnp.maximum(b[..., 3] - b[..., 1], 0.0))

    iou = inter / (area(p) + area(t) - inter + 1e-9)
    return jnp.max(jnp.where(mask[:, None, :] > 0, iou, 0.0), axis=-1)


class Reference:
    def __init__(self, config: dict):
        self.blocks = tuple(config["residual_blocks"])
        self.classes = int(config["num_classes"])
        anchors = np.asarray(config["anchors"], np.float32) / float(
            config["anchor_base"])
        self.anchors = anchors.reshape(len(config["strides"]), -1, 2)
        self.ignore = float(config["ignore_iou"])

    def prologue(self, batch, key, step):
        return batch["image"].astype(jnp.float32) / 255.0

    # ------------------------------------------------------------ forward
    def forward(self, params, x, operands="float32"):
        def unit(prefix, x, k, stride=1):
            """convolution, batch norm, leaky ReLU; rematerialised."""
            def f(x, kernel, scale, bias):
                if stride == 2:
                    x = jnp.pad(x, ((0, 0), (1, 0), (1, 0), (0, 0)))
                y = refnn.conv2d(x, kernel, stride,
                                 "VALID" if stride == 2 else "SAME", operands)
                y = refnn.batchnorm_train(y, scale, bias)
                return jnp.where(y > 0, y, 0.1 * y)

            return jax.checkpoint(f)(
                x, params[prefix + "/Conv_0/kernel"],
                params[prefix + "/BatchNorm_0/scale"],
                params[prefix + "/BatchNorm_0/bias"])

        def residual(prefix, x):
            y = unit(prefix + "/DarknetConv_0", x, 1)
            return x + unit(prefix + "/DarknetConv_1", y, 3)

        back = "Darknet53_0"
        x = unit(f"{back}/DarknetConv_0", x, 3)
        routes, r = [], 0
        for i, n in enumerate(self.blocks):
            def stage(x, i=i, n=n, r=r):
                x = unit(f"{back}/DarknetConv_{i + 1}", x, 3, 2)
                for j in range(n):
                    x = residual(f"{back}/DarknetResidual_{r + j}", x)
                return x

            # a second level of remat, stage by stage: the float32
            # activations of 64 images at 416x416 are 13.6 GiB otherwise
            x = jax.checkpoint(stage)(x)
            r += n
            routes.append(x)
        small, medium, large = routes[-3], routes[-2], routes[-1]

        @functools.partial(jax.checkpoint, static_argnums=(0,))
        def neck(i, x):
            for j, k in enumerate((1, 3, 1, 3, 1)):
                x = unit(f"YoloConvBlock_{i}/DarknetConv_{j}", x, k)
            return x

        def head(i, x):
            x = unit(f"YoloHead_{i}/DarknetConv_0", x, 3)
            y = refnn.conv2d(x, params[f"YoloHead_{i}/Conv_0/kernel"], 1,
                             "SAME", operands) + params[f"YoloHead_{i}/Conv_0/bias"]
            b, h, w, _ = y.shape
            return y.reshape(b, h, w, -1, 5 + self.classes)

        def up(x):
            return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)

        x13 = neck(0, large)
        out13 = head(0, x13)
        x26 = neck(1, jnp.concatenate(
            [up(unit("DarknetConv_0", x13, 1)), medium], axis=-1))
        out26 = head(1, x26)
        x52 = neck(2, jnp.concatenate(
            [up(unit("DarknetConv_1", x26, 1)), small], axis=-1))
        out52 = head(2, x52)
        return out52, out26, out13  # fine grid first, as the anchors are listed

    # --------------------------------------------------------------- loss
    def scale_loss(self, raw, y_true, boxes, mask, anchors):
        grid = raw.shape[1]
        cells = jnp.arange(grid, dtype=jnp.float32)
        cy, cx = jnp.meshgrid(cells, cells, indexing="ij")
        offset = jnp.stack([cx, cy], axis=-1)[None, :, :, None, :]
        pred_xy = jax.nn.sigmoid(raw[..., 0:2])
        box_xy = (pred_xy + offset) / grid
        box_wh = jnp.exp(jnp.clip(raw[..., 2:4], -9.0, 9.0)) * anchors
        corners = jnp.concatenate([box_xy - box_wh / 2, box_xy + box_wh / 2], -1)

        true_xy, true_wh = y_true[..., 0:2], y_true[..., 2:4]
        obj = y_true[..., 4]
        t_xy = true_xy * grid - jnp.floor(true_xy * grid)
        t_wh = jnp.where(true_wh <= 1e-9, 0.0,
                         jnp.log(jnp.maximum(true_wh, 1e-9) / anchors))
        weight = obj * (2.0 - true_wh[..., 0] * true_wh[..., 1])
        xy = 5.0 * (weight * jnp.square(t_xy - pred_xy).sum(-1)).sum((1, 2, 3))
        wh = 5.0 * (weight * jnp.square(t_wh - raw[..., 2:4]).sum(-1)).sum((1, 2, 3))

        b = raw.shape[0]
        best = iou_with_truth(
            jax.lax.stop_gradient(corners.reshape(b, -1, 4)), boxes, mask)
        background = (best.reshape(obj.shape) < self.ignore).astype(jnp.float32)
        objectness = refnn.sigmoid_xent(raw[..., 4], obj)
        found = (obj * objectness).sum((1, 2, 3))
        empty = 0.5 * ((1 - obj) * objectness * background).sum((1, 2, 3))
        cls = (obj[..., None] * refnn.sigmoid_xent(raw[..., 5:], y_true[..., 5:])
               ).sum((1, 2, 3, 4))
        return jnp.mean(xy + wh + found + empty + cls)

    def loss(self, outputs, batch):
        return sum(
            self.scale_loss(raw, batch[f"y_true_{s}"], batch["boxes"],
                            batch["boxes_mask"], self.anchors[s])
            for s, raw in enumerate(outputs))
