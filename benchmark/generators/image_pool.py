"""The one general traffic generator for training cells: a pool of host
batches made from the seed, in the format the program's loaders yield.

It reads nothing but the cell's traffic file (``traffic``) and the sizes in
its configuration file (``config``); a new mix is a new data file.  Every
seed gives the same shapes and the same number of batches, only other
pixels, labels and boxes, so a seed never changes the amount of work.

``labels: "class"``  image uint8 (B, S, S, C) + label int32 (B,).  Under the
noise every image carries its class's own coarse pattern (``pattern_cells``
squared cells, each channel either 0 or ``pattern_level``), so the label can
be learnt and the loss falls at the published learning rate.

``labels: "boxes"``  the detection batch: image uint8 (B, S, S, 3), the three
dense target grids ``y_true_0..2`` (B, G, G, A, 5 + classes) float32 (centre
x, y, w, h in [0, 1], objectness, one-hot class; a box goes to the grid of
the anchor its shape overlaps best, Redmon & Farhadi arXiv:1804.02767 s2.1),
``boxes`` (B, max_boxes, 4) corners, ``boxes_mask`` and ``gt_classes``.
Each box is painted in its class's colour where the label says it is.
"""

from __future__ import annotations

import numpy as np


def make_pool(config: dict, traffic: dict, seed: int) -> list[dict]:
    rng = np.random.default_rng(int(seed))
    kind = traffic["labels"]
    if kind == "class":
        make = _class_batch
    elif kind == "boxes":
        make = _boxes_batch
    else:
        raise ValueError(f"unknown labels kind {kind!r}")
    shared = _shared(config, traffic, rng)
    return [make(config, traffic, rng, shared)
            for _ in range(int(traffic["pool_batches"]))]


def _shared(config, traffic, rng) -> dict:
    """What all batches of one seed share: the classes' patterns or colours."""
    classes, ch = int(config["num_classes"]), int(config["channels"])
    cells = int(traffic.get("pattern_cells", 1))
    level = int(traffic["pattern_level"])
    return {"pattern": (rng.integers(0, 2, (classes, cells, cells, ch),
                                     dtype=np.uint8) * level).astype(np.uint8)}


def _noise(rng, shape, levels: int) -> np.ndarray:
    return rng.integers(0, levels, shape, dtype=np.uint8)


def _class_batch(config, traffic, rng, shared) -> dict:
    b, s, ch = (int(config["batch_size"]), int(config["image_size"]),
                int(config["channels"]))
    cells = int(traffic["pattern_cells"])
    if s % cells:
        raise ValueError(f"pattern_cells {cells} does not divide {s}")
    label = rng.integers(0, int(config["num_classes"]), b, dtype=np.int32)
    up = s // cells
    pattern = shared["pattern"][label].repeat(up, axis=1).repeat(up, axis=2)
    image = _noise(rng, (b, s, s, ch), int(traffic["noise_levels"]))
    image += pattern  # noise_levels + pattern_level <= 256: no wrap
    return {"image": image, "label": label}


def best_anchor(wh: np.ndarray, anchors: np.ndarray) -> np.ndarray:
    """Index of the anchor whose shape has the largest IoU with each box,
    both centred on one point."""
    inter = (np.minimum(wh[:, None, 0], anchors[None, :, 0])
             * np.minimum(wh[:, None, 1], anchors[None, :, 1]))
    union = (wh[:, 0] * wh[:, 1])[:, None] + anchors[:, 0] * anchors[:, 1] - inter
    return np.argmax(inter / union, axis=1)


def _boxes_batch(config, traffic, rng, shared) -> dict:
    b, s = int(config["batch_size"]), int(config["image_size"])
    classes = int(config["num_classes"])
    max_boxes = int(config["max_boxes"])
    grids = [s // int(st) for st in config["strides"]]
    anchors = np.asarray(config["anchors"], np.float32) / float(
        config["anchor_base"])
    per_scale = len(anchors) // len(grids)
    lo, hi = traffic["boxes_per_image"]
    wlo, whi = traffic["box_side"]

    image = _noise(rng, (b, s, s, 3), int(traffic["noise_levels"]))
    out = {f"y_true_{i}": np.zeros((b, g, g, per_scale, 5 + classes),
                                   np.float32) for i, g in enumerate(grids)}
    boxes = np.zeros((b, max_boxes, 4), np.float32)
    mask = np.zeros((b, max_boxes), np.float32)
    gt_classes = np.zeros((b, max_boxes), np.int32)
    counts = rng.integers(lo, hi + 1, b)
    for i in range(b):
        n = int(counts[i])
        wh = np.exp(rng.uniform(np.log(wlo), np.log(whi), (n, 2))
                    ).astype(np.float32)
        xy = (wh / 2 + rng.uniform(0, 1, (n, 2)) * (1 - wh)).astype(np.float32)
        cls = rng.integers(0, classes, n)
        boxes[i, :n] = np.concatenate([xy - wh / 2, xy + wh / 2], axis=1)
        mask[i, :n] = 1.0
        gt_classes[i, :n] = cls
        px = np.clip(np.rint(boxes[i, :n] * s).astype(int), 0, s)
        for j in range(n):  # later boxes paint over earlier ones
            x0, y0, x1, y1 = px[j]
            image[i, y0:y1, x0:x1] = (
                image[i, y0:y1, x0:x1] // 2 + shared["pattern"][cls[j], 0, 0])
        best = best_anchor(wh, anchors)
        for k, g in enumerate(grids):
            sel = best // per_scale == k
            if not sel.any():
                continue
            gx = np.minimum((xy[sel, 0] * g).astype(int), g - 1)
            gy = np.minimum((xy[sel, 1] * g).astype(int), g - 1)
            a = best[sel] % per_scale
            y = out[f"y_true_{k}"][i]
            y[gy, gx, a, 0:2] = xy[sel]
            y[gy, gx, a, 2:4] = wh[sel]
            y[gy, gx, a, 4] = 1.0
            y[gy, gx, a, 5 + cls[sel]] = 1.0
    return {"image": image, **out, "boxes": boxes, "boxes_mask": mask,
            "gt_classes": gt_classes}
