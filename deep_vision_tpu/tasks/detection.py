"""Detection task: YOLOv3 box codecs, loss, label encoding, postprocess.

Parity map (all in /root/reference/YOLO/tensorflow/):
- decode/encode: ``get_absolute_yolo_box`` yolov3.py:238-326,
  ``get_relative_yolo_box`` :329-349
- loss: ``YoloLoss`` :352-552 (xy/wh L2 in t-space ×(2-w·h)×λ_coord=5,
  obj/noobj BCE with ignore-mask IoU>0.5, λ_noobj=0.5, per-anchor class BCE)
- label encoding: preprocess.py:137-269 — reimplemented as one vectorized
  scatter over boxes instead of the reference's per-box Python loop
- postprocess: postprocess.py:12-96 → ops.boxes.batched_nms

TPU notes: the ignore mask compares pred boxes against a FIXED-SIZE padded
list of ground-truth boxes per image (batch["boxes"], mask in
batch["boxes_mask"]) — the reference's ``tf.boolean_mask`` is dynamic-shaped
(and mixes images across the batch); this formulation is static, per-image
correct, and vmap-free.  Layout rule: the 5+C axis of ``raw`` / ``y_true`` is
the lane axis on the chip, so ``x[..., k]`` on a full-size array is a pass
over all of it that fills 1-2 of 128 lanes.  ``yolo_scale_loss`` moves that
axis off the lanes once (``_channel_major``: cells on the lanes, the batch on
the sublanes) and only ever slices whole planes; ``tests/test_detection.py``
holds the rule on the traced loss.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deep_vision_tpu.models.yolo import ANCHOR_MASKS, YOLO_ANCHORS
from deep_vision_tpu.ops.boxes import batched_nms, broadcast_iou, xywh_to_corners

MAX_BOXES = 100  # static per-image ground-truth capacity


def decode_boxes(raw, anchors_wh):
    """t-space raw head output → (normalized xywh boxes, obj, classes).

    raw: (B, G, G, A, 5+C).  bx = (σ(tx)+Cx)/G;  bwh = anchor·e^t  —
    yolov3.py:238-326.
    """
    grid = raw.shape[1]
    t_xy, t_wh, obj, cls = jnp.split(raw, (2, 4, 5), axis=-1)
    cy, cx = jnp.meshgrid(jnp.arange(grid, dtype=jnp.float32),
                          jnp.arange(grid, dtype=jnp.float32), indexing="ij")
    c_xy = jnp.stack([cx, cy], axis=-1)[None, :, :, None, :]  # (1,G,G,1,2)
    b_xy = (jax.nn.sigmoid(t_xy) + c_xy) / grid
    b_wh = jnp.exp(jnp.clip(t_wh, -9.0, 9.0)) * anchors_wh
    return (jnp.concatenate([b_xy, b_wh], -1),
            jax.nn.sigmoid(obj), jax.nn.sigmoid(cls))


def encode_boxes(xywh, anchors_wh, eps: float = 1e-9):
    """normalized xywh → t-space targets (inverse of decode; :329-349)."""
    grid = xywh.shape[1]
    xy, wh = xywh[..., :2], xywh[..., 2:4]
    t_xy = xy * grid - jnp.floor(xy * grid)  # σ(tx) value, cell offset
    t_wh = jnp.log(jnp.maximum(wh, eps) / anchors_wh)
    t_wh = jnp.where(wh <= eps, 0.0, t_wh)  # empty cells → 0 target
    return t_xy, t_wh


def _bce(logit_or_prob, target, from_probs: bool, eps: float = 1e-7):
    if from_probs:
        p = jnp.clip(logit_or_prob, eps, 1 - eps)
        return -(target * jnp.log(p) + (1 - target) * jnp.log(1 - p))
    return jnp.maximum(logit_or_prob, 0) - logit_or_prob * target + \
        jnp.log1p(jnp.exp(-jnp.abs(logit_or_prob)))


def _channel_major(x, merged: bool):
    """(B, G, G, A, 5+C) → (A, 5+C, B, G·G): the one pass that takes the
    channel axis off the lanes; every piece is then a slice of planes.

    Two spellings of one move, so that XLA compiles each operand's to a
    single copy out of the layout it arrives in (read off the step compiled
    for the v5e: the other spelling costs either operand two more passes).
    ``merged``: the head conv's output, A·(5+C) on its lanes — moved as a
    (B, G·G, A·(5+C)) array and split on the major side, which is free.
    Otherwise ``y_true`` as the host hands it over, the batch between A
    and 5+C on the chip — moved as the 4-D array it is."""
    B, G, _, A, C = x.shape
    if merged:
        x = jnp.transpose(x.reshape(B, G * G, A * C), (2, 0, 1))
        return x.reshape(A, C, B, G * G)
    return jnp.transpose(x.reshape(B, G * G, A, C), (2, 3, 0, 1))


def yolo_scale_loss(raw, y_true, gt_boxes, gt_mask, anchors_wh,
                    ignore_thresh: float = 0.5, lambda_coord: float = 5.0,
                    lambda_noobj: float = 0.5, use_pallas: bool = False,
                    mesh=None):
    """Loss for ONE scale.

    raw: (B,G,G,A,5+C) head output; y_true: same shape, absolute xywh +
    obj + one-hot; gt_boxes: (B,MAX_BOXES,4) corner boxes; gt_mask: (B,M).
    Returns (total (B,), components dict).

    Computed on channel-major planes: ``decode_boxes`` / ``encode_boxes``
    written out per plane of an (A, 5+C, B, G·G) array, the cells on the
    minor axis (see the module's layout rule).
    """
    B, grid, _, A, _ = raw.shape
    cell = np.arange(grid * grid)
    c_xy = np.stack([cell % grid, cell // grid]).astype(np.float32)
    c_xy = c_xy[:, None, :]                                   # (2, 1, G·G)
    anchors = jnp.asarray(anchors_wh)[:, :, None, None]      # (A, 2, 1, 1)

    raw = _channel_major(raw, merged=True)
    y_true = _channel_major(y_true, merged=False)
    raw_wh, raw_obj = raw[:, 2:4], raw[:, 4]
    true_xy, true_wh, obj = y_true[:, 0:2], y_true[:, 2:4], y_true[:, 4]

    pred_xy_rel = jax.nn.sigmoid(raw[:, 0:2])
    pred_xy = (pred_xy_rel + c_xy) / grid
    pred_wh = jnp.exp(jnp.clip(raw_wh, -9.0, 9.0)) * anchors
    true_xy_rel = true_xy * grid - jnp.floor(true_xy * grid)
    eps = 1e-9  # encode_boxes': empty cells → 0 target
    true_wh_rel = jnp.log(jnp.maximum(true_wh, eps) / anchors)
    true_wh_rel = jnp.where(true_wh <= eps, 0.0, true_wh_rel)

    # small-box upweighting (2 - w·h), darknet yolo_layer.c:190 via :405-407
    weight = obj * (2.0 - true_wh[:, 0] * true_wh[:, 1])      # (A, B, G·G)
    xy_loss = jnp.square(true_xy_rel - pred_xy_rel).sum(1)
    xy_loss = (weight * xy_loss).sum((0, 2)) * lambda_coord
    wh_loss = jnp.square(true_wh_rel - raw_wh).sum(1)
    wh_loss = (weight * wh_loss).sum((0, 2)) * lambda_coord

    # ignore mask: preds overlapping ANY same-image gt > thresh are not
    # penalized as background (yolov3.py:438-459, static-shape version).
    # stop_gradient: the mask is a hard threshold (zero gradient anyway) and
    # pallas_call has no autodiff rule — without this the Pallas path fails
    # to linearize under value_and_grad.
    corners = jnp.concatenate(
        [pred_xy - pred_wh / 2.0, pred_xy + pred_wh / 2.0], 1)  # (A,4,B,G·G)
    corners = jax.lax.stop_gradient(
        jnp.transpose(corners, (2, 1, 0, 3)).reshape(B, 4, -1))  # (B, 4, N)
    # one scope over both implementations, so a trace reads the same work
    # under the same name whichever runs
    with jax.named_scope("best_iou"):
        if use_pallas:
            # fused tiled kernel (ops/pallas_ops.py) — avoids the (B,N,M)
            # HBM intermediate.  pallas_call has no GSPMD partitioning
            # rule, so a sharded mesh routes through a shard_map over the
            # data axis (the reduction is per-image independent);
            # single-device calls the kernel directly.
            from deep_vision_tpu.ops.pallas_ops import (
                best_iou_max_auto,
                best_iou_max_sharded,
            )

            if mesh is not None and mesh.devices.size > 1:
                best_iou = best_iou_max_sharded(corners, gt_boxes, gt_mask,
                                                mesh)
            else:
                best_iou = best_iou_max_auto(corners, gt_boxes, gt_mask)
        else:
            iou = broadcast_iou(jnp.swapaxes(corners, 1, 2), gt_boxes)
            iou = jnp.where(gt_mask[:, None, :] > 0, iou, 0.0)  # (B, N, M)
            best_iou = iou.max(-1)
    best_iou = jnp.swapaxes(best_iou.reshape(B, A, -1), 0, 1)  # (A, B, G·G)
    ignore = (best_iou < ignore_thresh).astype(jnp.float32)

    obj_entropy = _bce(raw_obj, obj, from_probs=False)
    obj_loss = (obj * obj_entropy).sum((0, 2))
    noobj_loss = ((1 - obj) * obj_entropy * ignore).sum((0, 2)) * lambda_noobj

    class_entropy = _bce(raw[:, 5:], y_true[:, 5:], from_probs=False)
    class_loss = (obj[:, None] * class_entropy).sum((0, 1, 3))

    total = xy_loss + wh_loss + obj_loss + noobj_loss + class_loss
    return total, {"xy": xy_loss, "wh": wh_loss,
                   "obj": obj_loss + noobj_loss, "class": class_loss}


class YoloTask:
    """Task bundle for the Trainer: multi-scale loss + eval.

    Validation computes mAP@0.5 (decode + NMS on device via
    ``eval_outputs``, VOC-style AP accumulated on host) — the evaluation
    the reference's README admits is "WIP" and never shipped.
    """

    monitor = "mAP"

    def __init__(self, num_classes: int,
                 anchors: np.ndarray = YOLO_ANCHORS,
                 masks: np.ndarray = ANCHOR_MASKS,
                 use_pallas: bool = False,
                 eval_score_threshold: float = 0.05,
                 mesh=None):
        self.num_classes = num_classes
        self.anchors = jnp.asarray(anchors)
        self.masks = masks
        self.use_pallas = use_pallas
        self.eval_score_threshold = eval_score_threshold
        # mesh routes the Pallas kernel through a data-axis shard_map
        # under multi-device meshes (best_iou_max_sharded); None or a
        # 1-device mesh calls the kernel directly
        self.mesh = mesh

    def _scale_anchors(self, scale: int):
        return self.anchors[self.masks[scale]]

    def loss(self, outputs, batch):
        totals, comps = 0.0, {}
        for s, raw in enumerate(outputs):
            t, c = yolo_scale_loss(
                raw, batch[f"y_true_{s}"], batch["boxes"],
                batch["boxes_mask"], self._scale_anchors(s),
                use_pallas=self.use_pallas, mesh=self.mesh)
            totals = totals + t.mean()
            for k, v in c.items():
                comps[f"{k}_{s}"] = v.mean()
        return totals, comps

    def eval_metrics(self, outputs, batch):
        # per-image loss, masked by the eval-padding weight so weight-0
        # filler rows don't pollute the metric
        w = batch.get("weight")
        if w is None:
            w = jnp.ones((batch["boxes"].shape[0],), jnp.float32)
        per_image = 0.0
        for s, raw in enumerate(outputs):
            t, _ = yolo_scale_loss(
                raw, batch[f"y_true_{s}"], batch["boxes"],
                batch["boxes_mask"], self._scale_anchors(s),
                use_pallas=self.use_pallas, mesh=self.mesh)
            per_image = per_image + t
        loss_sum = (per_image * w).sum()
        return {"loss": loss_sum, "neg_loss": -loss_sum, "count": w.sum()}

    def eval_outputs(self, outputs, batch):
        """Device-side decode + static-shape NMS for the host mAP
        accumulator (Trainer host-evaluator protocol)."""
        boxes, scores, classes, valid = postprocess(
            outputs, self.num_classes, anchors=np.asarray(self.anchors),
            masks=self.masks, score_threshold=self.eval_score_threshold)
        return {"det_boxes": boxes, "det_scores": scores,
                "det_classes": classes, "det_valid": valid,
                "gt_boxes": batch["boxes"], "gt_mask": batch["boxes_mask"],
                "gt_classes": batch["gt_classes"]}

    def make_host_evaluator(self):
        from deep_vision_tpu.tasks.map_eval import DetectionMAPAccumulator

        return DetectionMAPAccumulator(self.num_classes)


# ---------------------------------------------------------------------------
# Label encoding (host-side, numpy): preprocess.py:137-269 vectorized
# ---------------------------------------------------------------------------


def find_best_anchor(wh: np.ndarray, anchors: np.ndarray = YOLO_ANCHORS
                     ) -> np.ndarray:
    """Best of the 9 anchors by centered IoU (preprocess.py:226-269).

    wh: (N, 2) normalized → (N,) anchor index.
    """
    inter = np.minimum(wh[:, None, 0], anchors[None, :, 0]) * \
        np.minimum(wh[:, None, 1], anchors[None, :, 1])
    union = wh[:, None, 0] * wh[:, None, 1] + \
        anchors[None, :, 0] * anchors[None, :, 1] - inter
    return np.argmax(inter / np.maximum(union, 1e-9), axis=1)


def encode_labels(boxes_xywh: np.ndarray, classes: np.ndarray,
                  num_classes: int, grids: Sequence[int] = (52, 26, 13),
                  anchors: np.ndarray = YOLO_ANCHORS,
                  masks: np.ndarray = ANCHOR_MASKS):
    """One image's gt boxes → the 3 y_true grids + padded box list.

    boxes_xywh: (N, 4) normalized centroids; classes: (N,) int.
    Returns dict {y_true_0..2: (G,G,3,5+C), boxes: (MAX_BOXES,4) corners,
    boxes_mask: (MAX_BOXES,)}.
    Vectorized scatter (no per-box Python loop over grid ops): one
    best-anchor lookup, one np index-assign per scale.
    """
    n = len(boxes_xywh)
    out = {f"y_true_{s}": np.zeros((g, g, 3, 5 + num_classes), np.float32)
           for s, g in enumerate(grids)}
    boxes_list = np.zeros((MAX_BOXES, 4), np.float32)
    boxes_mask = np.zeros((MAX_BOXES,), np.float32)
    classes_list = np.zeros((MAX_BOXES,), np.int32)
    if n:
        # truncate EVERYTHING to MAX_BOXES so the y_true positives stay
        # consistent with the ignore-mask box list — otherwise overflow
        # boxes would be positives penalized as background
        m = min(n, MAX_BOXES)
        boxes_xywh = boxes_xywh[:m]
        classes = classes[:m]
        corners = np.concatenate([boxes_xywh[:, :2] - boxes_xywh[:, 2:4] / 2,
                                  boxes_xywh[:, :2] + boxes_xywh[:, 2:4] / 2], 1)
        boxes_list[:m] = corners
        boxes_mask[:m] = 1.0
        classes_list[:m] = classes
        best = find_best_anchor(boxes_xywh[:, 2:4], anchors)
        for s, g in enumerate(grids):
            sel = np.isin(best, masks[s])
            if not sel.any():
                continue
            b = boxes_xywh[sel]
            cls = classes[sel]
            a_idx = np.searchsorted(masks[s], best[sel])
            gx = np.clip((b[:, 0] * g).astype(int), 0, g - 1)
            gy = np.clip((b[:, 1] * g).astype(int), 0, g - 1)
            y = out[f"y_true_{s}"]
            y[gy, gx, a_idx, 0:4] = b[:, 0:4]
            y[gy, gx, a_idx, 4] = 1.0
            y[gy, gx, a_idx, 5 + cls] = 1.0
    return {**out, "boxes": boxes_list, "boxes_mask": boxes_mask,
            "gt_classes": classes_list}


# ---------------------------------------------------------------------------
# Postprocess: decode all scales → NMS (postprocess.py:12-96, batched)
# ---------------------------------------------------------------------------


def postprocess(outputs, num_classes: int, max_outputs: int = 100,
                iou_threshold: float = 0.5, score_threshold: float = 0.1,
                anchors: np.ndarray = YOLO_ANCHORS,
                masks: np.ndarray = ANCHOR_MASKS,
                pre_nms_top_k: int = 512,
                class_aware: bool = False,
                soft_nms: str = "off", soft_sigma: float = 0.5,
                max_per_class: int = 0):
    """raw 3-scale outputs → (boxes (B,K,4) corners, scores (B,K),
    classes (B,K), valid (B,K)).

    Only the ``pre_nms_top_k`` highest-scoring candidates per image enter
    NMS: the greedy N×N IoU matrix over all 10,647 anchors at 416² costs
    ~20 GB HBM at batch 16 (an OOM), while top-512 costs ~1 MB.  A box
    outside the top-k can never outrank one inside it, so results differ
    from exhaustive NMS only if >top_k−max_outputs of the leading boxes
    get suppressed — pick top_k ≫ max_outputs (default 512 ≫ 100).

    ``class_aware=True`` makes suppression CLASS-WISE (a box only
    suppresses same-class neighbours, via ops/boxes' class-offset
    trick) — what the serving epilogue uses; the default keeps the
    reference's class-agnostic eval behavior.  Fully jittable either
    way: this whole function traces into the AOT bucket programs
    (serve/workloads.DetectWorkload.make_epilogue).

    ``soft_nms``/``soft_sigma`` switch suppression to Soft-NMS decay
    and ``max_per_class`` caps each class's kept boxes — the
    ``--detect-*`` serving knobs, threaded to ops/boxes.nms_single
    (per-class K needs ``class_aware=True``; it is ignored in
    class-agnostic mode where per-box labels do not partition the
    kept set).
    """
    all_boxes, all_scores, all_cls = [], [], []
    anchors = jnp.asarray(anchors)
    for s, raw in enumerate(outputs):
        box, obj, cls = decode_boxes(raw, anchors[masks[s]])
        B = raw.shape[0]
        scores = obj * cls  # per-class confidence
        best_cls = jnp.argmax(scores, -1)
        best_score = jnp.max(scores, -1)
        all_boxes.append(xywh_to_corners(box).reshape(B, -1, 4))
        all_scores.append(best_score.reshape(B, -1))
        all_cls.append(best_cls.reshape(B, -1))
    boxes = jnp.concatenate(all_boxes, 1)
    scores = jnp.concatenate(all_scores, 1)
    classes = jnp.concatenate(all_cls, 1)
    k = min(pre_nms_top_k, scores.shape[1])
    scores, top_idx = jax.lax.top_k(scores, k)
    boxes = jnp.take_along_axis(boxes, top_idx[..., None], axis=1)
    classes = jnp.take_along_axis(classes, top_idx, axis=1)
    idx, sel_scores, valid = batched_nms(
        boxes, scores, max_outputs, iou_threshold, score_threshold,
        classes=classes if class_aware else None,
        soft=soft_nms, soft_sigma=soft_sigma,
        max_per_class=max_per_class if class_aware else 0)
    sel_boxes = jnp.take_along_axis(boxes, idx[..., None], axis=1)
    sel_classes = jnp.take_along_axis(classes, idx, axis=1)
    return sel_boxes, sel_scores, sel_classes, valid
