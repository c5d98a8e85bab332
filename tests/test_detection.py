"""Detection stack tests: codec roundtrip, hand-computed IoU/NMS fixtures,
label encoding, loss behavior, mAP (SURVEY §4b: numerical tests of loss and
box codecs against hand-computed fixtures)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deep_vision_tpu.models.yolo import ANCHOR_MASKS, YOLO_ANCHORS
from deep_vision_tpu.ops.boxes import (
    batched_nms,
    broadcast_iou,
    nms_single,
    xywh_to_corners,
)
from deep_vision_tpu.tasks import detection as D
from deep_vision_tpu.tasks.map_eval import MeanAPEvaluator, average_precision


def test_xywh_to_corners():
    box = jnp.array([[0.5, 0.5, 0.2, 0.4]])
    out = np.asarray(xywh_to_corners(box))
    np.testing.assert_allclose(out, [[0.4, 0.3, 0.6, 0.7]], atol=1e-6)


def test_broadcast_iou_hand_fixture():
    a = jnp.array([[0.0, 0.0, 2.0, 2.0]])          # area 4
    b = jnp.array([[1.0, 1.0, 3.0, 3.0],           # inter 1, union 7
                   [0.0, 0.0, 2.0, 2.0],           # identical
                   [5.0, 5.0, 6.0, 6.0]])          # disjoint
    iou = np.asarray(broadcast_iou(a, b))
    np.testing.assert_allclose(iou, [[1 / 7, 1.0, 0.0]], atol=1e-6)


def test_decode_encode_roundtrip():
    anchors = jnp.asarray(YOLO_ANCHORS[ANCHOR_MASKS[2]])
    rng = np.random.default_rng(0)
    raw = rng.normal(0, 1, size=(2, 13, 13, 3, 85)).astype(np.float32)
    box, obj, cls = D.decode_boxes(jnp.asarray(raw), anchors)
    t_xy, t_wh = D.encode_boxes(box, anchors)
    # encode(decode(raw)) recovers sigmoid(txy) and twh
    np.testing.assert_allclose(
        np.asarray(t_xy), jax.nn.sigmoid(raw[..., 0:2]), atol=1e-4)
    np.testing.assert_allclose(np.asarray(t_wh), raw[..., 2:4], atol=1e-4)
    assert float(obj.min()) >= 0 and float(obj.max()) <= 1


def test_nms_suppresses_overlaps():
    boxes = jnp.array([[0.0, 0.0, 1.0, 1.0],
                       [0.05, 0.0, 1.05, 1.0],   # IoU≈0.9 with box 0
                       [2.0, 2.0, 3.0, 3.0]])    # disjoint
    scores = jnp.array([0.9, 0.8, 0.7])
    idx, sel, valid = nms_single(boxes, scores, max_outputs=3,
                                 iou_threshold=0.5)
    assert valid.tolist() == [1.0, 1.0, 0.0]     # only 2 survive
    assert idx.tolist()[:2] == [0, 2]
    np.testing.assert_allclose(sel[:2], [0.9, 0.7])


def test_batched_nms_shapes():
    rng = np.random.default_rng(1)
    boxes = jnp.asarray(rng.uniform(0, 1, (4, 50, 4)).astype(np.float32))
    boxes = jnp.concatenate([boxes[..., :2],
                             boxes[..., :2] + 0.1 + boxes[..., 2:] * 0.2], -1)
    scores = jnp.asarray(rng.uniform(0, 1, (4, 50)).astype(np.float32))
    idx, sel, valid = batched_nms(boxes, scores, max_outputs=10)
    assert idx.shape == (4, 10) and valid.shape == (4, 10)


def test_batched_nms_topk_preselect_matches_exhaustive():
    """postprocess feeds NMS only the top-k scored boxes (the full N×N
    IoU matrix OOMs at 416²/batch 16); with k ≫ max_outputs the selected
    detections must be identical to exhaustive NMS."""
    rng = np.random.default_rng(7)
    N, K, TOPK = 200, 10, 50
    boxes = rng.uniform(0, 1, (N, 4)).astype(np.float32)
    boxes = np.concatenate(
        [boxes[:, :2], boxes[:, :2] + 0.05 + boxes[:, 2:] * 0.1], -1)
    scores = rng.uniform(0, 1, (N,)).astype(np.float32)

    full_idx, full_sel, full_valid = nms_single(
        jnp.asarray(boxes), jnp.asarray(scores), max_outputs=K)

    top_scores, top_idx = jax.lax.top_k(jnp.asarray(scores), TOPK)
    top_boxes = jnp.asarray(boxes)[top_idx]
    sub_idx, sub_sel, sub_valid = nms_single(top_boxes, top_scores,
                                             max_outputs=K)
    np.testing.assert_array_equal(np.asarray(full_valid),
                                  np.asarray(sub_valid))
    np.testing.assert_allclose(np.asarray(full_sel), np.asarray(sub_sel))
    # indices map back through the top-k gather
    np.testing.assert_array_equal(
        np.asarray(full_idx) * np.asarray(full_valid),
        np.asarray(top_idx)[np.asarray(sub_idx)] * np.asarray(sub_valid))


def test_postprocess_topk_equals_full_nms():
    """End-to-end: postprocess with the default top-512 preselect must
    return exactly what exhaustive NMS (pre_nms_top_k=all) returns on
    random, non-degenerate raw outputs — guards the gather wiring."""
    rng = np.random.default_rng(9)
    B = 2
    outputs = [jnp.asarray(rng.normal(size=(B, g, g, 3, 8))
                           .astype(np.float32)) for g in (8, 4, 2)]
    n_all = sum(g * g * 3 for g in (8, 4, 2))
    got = D.postprocess(outputs, 3, max_outputs=20, pre_nms_top_k=64)
    want = D.postprocess(outputs, 3, max_outputs=20, pre_nms_top_k=n_all)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6, atol=1e-7)


def test_postprocess_real_shapes_stay_small():
    """416² COCO shapes (10,647 candidates/image): postprocess must not
    materialize the exhaustive IoU matrix — regression guard for the
    batch-16 eval OOM."""
    B = 2
    outputs = [jnp.zeros((B, g, g, 3, 85), jnp.float32)
               for g in (52, 26, 13)]
    boxes, scores, classes, valid = D.postprocess(outputs, 80)
    assert boxes.shape == (B, 100, 4) and scores.shape == (B, 100)
    mem = jax.jit(lambda o: D.postprocess(o, 80)).lower(
        outputs).compile().memory_analysis()
    if mem is not None:  # CPU backend may not report
        assert mem.temp_size_in_bytes < 512 * 2**20, mem.temp_size_in_bytes


def test_find_best_anchor():
    # exactly the largest anchor → index 8; tiny box → index 0
    wh = np.array([[373 / 416, 326 / 416], [8 / 416, 10 / 416]])
    best = D.find_best_anchor(wh)
    assert best.tolist() == [8, 0]


def test_encode_labels_places_box():
    # one box at center, size matching anchor 8 (large) → scale 2, cell (6,6)
    boxes = np.array([[0.5, 0.5, 373 / 416, 326 / 416]], np.float32)
    classes = np.array([3])
    enc = D.encode_labels(boxes, classes, num_classes=20)
    y2 = enc["y_true_2"]  # 13×13 grid
    assert y2[6, 6, 2, 4] == 1.0          # obj at anchor slot 2 (idx 8)
    assert y2[6, 6, 2, 5 + 3] == 1.0      # one-hot class
    np.testing.assert_allclose(y2[6, 6, 2, 0:4], boxes[0], atol=1e-6)
    assert enc["y_true_0"].sum() == 0 and enc["y_true_1"].sum() == 0
    assert enc["boxes_mask"].sum() == 1


def test_encode_labels_overflow_truncated_consistently():
    """>MAX_BOXES boxes: y_true positives must cover exactly the same first
    MAX_BOXES boxes as the ignore-mask list, so no positive is simultaneously
    penalized as background."""
    rng = np.random.default_rng(7)
    n = D.MAX_BOXES + 20
    xy = rng.uniform(0.2, 0.8, (n, 2)).astype(np.float32)
    wh = rng.uniform(0.05, 0.3, (n, 2)).astype(np.float32)
    boxes = np.concatenate([xy, wh], 1)
    classes = rng.integers(0, 5, n)
    enc = D.encode_labels(boxes, classes, num_classes=5)
    assert enc["boxes_mask"].sum() == D.MAX_BOXES
    # every positive cell's box must appear in the ignore-mask list
    gt_corners = enc["boxes"][enc["boxes_mask"] > 0]
    for s in range(3):
        y = enc[f"y_true_{s}"]
        pos = y[..., 4] > 0
        for b in y[pos][:, 0:4]:
            corner = np.concatenate([b[:2] - b[2:] / 2, b[:2] + b[2:] / 2])
            match = np.abs(gt_corners - corner).max(1).min()
            assert match < 1e-6


def test_yolo_loss_zero_for_perfect_prediction():
    """If raw predictions exactly re-encode the ground truth, coordinate and
    class losses vanish and obj loss is small (finite BCE saturation)."""
    num_classes = 4
    enc = D.encode_labels(
        np.array([[0.48, 0.52, 116 / 416, 90 / 416]], np.float32),
        np.array([1]), num_classes, grids=(13,),
        masks=np.array([[6, 7, 8]]))
    y_true = jnp.asarray(enc["y_true_0"])[None]
    anchors = jnp.asarray(YOLO_ANCHORS[[6, 7, 8]])
    # build raw that decodes to the truth: logit-space inversion
    t_xy, t_wh = D.encode_boxes(y_true[..., 0:4], anchors)
    eps = 1e-6
    raw_xy = jnp.log(t_xy + eps) - jnp.log(1 - t_xy + eps)  # σ⁻¹
    obj_logit = jnp.where(y_true[..., 4:5] > 0, 20.0, -20.0)
    cls_logit = jnp.where(y_true[..., 5:] > 0, 20.0, -20.0)
    raw = jnp.concatenate([raw_xy, t_wh, obj_logit, cls_logit], -1)
    total, comps = D.yolo_scale_loss(
        raw, y_true, jnp.asarray(enc["boxes"])[None],
        jnp.asarray(enc["boxes_mask"])[None], anchors)
    assert float(comps["xy"].sum()) < 1e-4
    assert float(comps["wh"].sum()) < 1e-4
    assert float(comps["class"].sum()) < 1e-3
    assert float(comps["obj"].sum()) < 1e-3
    assert float(total.sum()) < 2e-3


def test_yolo_loss_penalizes_wrong_prediction():
    num_classes = 4
    enc = D.encode_labels(
        np.array([[0.5, 0.5, 116 / 416, 90 / 416]], np.float32),
        np.array([1]), num_classes, grids=(13,), masks=np.array([[6, 7, 8]]))
    y_true = jnp.asarray(enc["y_true_0"])[None]
    anchors = jnp.asarray(YOLO_ANCHORS[[6, 7, 8]])
    raw = jnp.zeros((1, 13, 13, 3, 5 + num_classes))
    total, _ = D.yolo_scale_loss(
        raw, y_true, jnp.asarray(enc["boxes"])[None],
        jnp.asarray(enc["boxes_mask"])[None], anchors)
    assert float(total.sum()) > 1.0


def test_yolo_loss_grad_with_pallas_path():
    """value_and_grad must work through the Pallas ignore-mask path —
    pallas_call has no autodiff rule, so the mask is stop_gradient'd."""
    num_classes = 3
    enc = D.encode_labels(
        np.array([[0.5, 0.5, 0.3, 0.3]], np.float32),
        np.array([1]), num_classes, grids=(13,), masks=np.array([[6, 7, 8]]))
    y_true = jnp.asarray(enc["y_true_0"])[None]
    anchors = jnp.asarray(YOLO_ANCHORS[[6, 7, 8]])
    raw = jnp.zeros((1, 13, 13, 3, 5 + num_classes))

    def loss_fn(raw):
        total, _ = D.yolo_scale_loss(
            raw, y_true, jnp.asarray(enc["boxes"])[None],
            jnp.asarray(enc["boxes_mask"])[None], anchors, use_pallas=True)
        return total.sum()

    loss, grads = jax.value_and_grad(loss_fn)(raw)
    assert np.isfinite(float(loss))
    assert np.isfinite(np.asarray(grads)).all()
    assert float(jnp.abs(grads).max()) > 0


def _slice_based_scale_loss(raw, y_true, gt_boxes, gt_mask, anchors_wh,
                            ignore_thresh=0.5, lambda_coord=5.0,
                            lambda_noobj=0.5):
    """The loss as it was written before it moved to channel-major planes:
    pieces cut out of the (B,G,G,A,5+C) arrays along their last axis, the
    box codecs called on the full-size arrays, the ignore mask from
    ``broadcast_iou``.  Kept here as the oracle the planes are held to.
    Returns (total, components, ignore mask (B,G,G,A))."""
    pred_xy_rel = jax.nn.sigmoid(raw[..., 0:2])
    pred_wh_rel = raw[..., 2:4]
    pred_box_abs, _, _ = D.decode_boxes(raw, anchors_wh)
    pred_corners = xywh_to_corners(pred_box_abs)
    true_wh_abs = y_true[..., 2:4]
    true_obj = y_true[..., 4:5]
    true_xy_rel, true_wh_rel = D.encode_boxes(y_true[..., 0:4], anchors_wh)
    weight = 2.0 - true_wh_abs[..., 0] * true_wh_abs[..., 1]
    obj = true_obj[..., 0]
    xy_loss = jnp.square(true_xy_rel - pred_xy_rel).sum(-1)
    xy_loss = (obj * weight * xy_loss).sum((1, 2, 3)) * lambda_coord
    wh_loss = jnp.square(true_wh_rel - pred_wh_rel).sum(-1)
    wh_loss = (obj * weight * wh_loss).sum((1, 2, 3)) * lambda_coord
    flat_pred = jax.lax.stop_gradient(
        pred_corners.reshape(raw.shape[0], -1, 4))
    iou = broadcast_iou(flat_pred, gt_boxes)
    iou = jnp.where(gt_mask[:, None, :] > 0, iou, 0.0)
    ignore = (iou.max(-1).reshape(obj.shape) < ignore_thresh).astype(
        jnp.float32)
    obj_entropy = D._bce(raw[..., 4:5], true_obj, from_probs=False)[..., 0]
    obj_loss = (obj * obj_entropy).sum((1, 2, 3))
    noobj_loss = ((1 - obj) * obj_entropy * ignore).sum((1, 2, 3)) \
        * lambda_noobj
    class_entropy = D._bce(raw[..., 5:], y_true[..., 5:], from_probs=False)
    class_loss = (true_obj * class_entropy).sum((1, 2, 3, 4))
    total = xy_loss + wh_loss + obj_loss + noobj_loss + class_loss
    return total, {"xy": xy_loss, "wh": wh_loss,
                   "obj": obj_loss + noobj_loss, "class": class_loss}, ignore


def _scale_case(grid, num_classes, batch=2, seed=0):
    """One scale's (raw, y_true, boxes, mask, anchors): seeded boxes
    encoded by ``encode_labels``, and a raw head output that is noise
    except at every box's cell, where ALL three anchors predict the box
    within a few percent — so the two anchors the box was not assigned to
    are background predictions above the ignore threshold, and the
    noise around them is background below it."""
    rng = np.random.default_rng(seed)
    scale = {52: 0, 26: 1, 13: 2}[grid]
    masks = np.asarray(ANCHOR_MASKS[scale])[None]
    anchors = YOLO_ANCHORS[ANCHOR_MASKS[scale]]
    raw = rng.normal(0, 0.5, (batch, grid, grid, 3, 5 + num_classes))
    y_true, boxes, boxes_mask = [], [], []
    for b in range(batch):
        n = 6
        xy = rng.uniform(0.15, 0.85, (n, 2))
        # sized like this scale's anchors, so encode_labels keeps them here
        wh = anchors[rng.integers(0, 3, n)] * rng.uniform(0.9, 1.1, (n, 2))
        enc = D.encode_labels(
            np.concatenate([xy, wh], 1).astype(np.float32),
            rng.integers(0, num_classes, n), num_classes, grids=(grid,),
            masks=masks)
        y_true.append(enc["y_true_0"])
        boxes.append(enc["boxes"])
        boxes_mask.append(enc["boxes_mask"])
        gx = np.clip((xy[:, 0] * grid).astype(int), 0, grid - 1)
        gy = np.clip((xy[:, 1] * grid).astype(int), 0, grid - 1)
        frac = np.clip(xy * grid - np.stack([gx, gy], 1), 0.02, 0.98)
        raw[b, gy, gx, :, 0:2] = (np.log(frac) - np.log1p(-frac))[:, None]
        raw[b, gy, gx, :, 2:4] = np.log(wh[:, None] / anchors[None]) \
            + rng.normal(0, 0.03, (n, 3, 2))
    return tuple(jnp.asarray(np.asarray(a, np.float32)) for a in
                 (raw, y_true, boxes, boxes_mask, anchors))


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("num_classes", [3, 80])
@pytest.mark.parametrize("grid", [13, 26, 52])
def test_yolo_loss_equals_slice_based_oracle(grid, num_classes, use_pallas):
    """The channel-major loss is the slice-based one: per-image total, the
    four components and the gradient with respect to ``raw``; only the
    order of summation may differ."""
    raw, y_true, boxes, mask, anchors = _scale_case(grid, num_classes)

    # jitted: eager, every operation of both formulations compiles alone
    def new(r):
        return D.yolo_scale_loss(r, y_true, boxes, mask, anchors,
                                 use_pallas=use_pallas)

    def old(r):
        return _slice_based_scale_loss(r, y_true, boxes, mask, anchors)

    want_total, want_comps, ignore = jax.jit(old)(raw)
    # the case means something only if background predictions fall on
    # both sides of the ignore threshold
    background = np.asarray(y_true[..., 4]) == 0
    ignored = np.asarray(ignore)[background]
    assert 0 < (ignored == 0).sum() < ignored.size

    got_total, got_comps = jax.jit(new)(raw)
    assert got_total.shape == want_total.shape == (raw.shape[0],)
    np.testing.assert_allclose(got_total, want_total, rtol=1e-5)
    assert set(got_comps) == {"xy", "wh", "obj", "class"}
    for k in got_comps:
        np.testing.assert_allclose(got_comps[k], want_comps[k], rtol=1e-5,
                                   err_msg=k)

    got_grad = jax.jit(jax.grad(lambda r: new(r)[0].sum()))(raw)
    want_grad = jax.jit(jax.grad(lambda r: old(r)[0].sum()))(raw)
    np.testing.assert_allclose(got_grad, want_grad, rtol=1e-5, atol=1e-7)


def _cuts_lanes(eqn, lanes=128):
    """Does this slice/split/concatenate/gather cut or join its operands
    along their last axis, with a piece narrower than the chip's lanes?
    (Dropping a padded tail — ``out[:, :n]`` of a kernel's result — keeps
    whole lane tiles and is not the fault.)"""
    name = eqn.primitive.name
    if name == "gather":
        return True
    ins = [v.aval.shape for v in eqn.invars if v.aval.ndim]
    outs = [v.aval.shape for v in eqn.outvars]
    if name in ("slice", "dynamic_slice"):
        cut = outs[0][-1] != ins[0][-1]
    elif name == "split":
        cut = eqn.params["axis"] == len(ins[0]) - 1
    else:
        cut = eqn.params["dimension"] == len(ins[0]) - 1
    return cut and min(s[-1] for s in (outs if name != "concatenate"
                                       else ins)) < lanes


@pytest.mark.parametrize("with_grad", [False, True], ids=["loss", "grad"])
def test_yolo_loss_never_slices_the_lane_axis_of_a_full_array(
        with_grad, jaxpr_equations):
    """Traced (not run) at the shapes of ``yolov3-416-train-b64``: no
    slice, split, concatenate or gather takes an operand of a whole
    plane's size or more (B·N elements) apart along its last axis into
    pieces narrower than 128 — that axis is the lane axis on the chip, and
    ``x[..., 0:2]`` on a (B,G,G,A,85) array is a pass over all of it that
    fills 2 lanes."""
    batch, num_classes = 64, 80
    task = D.YoloTask(num_classes, use_pallas=True)
    S = jax.ShapeDtypeStruct
    grids = (52, 26, 13)
    outputs = [S((batch, g, g, 3, 5 + num_classes), jnp.float32)
               for g in grids]
    targets = {f"y_true_{s}": o for s, o in enumerate(outputs)}
    targets["boxes"] = S((batch, D.MAX_BOXES, 4), jnp.float32)
    targets["boxes_mask"] = S((batch, D.MAX_BOXES), jnp.float32)
    def fn(outputs, targets):
        return task.loss(outputs, targets)[0]

    if with_grad:
        fn = jax.grad(fn)
    jaxpr = jax.make_jaxpr(fn)(outputs, targets).jaxpr

    smallest_plane = batch * 3 * min(grids) ** 2
    seen, bad = set(), []
    for eqn in jaxpr_equations(jaxpr):
        name = eqn.primitive.name
        seen.add(name)
        if name not in ("slice", "dynamic_slice", "split", "concatenate",
                        "gather"):
            continue
        biggest = max(v.aval.size for v in eqn.invars)
        if biggest >= smallest_plane and _cuts_lanes(eqn):
            bad.append(f"{name} {[v.aval.shape for v in eqn.invars]} -> "
                       f"{[v.aval.shape for v in eqn.outvars]}")
    assert "pallas_call" in seen and "slice" in seen  # the walk saw the loss
    assert not bad, bad


def test_lane_axis_check_catches_the_old_formulation(jaxpr_equations):
    """The same walk over the slice-based oracle does flag it."""
    raw = jax.ShapeDtypeStruct((4, 13, 13, 3, 8), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda r: _slice_based_scale_loss(
        r, r, jnp.zeros((4, 5, 4)), jnp.ones((4, 5)),
        jnp.ones((3, 2)))[0])(raw).jaxpr
    cuts = [e for e in jaxpr_equations(jaxpr)
            if e.primitive.name in ("slice", "split", "concatenate")
            and max(v.aval.size for v in e.invars) >= 4 * 507
            and _cuts_lanes(e)]
    assert cuts


@pytest.fixture(scope="module")
def one_v5e_chip():
    """A described (not attached) v5e chip to compile for; nothing runs."""
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_yolo_loss_compiles_to_one_copy_an_operand(one_v5e_chip):
    """What the jaxpr cannot show: XLA is free to keep 5+C on the lanes
    behind a transpose and slice it there again.  Compiled for the v5e —
    head conv, the head's reshape and cast, loss and gradient at the 26×26
    scale of ``yolov3-416-train-b64`` — the step moves a full-size float32
    array exactly twice outside a fusion: ``raw`` out of the conv's layout
    and ``y_true`` out of the host's (``_channel_major``'s two spellings;
    either spelling used for both operands makes it four to six)."""
    import re

    batch, grid, num_classes, features = 64, 26, 80, 512
    chans = 3 * (5 + num_classes)

    def S(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    def step(kernel, x, y_true, boxes, mask):
        def loss(kernel):
            raw = jax.lax.conv_general_dilated(
                x, kernel, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            raw = raw.reshape(batch, grid, grid, 3, 5 + num_classes)
            return D.yolo_scale_loss(
                raw.astype(jnp.float32), y_true, boxes, mask,
                jnp.asarray(YOLO_ANCHORS[ANCHOR_MASKS[1]])
            )[0].mean()  # the XLA ignore mask: Mosaic is not under test
        return jax.value_and_grad(loss)(kernel)

    hlo = jax.jit(step).lower(
        S((1, 1, features, chans), jnp.bfloat16),
        S((batch, grid, grid, features), jnp.bfloat16),
        S((batch, grid, grid, 3, 5 + num_classes)),
        S((batch, D.MAX_BOXES, 4)), S((batch, D.MAX_BOXES))).compile().as_text()
    entry = hlo[hlo.index("ENTRY"):]
    moves = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = f32\[([\d,]+)\]\S* "
                     r"(copy|reshape|slice|transpose)\(", line)
        if m and np.prod([int(d) for d in m.group(1).split(",")]) \
                >= batch * grid * grid * chans:
            moves.append(line.strip()[:120])
    assert len(moves) == 2, moves


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_scan_kernels_compile_for_the_chip_at_published_widths(
        one_v5e_chip, monkeypatch, dtype):
    """Here, beside the file's other compile for the described chip (one
    process may hold the TPU's compiler, so such tests share a file): the
    state-space scan's two kernels (``ops/ssd.py``) at the widths of
    ``granite-4.0-h-micro-train-packed4k`` -- a row of 4,096 tokens, 64
    heads of 64, state 128, chunk 256 -- go through Mosaic, tiling and VMEM
    included, which interpret mode says nothing about; and the program
    around them holds no ``(256, 256)`` float32 value."""
    import re

    from deep_vision_tpu.ops import ssd

    length, heads, dim, n, chunk = 4096, 64, 64, 128, 256
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def S(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    def loss(x, dt, a, b, c, seg):
        return jnp.sum(ssd.ssd_scan(x, dt, a, b, c, seg, chunk))

    hlo = jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))).lower(
        S((1, length, heads, dim), dtype), S((1, length, heads)), S((heads,)),
        S((1, length, n), dtype), S((1, length, n), dtype),
        S((1, length), jnp.int32)).compile().as_text()
    assert hlo.count("tpu_custom_call") == 2
    assert not re.search(rf"f32\[[\d,]*{chunk},{chunk}\]", hlo)


@pytest.mark.parametrize("length, block, heads, kv_heads, dim", [
    (8192, 1024, 32, 8, 64), (4096, 512, 32, 8, 64), (8192, 512, 20, 20, 256)],
    ids=["lfm2", "granite", "glm"])
def test_attention_kernels_compile_for_the_chip_at_the_cells_shapes(
        one_v5e_chip, monkeypatch, length, block, heads, kv_heads, dim):
    """Beside the scan's: the attention's two kernels (``ops/attention.py``)
    at the shapes of the three language cells -- rows of 8,192 and 4,096, 32
    / 8 heads of 64 and 20 / 20 heads of 256, bfloat16, the models'
    ``attention_block`` -- go through Mosaic, tiling and VMEM included; ``k``
    and ``v`` reach the kernels at their own heads; and the program around
    them holds no float32 value with a face of the kernels' blocks."""
    import re

    from deep_vision_tpu.ops import attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    def loss(q, k, v, seg):
        return jnp.sum(attention.causal_attention(
            q, k, v, seg, 0.125, block).astype(jnp.float32))

    hlo = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        S((1, length, heads, dim)), S((1, length, kv_heads, dim)),
        S((1, length, kv_heads, dim)), S((1, length), jnp.int32)).compile().as_text()
    assert hlo.count("tpu_custom_call") == 2
    block_q, block_k = attention._blocks(length, block)
    assert not re.search(rf"f32\[[\d,]*({block_k},{block_q}|{length},{length})\]", hlo)


def test_average_precision_perfect():
    r = np.array([0.5, 1.0])
    p = np.array([1.0, 1.0])
    assert average_precision(r, p) == pytest.approx(1.0)
    assert average_precision(r, p, use_07_metric=True) == pytest.approx(1.0, abs=0.1)


def test_map_evaluator_perfect_and_miss():
    ev = MeanAPEvaluator(num_classes=2)
    gt = np.array([[0.0, 0.0, 1.0, 1.0]])
    ev.add(gt, np.array([0.9]), np.array([0]), gt, np.array([0]))
    # second image: class 1 gt, detection misses (disjoint box)
    ev.add(np.array([[5, 5, 6, 6.0]]), np.array([0.8]), np.array([1]),
           np.array([[0.0, 0.0, 1.0, 1.0]]), np.array([1]))
    res = ev.compute()
    assert res["per_class"][0] == pytest.approx(1.0)
    assert res["per_class"][1] == pytest.approx(0.0)
    assert res["mAP"] == pytest.approx(0.5)
    # exact hit scores 1.0 at every COCO threshold; the miss 0 at every one
    assert res["mAP50_95"] == pytest.approx(0.5)


def test_map_coco_average_partial_overlap():
    """A detection at IoU 0.8 passes thresholds 0.50–0.80 (7 of the 10 COCO
    grid points) and fails 0.85–0.95 → mAP50_95 = 0.7 while mAP@0.5 = 1."""
    ev = MeanAPEvaluator(num_classes=1)
    gt = np.array([[0.0, 0.0, 10.0, 10.0]])
    det = np.array([[0.0, 0.0, 10.0, 8.0]])   # inter 80 / union 100 = 0.8
    ev.add(det, np.array([0.9]), np.array([0]), gt, np.array([0]))
    res = ev.compute()
    assert res["mAP"] == pytest.approx(1.0)
    assert res["mAP50_95"] == pytest.approx(0.7)


def test_map_boundary_iou_counts_as_matched():
    """A detection EXACTLY on a COCO grid threshold matches at that
    threshold by construction (IOU_EPS comparison slack), independent of
    how the grid doubles were produced — previously this held only
    because np.arange(...).round(2) and the IoU arithmetic happened to
    round to the same nearest doubles."""
    ev = MeanAPEvaluator(num_classes=1)
    for thr in MeanAPEvaluator.COCO_IOUS:
        # gt 10×10 at origin; det [0,0,10,10t] nests inside it, so
        # union = gt area and IoU = inter/union = 100t/100 = exactly t
        ev.add(np.array([[0.0, 0.0, 10.0, 10.0 * thr]]), np.array([0.9]),
               np.array([0]), np.array([[0.0, 0.0, 10.0, 10.0]]),
               np.array([0]))
    res = ev.compute()
    # image k's IoU is grid point k: it matches thresholds 0..k, so
    # mAP50_95 = mean over thresholds of AP with (10−k)/10 recall ...
    # the key regression signal is the primary threshold: every image
    # with IoU ≥ 0.5 (all 10) must match at 0.5 despite 5 of them
    # sitting exactly ON a grid value
    assert res["mAP"] == pytest.approx(1.0)
    assert res["mAP50_95"] > 0.0


def test_map_matching_rules_crowded_objects():
    """The two matching rules diverge on crowded scenes, and each metric
    uses its own: det2's argmax-IoU gt is taken by det1, so VOC-devkit
    matching (mAP@0.5 — comparable to published VOC numbers) counts it
    FP (AP 0.5), while COCO matching (the mAP50_95 grid) lets it fall
    through to the unmatched gt above threshold (AP 1.0 at IoUs ≤ 0.8)."""
    ev = MeanAPEvaluator(num_classes=1)
    gts = np.array([[0.0, 0.0, 10.0, 10.0], [2.0, 0.0, 12.0, 10.0]])
    dets = np.array([[0.0, 0.0, 10.0, 10.0],   # IoU 1.0 / 0.667
                     [1.0, 0.0, 11.0, 10.0]])  # IoU 0.818 / 0.818 (tie)
    ev.add(dets, np.array([0.9, 0.8]), np.array([0, 0]),
           gts, np.array([0, 0]))
    res = ev.compute()
    assert res["mAP"] == pytest.approx(0.5)       # VOC rule: det2 is FP
    # COCO rule: both match for the 7 grid points ≤0.80 where det2's 0.818
    # clears threshold (AP 1.0); above that only det1 matches.  AP at a
    # threshold where recall stops at 0.5 with precision 1.0 is 0.5, so
    # the average is (7·1.0 + 3·0.5)/10
    assert res["mAP50_95"] == pytest.approx(0.85)


def test_yolov3_model_shapes():
    from deep_vision_tpu.models.yolo import YoloV3

    model = YoloV3(num_classes=20)
    x = jnp.zeros((1, 128, 128, 3))
    variables = jax.eval_shape(
        lambda a: model.init({"params": jax.random.PRNGKey(0)}, a,
                             train=False), x)
    outs = jax.eval_shape(
        lambda v, a: model.apply(v, a, train=False), variables, x)
    assert outs[0].shape == (1, 16, 16, 3, 25)   # large grid (÷8)
    assert outs[1].shape == (1, 8, 8, 3, 25)
    assert outs[2].shape == (1, 4, 4, 3, 25)
    from deep_vision_tpu.models.common import count_params

    n = sum(x.size for x in jax.tree_util.tree_leaves(variables["params"]))
    assert 61_000_000 < n < 63_000_000  # canonical yolov3-coco≈62M (here C=20)
