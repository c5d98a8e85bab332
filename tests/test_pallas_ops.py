"""Pallas kernel numerics vs the XLA reference implementation."""

import jax.numpy as jnp
import numpy as np
import pytest

from deep_vision_tpu.ops.boxes import broadcast_iou
from deep_vision_tpu.ops.pallas_ops import best_iou_max


def _reference(planes, gt, mask):
    """The XLA formulation on (B, N, 4) boxes, fed the kernel's (B, 4, N)
    corner planes."""
    iou = broadcast_iou(jnp.swapaxes(planes, 1, 2), gt)
    iou = jnp.where(mask[:, None, :] > 0, iou, 0.0)
    return iou.max(-1)


def _boxes(rng, B, N, M):
    """Seeded (B, 4, N) prediction planes, (B, M, 4) gts and a (B, M) mask."""
    p1 = rng.uniform(0, 0.8, (B, 2, N)).astype(np.float32)
    pred = np.concatenate([p1, p1 + rng.uniform(0.05, 0.2, (B, 2, N))
                           .astype(np.float32)], 1)
    g1 = rng.uniform(0, 0.8, (B, M, 2)).astype(np.float32)
    gt = np.concatenate([g1, g1 + rng.uniform(0.05, 0.2, (B, M, 2))
                         .astype(np.float32)], -1)
    mask = (rng.uniform(size=(B, M)) > 0.5).astype(np.float32)
    return jnp.asarray(pred), jnp.asarray(gt), jnp.asarray(mask)


def test_best_iou_max_matches_reference():
    # N not a tile multiple, M not a sublane multiple
    pred, gt, mask = _boxes(np.random.default_rng(0), 2, 700, 100)
    got = best_iou_max(pred, gt, mask, interpret=True)
    want = _reference(pred, gt, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_best_iou_max_all_masked_is_zero():
    pred = jnp.asarray(np.random.default_rng(1)
                       .uniform(0, 1, (1, 4, 64)).astype(np.float32))
    gt = jnp.zeros((1, 8, 4))
    mask = jnp.zeros((1, 8))
    out = best_iou_max(pred, gt, mask, interpret=True)
    assert float(jnp.abs(out).max()) == 0.0


@pytest.mark.parametrize("shape", [
    {},
    # a batch that is not a whole number of TILE_B tiles
    {"batch": 12, "n_pred": 300, "n_gt": 20},
    # what yolov3-416-train-b64 compiles: no N is a multiple of TILE_N
    {"batch": 64, "n_pred": 8112, "n_gt": 100},
    {"batch": 64, "n_pred": 2028, "n_gt": 100},
    {"batch": 64, "n_pred": 507, "n_gt": 100},
], ids=["default", "b12", "b64-52", "b64-26", "b64-13"])
def test_parity_check_passes_interpret(shape):
    """The startup check the CLI runs before baking the Pallas path in."""
    from deep_vision_tpu.ops.pallas_ops import best_iou_parity

    assert best_iou_parity(interpret=True, **shape) < 1e-5


def test_best_iou_max_sharded_matches_reference(mesh8):
    """The data-axis shard_map wrapper (the multi-chip path for the fused
    kernel) reproduces the XLA reference on an 8-device mesh."""
    from deep_vision_tpu.ops.pallas_ops import best_iou_max_sharded

    # 2 images per shard
    pred, gt, mask = _boxes(np.random.default_rng(2), 16, 300, 40)
    got = best_iou_max_sharded(pred, gt, mask, mesh8)
    want = _reference(pred, gt, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_kernels_lower_for_tpu_at_zoo_shapes():
    """Mosaic lowering needs no chip: exporting for the TPU platform runs
    the Pallas→Mosaic lowering rules on the CPU host, so an op Mosaic has
    no rule for (the uint8→float32 cast both ingest kernels once used)
    fails here and not on the first chip run.  Shapes are the ones
    chip_smoke.py compiles: every default ``cli.serve`` bucket, the
    ResNet-50 train batch per shard on one and four chips, and the three
    YOLO scales at the ``yolov3_voc`` / ``yolov3_coco`` batches."""
    import functools

    import jax

    from deep_vision_tpu.ops import pallas_ops
    from deep_vision_tpu.tasks.detection import MAX_BOXES

    S = jax.ShapeDtypeStruct

    def lower(fn, *specs):
        jax.export.export(jax.jit(fn), platforms=["tpu"])(*specs)

    for b in (1, 2, 4, 8, 16, 32):
        lower(functools.partial(pallas_ops.serve_ingest, kind="imagenet",
                                act_scale=0.02),
              S((b, 224, 224, 3), jnp.uint8))
    for b in (256, 64):
        lower(functools.partial(pallas_ops.train_ingest, kind="imagenet"),
              S((b, 224, 224, 3), jnp.uint8), S((b, 4), jnp.float32))
    for b in (16, 128):
        for s in (8, 16, 32):
            n = 3 * (416 // s) ** 2
            lower(pallas_ops.best_iou_max, S((b, 4, n), jnp.float32),
                  S((b, MAX_BOXES, 4), jnp.float32),
                  S((b, MAX_BOXES), jnp.float32))


@pytest.mark.parametrize("name,kwargs,specs", [
    ("serve_ingest", {"kind": "imagenet", "act_scale": 0.02},
     [((2, 32, 32, 3), jnp.uint8)]),
    ("train_ingest", {"kind": "imagenet"},
     [((2, 32, 32, 3), jnp.uint8), ((2, 4), jnp.float32)]),
    ("best_iou_max", {},
     [((2, 4, 192), jnp.float32), ((2, 100, 4), jnp.float32),
      ((2, 100), jnp.float32)]),
])
def test_kernel_lowers_under_its_name(name, kwargs, specs):
    """The Mosaic custom call carries the kernel's name, which is what a
    device trace shows the kernel under."""
    import functools

    import jax

    from deep_vision_tpu.ops import pallas_ops

    fn = functools.partial(getattr(pallas_ops, name), **kwargs)
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(
        *[jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in specs])
    assert f'kernel_name = "{name}"' in exported.mlir_module()


def test_scan_kernels_lower_under_their_names(monkeypatch):
    """``ops/ssd.py`` chooses interpret mode by the backend and takes no
    flag, so the test says the backend is a TPU: forward and backward at
    the granite cell's widths lower through Mosaic's rules, and the two
    custom calls carry the names a device trace shows them under."""
    import jax

    from deep_vision_tpu.ops import ssd

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    length, heads, dim, n = 4096, 64, 64, 128
    S = jax.ShapeDtypeStruct

    def loss(x, dt, a, b, c, seg):
        return jnp.sum(ssd.ssd_scan(x, dt, a, b, c, seg, 256))

    exported = jax.export.export(
        jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))), platforms=["tpu"])(
            S((1, length, heads, dim), jnp.bfloat16),
            S((1, length, heads), jnp.float32), S((heads,), jnp.float32),
            S((1, length, n), jnp.bfloat16), S((1, length, n), jnp.bfloat16),
            S((1, length), jnp.int32))
    text = exported.mlir_module()
    assert 'kernel_name = "ssd_chunk_fwd"' in text
    assert 'kernel_name = "ssd_chunk_bwd"' in text

