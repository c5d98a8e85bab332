"""Each configuration's plain reference against the program's own model and
loss, at a small size in float64 on the CPU, and the generator's detection
targets against the program's encoder."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
from flax import traverse_util

from benchmark import run, weights

CONFIGS = os.path.join(run.HERE, "configs")


def _load(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def _worst(a: dict, b: dict) -> float:
    return max(float(jnp.linalg.norm(a[k] - b[k]) / (jnp.linalg.norm(b[k]) + 1e-12))
               for k in b)


def _weights(variables, seed):
    shapes = {k: v.shape for k, v in traverse_util.flatten_dict(
        variables["params"], sep="/").items()}
    rng = np.random.default_rng(seed)
    return {k: (v.astype(jnp.float64) if v.ndim > 1 else
                v.astype(jnp.float64) + 0.1 * rng.standard_normal(v.shape))
            for k, v in weights.make(shapes, seed).items()}


def test_resnet_reference_matches_the_zoo_model():
    import optax

    from deep_vision_tpu.models import resnet
    from deep_vision_tpu.ops.preprocess import make_imagenet_preprocess

    with jax.enable_x64(True):
        config = dict(_load("resnet50"), stage_sizes=[1, 2, 1, 1])
        ref = run.load_module(os.path.join(CONFIGS, "resnet50.py"), "r").Reference(config)
        model = resnet.ResNet(stage_sizes=(1, 2, 1, 1), num_classes=10,
                              dtype=jnp.float64)
        rng = np.random.default_rng(0)
        batch = {"image": jnp.asarray(rng.integers(0, 256, (8, 64, 64, 3), dtype=np.uint8)),
                 "label": jnp.asarray(rng.integers(0, 10, 8, dtype=np.int32))}
        v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, 64, 3)), train=False)
        w, key = _weights(v, 7), weights.seed_key(7)
        pre = make_imagenet_preprocess()

        def program(p):
            step_key = jax.random.fold_in(key, 0)
            x = pre(batch, jax.random.fold_in(step_key, 1), train=True)["image"]
            out, _ = model.apply(
                {"params": traverse_util.unflatten_dict(p, sep="/"),
                 "batch_stats": v["batch_stats"]},
                x.astype(jnp.float64), train=True, mutable=["batch_stats"])
            return optax.softmax_cross_entropy_with_integer_labels(
                out, batch["label"]).mean()

        def reference(p):
            x = ref.prologue(batch, key, 0).astype(jnp.float64)
            return ref.loss(ref.forward(p, x), batch)

        lp, gp = jax.value_and_grad(program)(w)
        lr, gr = jax.value_and_grad(reference)(w)
    assert abs(float(lp) - float(lr)) < 1e-5 * abs(float(lr))
    assert _worst(gp, gr) < 1e-4


def test_yolo_reference_matches_the_zoo_model_and_task():
    from deep_vision_tpu.models.yolo import YoloV3
    from deep_vision_tpu.tasks.detection import YoloTask, encode_labels

    with jax.enable_x64(True):
        config = dict(_load("yolov3-416"), image_size=64, num_classes=3,
                      batch_size=4, residual_blocks=[1, 1, 1, 1, 1])
        with open(os.path.join(run.HERE, "traffic", "boxes-pool4.json")) as f:
            traffic = dict(json.load(f), pool_batches=1)
        gen = run.load_module(os.path.join(run.HERE, "generators", "image_pool.py"), "g")
        host = gen.make_pool(config, traffic, 5)[0]
        batch = {k: jnp.asarray(x) for k, x in host.items()}
        ref = run.load_module(os.path.join(CONFIGS, "yolov3-416.py"), "r").Reference(config)
        model = YoloV3(num_classes=3, dtype=jnp.float64, width=0.125,
                       blocks=(1, 1, 1, 1, 1))
        task = YoloTask(3, use_pallas=False)
        v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, 64, 3)), train=False)
        w = _weights(v, 7)

        def program(p):
            x = batch["image"].astype(jnp.float64) / 255.0
            out, _ = model.apply(
                {"params": traverse_util.unflatten_dict(p, sep="/"),
                 "batch_stats": v["batch_stats"]}, x, train=True,
                mutable=["batch_stats"])
            return task.loss(out, batch)[0]

        def reference(p):
            x = ref.prologue(batch, None, 0).astype(jnp.float64)
            return ref.loss(ref.forward(p, x), batch)

        lp, gp = jax.value_and_grad(program)(w)
        lr, gr = jax.value_and_grad(reference)(w)
    assert abs(float(lp) - float(lr)) < 1e-5 * abs(float(lr))
    assert _worst(gp, gr) < 1e-4

    # the generator's targets are the ones the program's own encoder makes
    for i in range(host["image"].shape[0]):
        n = int(host["boxes_mask"][i].sum())
        corners = host["boxes"][i, :n]
        xywh = np.concatenate([(corners[:, :2] + corners[:, 2:]) / 2,
                               corners[:, 2:] - corners[:, :2]], axis=1)
        want = encode_labels(xywh, host["gt_classes"][i, :n], 3, grids=(8, 4, 2))
        for s in range(3):
            np.testing.assert_allclose(host[f"y_true_{s}"][i], want[f"y_true_{s}"],
                                       atol=1e-6)
