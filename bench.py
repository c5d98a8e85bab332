"""Driver benchmark: ResNet-50 train-step throughput on the attached chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...} —
extra keys report achieved TFLOP/s and MFU (model FLOPs utilization,
%-of-peak for the chip's bf16 matmul rate).

Baseline (BASELINE.md): the reference's only measured training throughput is
~800 img/s aggregate on 8 GPUs (ResNet-34 log timestamps,
ResNet/pytorch/logs/resnet34-yanjiali-010319.log) ⇒ ~100 img/s/chip; the
driver metric is "ResNet-50 ILSVRC2012 images/sec/chip" so vs_baseline
divides by 100.

Modes:
    python bench.py              # train-step throughput + MFU (driver mode)
    python bench.py --pipeline   # host input-pipeline throughput (JPEG
                                 # decode+augment through ImageNetLoader)
    python bench.py --profile    # also write a jax.profiler trace
    python bench.py --task yolo  # one task's train step at production shape
    python bench.py --all        # every task, one subprocess each
"""

from __future__ import annotations

import argparse
import functools
import json
import random
import time

import jax
import jax.numpy as jnp

BASELINE_IMG_PER_SEC_PER_CHIP = 100.0

# the peak-TFLOP/s spec table moved to obs/mfu.py — one source of truth
# for the training MFU here and the serving MFU gauge (/metrics);
# PEAK_BF16_TFLOPS stays importable from bench for existing callers
from deep_vision_tpu.obs.mfu import (  # noqa: E402
    PEAK_BF16_TFLOPS,
    compiled_flops as _compiled_flops,
    peak_tflops as _peak_tflops,
)


def _device_fields() -> dict:
    """What every device-measurement line carries: the device JAX ran on.
    Resolving the peak first makes a device the table does not list (a
    CPU backend) an error before anything is timed — a train-step,
    task or forward rate is a chip number or it is nothing."""
    dev = jax.devices()[0]
    _peak_tflops(dev.device_kind)
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "n_devices": len(jax.devices())}


def bench_train_step(batch: int = 256, size: int = 224, steps: int = 20,
                     profile: bool = False, scan_steps: int = 40,
                     ema_decay: float = 0.0, grad_accum: int = 1,
                     momentum_dtype: str | None = None) -> dict:
    """Sustained ResNet-50 train-step throughput.

    ``scan_steps`` mirrors the Trainer's multi-step dispatch
    (``TrainConfig.scan_steps`` / ``--scan-steps``, core/trainer.py): K
    optimizer updates per device program via ``lax.scan``, which amortizes
    the per-step host-dispatch overhead.  ``scan_steps=1`` measures the
    step-per-dispatch path.

    ``ema_decay``/``grad_accum`` mirror the Trainer's recipe arithmetic
    (--ema-decay / --grad-accum): the EMA warmup FMA over params after
    each update, and sequential interleaved microbatches with grad
    averaging — so their throughput cost is measured, not assumed
    (VERDICT r3 #3).  The metric name gains _ema/_gaN suffixes.  Note
    this is the same LEAN step as the base row (no divergence guard, no
    per-microbatch rng fold), so the DELTA between rows isolates the
    recipe's cost; the coupled cli.train run in docs/PERF.md carries the
    full Trainer step.
    """
    from deep_vision_tpu.core.optim import OptimizerConfig, build_optimizer
    from deep_vision_tpu.core.state import TrainState
    from deep_vision_tpu.models.resnet import ResNet50
    from deep_vision_tpu.tasks.classification import ClassificationTask

    device = _device_fields()
    model = ResNet50(dtype=jnp.bfloat16)
    task = ClassificationTask(1000)
    tx = build_optimizer(OptimizerConfig(
        name="sgd", learning_rate=0.1, momentum=0.9, weight_decay=1e-4,
        momentum_dtype=momentum_dtype))

    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (batch, size, size, 3), jnp.float32)
    y = jax.random.randint(rng, (batch,), 0, 1000)

    variables = jax.jit(functools.partial(model.init, train=False))(
        {"params": rng}, x[:1])
    state = TrainState.create(
        apply_fn=model.apply, params=variables["params"], tx=tx,
        batch_stats=variables["batch_stats"], rng=rng,
        ema=ema_decay > 0)

    def grad_one(state, params, batch_stats, image, label):
        def loss_fn(params):
            out, new_vars = state.apply_fn(
                {"params": params, "batch_stats": batch_stats},
                image, train=True, mutable=["batch_stats"])
            loss, _ = task.loss(out, {"label": label})
            return loss, new_vars["batch_stats"]

        return jax.value_and_grad(loss_fn, has_aux=True)(params)

    def one_step(state, image, label):
        if grad_accum == 1:
            (loss, new_bs), grads = grad_one(
                state, state.params, state.batch_stats, image, label)
        else:
            # trainer-exact microbatching: interleaved split, stats
            # threaded sequentially, grads averaged (core/trainer.py)
            def split(x):
                return jnp.swapaxes(
                    x.reshape(x.shape[0] // grad_accum, grad_accum,
                              *x.shape[1:]), 0, 1)

            mi, ml = split(image), split(label)
            gzero = jax.tree_util.tree_map(jnp.zeros_like, state.params)

            def body(carry, xs):
                bs, gsum = carry
                im, lb = xs
                (l, bs), g = grad_one(state, state.params, bs, im, lb)
                return (bs, jax.tree_util.tree_map(jnp.add, gsum, g)), l

            (new_bs, gsum), losses = jax.lax.scan(
                body, (state.batch_stats, gzero), (mi, ml))
            grads = jax.tree_util.tree_map(lambda g: g / grad_accum, gsum)
            loss = jnp.mean(losses)
        new_state = state.apply_gradients(grads, batch_stats=new_bs)
        if ema_decay:
            t = new_state.step.astype(jnp.float32)
            d = jnp.minimum(ema_decay, (1.0 + t) / (10.0 + t))
            new_state = new_state.replace(
                ema_params=jax.tree_util.tree_map(
                    lambda e, p: d * e + (1 - d) * p,
                    new_state.ema_params, new_state.params))
        return new_state, loss

    K = max(1, scan_steps)

    @functools.partial(jax.jit, donate_argnums=0)
    def train_block(state, image, label):
        def body(s, _):
            s, loss = one_step(s, image, label)
            return s, loss

        # unroll=2: halves the loop-trip overhead and lets XLA overlap
        # step i's optimizer update with step i+1's first convs — measured
        # 99.6 ms/step vs 101.1 unrolled=1 vs 105 per-dispatch
        state, losses = jax.lax.scan(body, state, None, length=K, unroll=2)
        return state, losses[-1]

    # AOT compiles.  The FLOP count (honest MFU numerator, no hand-derived
    # constants) comes from XLA's cost analysis of the SINGLE-step
    # executable — the scan executable reports its loop body only once
    # regardless of trip count, so it can't be used directly.
    step_flops = _cost_flops(jax.jit(one_step).lower(state, x, y).compile())
    compiled = train_block.lower(state, x, y).compile()
    hbm_gib = _hbm_gib(compiled)

    # warmup; the scalar fetch drains the dispatch
    state, loss = compiled(state, x, y)
    float(jax.device_get(loss))

    blocks = max(1, steps // K) if K > 1 else steps
    if profile:
        jax.profiler.start_trace("/tmp/bench_profile")
    t0 = time.perf_counter()
    for _ in range(blocks):
        state, loss = compiled(state, x, y)
    float(jax.device_get(loss))  # drains the async dispatch chain
    dt = time.perf_counter() - t0
    steps = blocks * K
    if profile:
        jax.profiler.stop_trace()
        print("# trace written to /tmp/bench_profile")

    # normalize by the devices the step ACTUALLY spans (a plain jit runs on
    # one device regardless of how many chips the host exposes)
    n_chips = len({d for arr in jax.tree_util.tree_leaves(state)
                   for d in arr.devices()}) or 1
    img_per_sec_per_chip = steps * batch / dt / n_chips
    suffix = ("_ema" if ema_decay else "") + \
        (f"_ga{grad_accum}" if grad_accum > 1 else "") + \
        ("_bf16mom" if momentum_dtype == "bfloat16" else "")
    out = {
        "metric": "resnet50_train_images_per_sec_per_chip" + suffix,
        "value": round(img_per_sec_per_chip, 1),
        "unit": "images/sec/chip",
        "vs_baseline": round(
            img_per_sec_per_chip / BASELINE_IMG_PER_SEC_PER_CHIP, 2),
    }
    # cost analysis counts a lax.scan body once regardless of trip count,
    # so the microbatch scan inside a grad-accum step under-reports FLOPs
    # ~accum-fold — suppress the derived fields there (img/s is the metric)
    if step_flops and grad_accum == 1:
        achieved = step_flops * steps / dt / n_chips / 1e12
        out["tflops_per_chip"] = round(achieved, 1)
        out["mfu_pct"] = round(
            100.0 * achieved / _peak_tflops(device["device_kind"]), 1)
    out.update(device)
    out["batch"] = batch
    out["scan_steps"] = K
    if ema_decay:
        out["ema_decay"] = ema_decay
    if grad_accum > 1:
        out["grad_accum"] = grad_accum
    if hbm_gib:
        out["hbm_gib"] = hbm_gib
    return out


def _peak_hbm_gib() -> float | None:
    """Process-lifetime peak device-memory use, GiB (per-model when each
    task bench runs in its own process — what ``--all`` does).  Returns
    None where the runtime doesn't expose allocator stats."""
    try:
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        return round(peak / 2**30, 2) if peak else None
    except Exception:
        return None


def _hbm_gib(compiled) -> float | None:
    """Static HBM footprint of one executable from XLA's own memory
    analysis: live arguments + outputs (minus donated aliases) + compiler
    temp arena.  Available even when allocator stats are not."""
    try:
        ma = compiled.memory_analysis()
        b = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
        return round(b / 2**30, 2) if b else None
    except Exception:
        return None


def _cost_flops(compiled) -> float | None:
    """FLOPs of one executable per XLA's cost analysis (honest MFU
    numerator — no hand-derived constants); shared with the serving
    registry via obs/mfu.py."""
    return _compiled_flops(compiled)


def _finish(out: dict, compiled, dt: float, n_steps: int, batch_size: int,
            baseline: float | None = None) -> None:
    """Shared result assembly for the task benches."""
    rate = n_steps * batch_size / dt
    out["value"] = round(rate, 1)
    if baseline:
        out["vs_baseline"] = round(rate / baseline, 2)
    step_flops = _cost_flops(compiled)
    if step_flops:
        out["tflops_per_chip"] = round(step_flops * n_steps / dt / 1e12, 1)
    hbm = _hbm_gib(compiled)
    if hbm:
        out["hbm_gib"] = hbm
    out["ms_per_step"] = round(dt / n_steps * 1e3, 1)
    out["batch"] = batch_size


def _time_step(compiled, args, steps: int, loss_of, profile: bool = False):
    """Warm once, then time ``steps`` sequential dispatches, draining the
    async chain through a scalar fetch."""
    out = compiled(*args)
    float(jax.device_get(loss_of(out)))
    if profile:
        jax.profiler.start_trace("/tmp/bench_profile")
    t0 = time.perf_counter()
    for _ in range(steps):
        out = compiled(*(out[:1] + args[1:]))
    float(jax.device_get(loss_of(out)))
    dt = time.perf_counter() - t0
    if profile:
        jax.profiler.stop_trace()
        print("# trace written to /tmp/bench_profile")
    return dt


def bench_task(name: str, steps: int | None = None,
               batch: int | None = None, profile: bool = False) -> dict:
    """Train-step throughput for one non-classification task at the
    REFERENCE's production shapes (VERDICT r02 item 4):

    - ``yolo``       YOLOv3-Darknet53 416², per-chip batch 16 (the
                     reference's per-GPU batch, YOLO/tensorflow/train.py:282)
    - ``centernet``  CenterNet (2-stack hourglass) 256² batch 32
                     (zoo/centernet.py — the stack the reference left broken)
    - ``hourglass``  Stacked Hourglass-104 256² batch 16, 16 joints @64²
    - ``cyclegan``   ResNet-9 G ×2 + PatchGAN D ×2, 256² batch 1
                     (CycleGAN/tensorflow/train.py batch_size=1)
    - ``dcgan``      28²×1 MNIST GAN, batch 256 (DCGAN/tensorflow/main.py)

    Each model trains bf16-compute / f32-params like the ResNet bench; the
    step is the same math the Trainer/AdversarialTrainer jits.  Reports
    images/sec/chip and process-peak HBM.
    """
    import numpy as np

    from deep_vision_tpu.core.optim import OptimizerConfig, build_optimizer
    from deep_vision_tpu.core.state import TrainState

    device = _device_fields()
    rng = jax.random.PRNGKey(0)
    out: dict = {"metric": f"{name}_train_images_per_sec_per_chip",
                 "unit": "images/sec/chip"}

    def single_state_run(model, task, batch, opt, n_steps, batch_size,
                         baseline=None):
        variables = jax.jit(functools.partial(model.init, train=False))(
            {"params": rng}, batch["image"][:1])
        state = TrainState.create(
            apply_fn=model.apply, params=variables["params"],
            tx=build_optimizer(opt),
            batch_stats=variables.get("batch_stats", {}), rng=rng)

        def one_step(state, batch):
            def loss_fn(params):
                outputs, new_vars = state.apply_fn(
                    {"params": params, "batch_stats": state.batch_stats},
                    batch["image"], train=True, mutable=["batch_stats"])
                loss, _ = task.loss(outputs, batch)
                return loss, new_vars["batch_stats"]

            (loss, bs), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params)
            return state.apply_gradients(grads, batch_stats=bs), loss

        compiled = jax.jit(one_step, donate_argnums=0).lower(
            state, batch).compile()
        dt = _time_step(compiled, (state, batch), n_steps, lambda o: o[1],
                        profile=profile)
        _finish(out, compiled, dt, n_steps, batch_size, baseline)

    if name == "yolo":
        from deep_vision_tpu.models.yolo import YoloV3
        from deep_vision_tpu.tasks.detection import MAX_BOXES, YoloTask

        B, S = batch or 16, 416
        npr = np.random.default_rng(0)
        batch = {"image": jnp.asarray(
                     npr.normal(size=(B, S, S, 3)).astype(np.float32)),
                 "boxes": jnp.asarray(np.clip(
                     npr.uniform(0, 1, (B, MAX_BOXES, 4)), 0, 1)
                     .astype(np.float32)),
                 "boxes_mask": jnp.asarray(
                     (np.arange(MAX_BOXES) < 8)[None]
                     .repeat(B, 0).astype(np.float32))}
        for s, g in enumerate((52, 26, 13)):
            y = np.zeros((B, g, g, 3, 85), np.float32)
            # a few positive cells so every loss branch executes
            y[:, g // 2, g // 2, 0, 0:4] = (0.5, 0.5, 0.1, 0.1)
            y[:, g // 2, g // 2, 0, 4] = 1.0
            y[:, g // 2, g // 2, 0, 5] = 1.0
            batch[f"y_true_{s}"] = jnp.asarray(y)
        # reference: ~180 img/s aggregate on 8×V100 ⇒ 22.5 img/s/chip
        single_state_run(
            YoloV3(num_classes=80, dtype=jnp.bfloat16), YoloTask(80), batch,
            OptimizerConfig(name="sgd", learning_rate=1e-3, momentum=0.9),
            steps or 20, B, baseline=22.5)
    elif name == "centernet":
        from deep_vision_tpu.models.centernet import CenterNet
        from deep_vision_tpu.tasks.centernet import (CenterNetTask,
                                                     encode_centernet_labels)

        B, S = batch or 32, 256  # zoo/centernet.py: batch 32 @ 256²
        npr = np.random.default_rng(0)
        enc = [encode_centernet_labels(
            np.array([[0.3 + 0.4 * npr.random(), 0.3 + 0.4 * npr.random(),
                       0.2, 0.2]], np.float32),
            np.array([int(npr.integers(0, 80))]), 80, grid=S // 4)
            for _ in range(B)]
        batch = {k: jnp.asarray(np.stack([e[k] for e in enc]))
                 for k in enc[0]}
        batch["image"] = jnp.asarray(
            npr.normal(size=(B, S, S, 3)).astype(np.float32))
        single_state_run(
            CenterNet(num_classes=80, dtype=jnp.bfloat16),
            CenterNetTask(80), batch,
            OptimizerConfig(name="adam", learning_rate=2.5e-4),
            steps or 20, B)
    elif name == "hourglass":
        from deep_vision_tpu.models.hourglass import StackedHourglass
        from deep_vision_tpu.tasks.pose import PoseTask

        B = batch or 16
        batch = {"image": jax.random.normal(rng, (B, 256, 256, 3)),
                 "heatmaps": jnp.clip(
                     jax.random.normal(rng, (B, 64, 64, 16)), 0, 1)}
        single_state_run(
            StackedHourglass(num_stack=4, num_heatmap=16,
                             dtype=jnp.bfloat16),
            PoseTask(), batch,
            OptimizerConfig(name="adam", learning_rate=2.5e-4),
            steps or 20, B)
    elif name in ("cyclegan", "dcgan"):
        if name == "cyclegan":
            from deep_vision_tpu.models import gan as gan_models
            from deep_vision_tpu.tasks.gan import CycleGANTask

            B = batch or 1
            task = CycleGANTask(
                lambda: gan_models.CycleGANGenerator(dtype=jnp.bfloat16),
                lambda: gan_models.PatchGANDiscriminator(
                    dtype=jnp.bfloat16))
            host = {"image_a": np.random.default_rng(0).normal(
                        size=(B, 256, 256, 3)).astype(np.float32),
                    "image_b": np.random.default_rng(1).normal(
                        size=(B, 256, 256, 3)).astype(np.float32)}
            n_steps = steps or 40
        else:
            from deep_vision_tpu.models.gan import (DCGANDiscriminator,
                                                    DCGANGenerator)
            from deep_vision_tpu.tasks.gan import DCGANTask

            B = batch or 256
            task = DCGANTask(DCGANGenerator(dtype=jnp.bfloat16),
                             DCGANDiscriminator(dtype=jnp.bfloat16))
            host = {"image": np.random.default_rng(0).normal(
                size=(B, 28, 28, 1)).astype(np.float32)}
            n_steps = steps or 200
        states = task.init_states(rng, host)
        batch = jax.tree_util.tree_map(
            jnp.asarray, task.host_prepare(dict(host)))
        compiled = jax.jit(task.train_step, donate_argnums=0).lower(
            states, batch, rng).compile()
        dt = _time_step(compiled, (states, batch, rng), n_steps,
                        lambda o: next(iter(o[2].values())), profile=profile)
        _finish(out, compiled, dt, n_steps, B)
    else:
        raise SystemExit(f"unknown --task {name}")
    peak = _peak_hbm_gib()
    if peak:
        out["peak_hbm_gib"] = peak
    out.update(device)
    return out


def bench_infer(name: str = "resnet50", steps: int | None = None,
                batch: int | None = None) -> dict:
    """Forward-only (serving) throughput:

    - ``resnet50``  batch-256 bf16 classification forward;
    - ``yolo``      batch-16 416² forward INCLUDING the full on-device
                    postprocess (3-scale decode + score filter + batched
                    NMS, ops/boxes.py) — the reference runs NMS in host
                    Python per image (YOLO/tensorflow/postprocess.py).
    """
    import numpy as np

    device = _device_fields()
    rng = jax.random.PRNGKey(0)
    if name == "resnet50":
        from deep_vision_tpu.models.resnet import ResNet50

        B = batch or 256
        model = ResNet50(dtype=jnp.bfloat16)
        x = jax.random.normal(rng, (B, 224, 224, 3), jnp.float32)
        variables = jax.jit(functools.partial(model.init, train=False))(
            {"params": rng}, x[:1])

        def fwd(variables, x):
            logits = model.apply(variables, x, train=False)
            return jnp.argmax(logits, -1)

    elif name == "yolo":
        from deep_vision_tpu.models.yolo import YoloV3
        from deep_vision_tpu.tasks.detection import YoloTask

        B = batch or 16
        model = YoloV3(num_classes=80, dtype=jnp.bfloat16)
        task = YoloTask(80)
        x = jax.random.normal(rng, (B, 416, 416, 3), jnp.float32)
        variables = jax.jit(functools.partial(model.init, train=False))(
            {"params": rng}, x[:1])

        def fwd(variables, x):
            from deep_vision_tpu.tasks.detection import postprocess

            outputs = model.apply(variables, x, train=False)
            boxes, scores, classes, valid = postprocess(
                outputs, 80, anchors=np.asarray(task.anchors),
                masks=task.masks)
            return scores

    else:
        raise SystemExit(f"unknown --infer target {name}")

    compiled = jax.jit(fwd).lower(variables, x).compile()
    n_steps = steps or (20 if name == "yolo" else 40)
    out_first = compiled(variables, x)
    float(jax.device_get(out_first.reshape(-1)[0]))
    t0 = time.perf_counter()
    for _ in range(n_steps):
        o = compiled(variables, x)
    float(jax.device_get(o.reshape(-1)[0]))
    dt = time.perf_counter() - t0
    out = {"metric": f"{name}_infer_images_per_sec_per_chip",
           "value": round(n_steps * B / dt, 1),
           "unit": "images/sec/chip",
           "ms_per_batch": round(dt / n_steps * 1e3, 1), "batch": B}
    hbm = _hbm_gib(compiled)
    if hbm:
        out["hbm_gib"] = hbm
    out.update(device)
    return out


def bench_serve(model_name: str = "lenet5", loads: tuple = (1, 8),
                duration_s: float = 2.0, max_batch: int = 8,
                max_wait_ms: float = 2.0, pipeline_depth: int = 2,
                faults: str = "", fault_seed: int = 0,
                serve_devices: int = 1,
                serve_mesh: tuple | None = None,
                mesh_min_shard_dim: int = 1024,
                wire_dtype: str = "float32",
                infer_dtype: str = "float32",
                calib_batches: int = 2,
                trace: bool = True) -> dict:
    """Closed-loop load generator against the dynamic-batching engine
    (``deep_vision_tpu/serve``): C client threads each submit one image,
    wait for the answer, repeat — so C is the offered load (concurrency),
    and the engine's batcher decides how requests coalesce into bucketed
    device batches.  One JSON line reports p50/p95/p99 request latency
    and sustained img/s at every load point — the knee where latency
    rises faster than throughput is the max_wait/bucket tuning signal
    (docs/SERVING.md) — plus the pipelined executor's overlap block
    (device-idle fraction, in-flight high-water mark, staged-buffer
    reuse, bulk D2H bytes) so serving regressions are trackable the way
    the default run tracks training.  ``--serve-pipeline-depth 1`` is the
    synchronous comparison run.

    ``--faults`` (a deterministic spec, docs/SERVING.md) exercises the
    failure paths under load — each load point then also reports its
    error count, and the JSON gains a ``health`` block (state machine,
    retries, quarantines, watchdog restarts) so fault-tolerance overhead
    and behavior are benchmarkable, not just unit-tested.

    ``serve_devices > 1`` replicates the engine over that many local
    devices (serve/replicas.py) and the JSON gains ``replicas`` —
    per-replica batches, img/s, and in-flight high-water — plus the
    routing counters; ``bench.py --serve --serve-devices N`` sweeps
    replica counts 1, 2, 4, ... N and emits the device-scaling table
    (docs/PERF.md).

    ``serve_mesh=(D, M)`` instead builds ONE engine on a D×M
    data×model mesh (registry ``for_mesh``): batches split D ways,
    params shard M ways (first-divisible-axis fallback at
    ``mesh_min_shard_dim``), and the JSON gains ``mesh`` /
    ``param_shard_bytes`` / ``param_global_bytes`` — the per-chip HBM
    column of the ``--serve-mesh`` sweep (``bench_serve_mesh``).

    ``wire_dtype``/``infer_dtype`` select the serving wire format and
    on-device compute dtype (docs/SERVING.md); the JSON records both
    plus the ``h2d`` block (transfers, MiB, per-bucket bytes) and the
    resident ``weight_hbm_bytes`` so BENCH_* trajectories track
    transfer volume and weight footprint alongside latency —
    ``bench.py --serve --serve-wire`` runs the full 6-cell comparison
    (``bench_serve_wire``); ``infer_dtype="int8"`` calibrates with
    ``calib_batches`` synthetic batches (serve/quant.py).

    ``trace`` toggles per-request span collection (obs/trace.py): the
    JSON gains ``serving_mfu``/``mfu`` (analytic-FLOPs utilization,
    docs/OBSERVABILITY.md) and ``stages`` (mean per-stage milliseconds
    across traced requests); ``bench.py --serve --serve-obs`` runs
    trace-off then trace-on and reports the overhead deltas.
    """
    import sys
    import tempfile
    import threading

    import numpy as np

    from deep_vision_tpu.core.config import get_config
    from deep_vision_tpu.core.restore import load_state
    from deep_vision_tpu.obs.trace import Tracer
    from deep_vision_tpu.serve.engine import BatchingEngine
    from deep_vision_tpu.serve.admission import Shed
    from deep_vision_tpu.serve.faults import FaultPlane, Quarantined
    from deep_vision_tpu.serve.registry import CheckpointServingModel

    cfg = get_config(model_name)
    with tempfile.TemporaryDirectory() as td:
        # random-init fallback: serving latency is weight-agnostic
        model, state = load_state(cfg, td,
                                  log=lambda m: print(m, file=sys.stderr))
    sm = CheckpointServingModel(model_name, cfg, model, state,
                                wire_dtype=wire_dtype,
                                infer_dtype=infer_dtype,
                                calib_batches=calib_batches)
    if sm.wire_dtype == np.uint8:
        img = np.random.RandomState(0).randint(
            0, 256, size=sm.input_shape, dtype=np.uint8)
    else:
        img = np.random.RandomState(0).randn(
            *sm.input_shape).astype(np.float32)
    tracer = Tracer(enabled=trace)
    if serve_mesh is not None:
        from deep_vision_tpu.parallel.mesh import make_mesh
        from deep_vision_tpu.serve.engine import sharded_buckets
        from deep_vision_tpu.serve.replicas import local_devices

        n_data, n_model = int(serve_mesh[0]), int(serve_mesh[1])
        mesh = make_mesh({"data": n_data, "model": n_model},
                         devices=local_devices(n_data * n_model))
        engine_ctx = BatchingEngine(
            sm.for_mesh(mesh, min_shard_dim=mesh_min_shard_dim),
            max_batch=max_batch, max_wait_ms=max_wait_ms,
            buckets=sharded_buckets(max_batch, n_data),
            pipeline_depth=pipeline_depth,
            faults=FaultPlane(faults, fault_seed), tracer=tracer)
    elif serve_devices > 1:
        from deep_vision_tpu.serve.replicas import (ReplicatedEngine,
                                                    local_devices)

        engine_ctx = ReplicatedEngine(
            sm, devices=local_devices(serve_devices),
            max_batch=max_batch, max_wait_ms=max_wait_ms,
            pipeline_depth=pipeline_depth,
            faults=FaultPlane(faults, fault_seed), tracer=tracer)
    else:
        engine_ctx = BatchingEngine(
            sm, max_batch=max_batch, max_wait_ms=max_wait_ms,
            pipeline_depth=pipeline_depth,
            faults=FaultPlane(faults, fault_seed), tracer=tracer)
    points = []
    with engine_ctx as engine:
        engine.warmup()  # compiles excluded from every load point
        for clients in loads:
            latencies: list = []
            errors = [0]
            retries = [0]
            lock = threading.Lock()
            stop_at = time.perf_counter() + duration_s

            def client(seed):
                # a well-behaved closed-loop client: a queue-full shed
                # carries a Retry-After hint, so honor it with jittered
                # backoff (bounded) instead of polluting the error
                # column — only sheds that exhaust the retry budget, or
                # carry no hint (deadline/shutdown), count as errors
                rng = random.Random(seed)
                local, local_err, local_retry = [], 0, 0
                while time.perf_counter() < stop_at:
                    t0 = time.perf_counter()
                    r = None
                    try:
                        for _ in range(3):  # 1 attempt + 2 retries
                            r = engine.infer(img, timeout=60)
                            if not (isinstance(r, Shed)
                                    and r.retry_after_s):
                                break
                            local_retry += 1
                            time.sleep(min(r.retry_after_s, 0.25)
                                       * (0.5 + rng.random()))
                        if isinstance(r, (Shed, Quarantined)):
                            local_err += 1
                            continue
                    except Exception:  # noqa: BLE001 — injected faults
                        local_err += 1
                        continue
                    local.append(time.perf_counter() - t0)
                with lock:
                    latencies.extend(local)
                    errors[0] += local_err
                    retries[0] += local_retry

            threads = [threading.Thread(target=client, args=(k,))
                       for k in range(clients)]
            t_start = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t_start
            lat_ms = np.asarray(latencies) * 1e3
            points.append({
                "clients": clients, "requests": len(latencies),
                "errors": errors[0], "retries": retries[0],
                "img_per_sec": round(len(latencies) / elapsed, 1),
                "p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
                "p95_ms": round(float(np.percentile(lat_ms, 95)), 2),
                "p99_ms": round(float(np.percentile(lat_ms, 99)), 2)})
        stats = engine.stats()
    pipe = stats["pipeline"]
    staging = pipe["staging"]
    health = stats["health"]
    out = {"metric": f"serve_{model_name}_img_per_sec",
            "value": points[-1]["img_per_sec"], "unit": "img/s",
            "model": model_name, "max_batch": max_batch,
            "max_wait_ms": max_wait_ms, "buckets": stats["buckets"],
            "pipeline_depth": pipeline_depth,
            "wire_dtype": stats["wire_dtype"],
            "infer_dtype": stats["infer_dtype"],
            "weight_hbm_bytes": stats.get("weight_hbm_bytes"),
            "calib_batches": (calib_batches
                              if infer_dtype == "int8" else None),
            "faults": faults or None,
            "loads": points,
            "h2d": {
                "transfers": pipe["h2d_transfers"],
                "mib": round(pipe["h2d_bytes"] / 2**20, 3),
                "bytes_per_batch": round(
                    pipe["h2d_bytes"] / max(1, pipe["h2d_transfers"])),
                "bytes_by_bucket": pipe["h2d_bytes_by_bucket"]},
            "health": {
                "state": health["state"],
                "batch_failures": health["batch_failures"],
                "retry_executions": health["retry_executions"],
                "quarantined": health["quarantined"],
                "watchdog_restarts": health["watchdog_restarts"],
                "exec_timeouts": health["exec_timeouts"],
                **({"faults": health["faults"]}
                   if "faults" in health else {})},
            "engine": {"batches": stats["batches"],
                       "compiles": stats["compiles"],
                       "padded_images": stats["padded_images"]},
            "overlap": {
                "device_idle_frac": pipe["device_idle_frac"],
                "max_inflight": pipe["max_inflight"],
                "bulk_transfers": pipe["bulk_transfers"],
                "bulk_transfer_mib": round(
                    pipe["bulk_transfer_bytes"] / 2**20, 3),
                "staged_buffers_allocated": staging["allocated"],
                "staged_buffer_reuses": staging["reused"],
                "exec_ewma_ms_by_bucket":
                    stats["admission"]["exec_ewma_ms_by_bucket"]},
            "device_kind": jax.devices()[0].device_kind}
    mfu = stats.get("mfu") or {}
    out["serving_mfu"] = mfu.get("serving_mfu")
    out["mfu"] = {k: mfu.get(k) for k in
                  ("serving_mfu", "flops_source", "flops_total",
                   "compute_s", "unknown_flops_batches",
                   "peak_flops_per_s")}
    tr = stats.get("trace") or {}
    out["trace_enabled"] = trace
    if tr.get("enabled"):
        out["stages"] = {"stage_ms_avg": tr.get("stage_ms_avg"),
                         "traces_finished": tr.get("finished"),
                         "slow_sampled": tr.get("slow_sampled")}
    if serve_mesh is not None:
        out["mesh"] = stats.get("mesh_shape")
        out["param_shard_bytes"] = stats.get("param_shard_bytes")
        out["param_global_bytes"] = stats.get("param_global_bytes")
    if "replicas" in stats:
        out["serve_devices"] = serve_devices
        out["replicas"] = [
            {"replica": r["replica"], "device": r["device"],
             "state": r["state"], "batches": r["batches"],
             "routed_batches": r["routed_batches"],
             "img_per_sec": r["img_per_sec"],
             "max_inflight": r["max_inflight"]}
            for r in stats["replicas"]]
        out["routing"] = stats["routing"]
        out["admission_free_replicas"] = \
            stats["admission"]["free_replicas"]
    return out


def bench_serve_scaling(serve_devices: int, **kwargs) -> dict:
    """Device-scaling sweep: run the serve bench at replica counts
    1, 2, 4, ... ``serve_devices`` and emit one JSON with the scaling
    table (img/s + p99 at the top load point per count) plus the full
    detail of the widest run.  On real multi-chip hardware 1→2 replicas
    should show >1.6× offered-throughput capacity (docs/PERF.md); on a
    single shared host device the table measures routing overhead
    instead."""
    counts, c = [], 1
    while c < serve_devices:
        counts.append(c)
        c *= 2
    counts.append(serve_devices)
    table, last = [], None
    for k in counts:
        last = bench_serve(serve_devices=k, **kwargs)
        top = last["loads"][-1]
        table.append({"replicas": k,
                      "img_per_sec": top["img_per_sec"],
                      "p50_ms": top["p50_ms"], "p99_ms": top["p99_ms"],
                      "errors": top["errors"]})
    base = table[0]["img_per_sec"] or 1.0
    for row in table:
        row["speedup_vs_1"] = round(row["img_per_sec"] / base, 2)
    last["scaling"] = table
    return last


def bench_serve_mesh(mesh_devices: int = 4,
                     mesh_min_shard_dim: int = 64, **kwargs) -> dict:
    """Mesh-cell sweep (``bench.py --serve-mesh N``; docs/PERF.md
    "Mesh scaling"): the serve bench across the 1×1 baseline, the pure
    data-parallel N×1, the pure model-parallel 1×N, and the squarest
    2-D D×M factorization of N — img/s, p99, and per-chip
    ``param_shard_bytes`` per cell, so the throughput cost and HBM
    saving of each layout are measured side by side.  On forced host
    devices the throughput columns measure GSPMD partitioning overhead
    on one shared chip (the HBM column is layout-true everywhere);
    real ICI separates the cells.  ``mesh_min_shard_dim`` defaults low
    (64) so the zoo's small models actually shard — production keeps
    the registry's 1024 floor."""
    n = int(mesh_devices)
    cells = [(1, 1), (n, 1), (1, n)]
    d = max((k for k in range(2, n) if n % k == 0 and k * k <= n),
            default=None)
    if d is not None:
        cells.append((max(d, n // d), min(d, n // d)))
    table, last = [], None
    for n_data, n_model in cells:
        last = bench_serve(serve_mesh=(n_data, n_model),
                           mesh_min_shard_dim=mesh_min_shard_dim,
                           **kwargs)
        top = last["loads"][-1]
        shard = last.get("param_shard_bytes")
        glob = last.get("param_global_bytes")
        table.append({
            "mesh": f"{n_data}x{n_model}",
            "img_per_sec": top["img_per_sec"],
            "p50_ms": top["p50_ms"], "p99_ms": top["p99_ms"],
            "errors": top["errors"],
            "param_shard_bytes": shard,
            "param_global_bytes": glob,
            "hbm_frac_of_replicated": round(shard / glob, 4)
            if shard and glob else None})
    last["mesh_sweep"] = table
    return last


def bench_serve_batch(model_name: str = "lenet5", n_images: int = 256,
                      shard_size: int | None = None, max_batch: int = 8,
                      max_wait_ms: float = 2.0, pipeline_depth: int = 2,
                      mesh: tuple = (2, 2),
                      mesh_min_shard_dim: int = 64,
                      loads: tuple = (2, 8),
                      duration_s: float = 2.0) -> dict:
    """Offline batch tier bench (``bench.py --serve-batch``; docs/PERF.md
    "Batch tier"): a bulk job drained through the trough-filling
    scheduler (serve/batch_sched.py) on a forced-host 2×2 data×model
    mesh engine, two phases:

    1. *Bulk-only drain*: one ``n_images`` job with no interactive
       traffic — sustained batch img/s, the drain-phase compute
       occupancy (Δcompute_s / Δwall from the MFU meter, window-free),
       and the occupancy-weighted MFU — the sustained-throughput
       figure the batch tier exists to maximize.
    2. *Interference sweep*: for each closed-loop interactive load C,
       interactive p50/p99 WITHOUT any batch work vs WITH a bulk job
       draining behind the priority band — the p99 ratio is the
       acceptance number (≈1.0: the band admits shards only into
       troughs), alongside the batch throughput the troughs yielded.

    On forced host devices every cell shares one chip, so absolute
    img/s undersells real hardware — the occupancy, MFU, and p99-ratio
    columns are the transferable numbers."""
    import sys
    import tempfile
    import threading

    import numpy as np

    from deep_vision_tpu.core.config import get_config
    from deep_vision_tpu.core.restore import load_state
    from deep_vision_tpu.obs.mfu import round_mfu
    from deep_vision_tpu.parallel.mesh import make_mesh
    from deep_vision_tpu.serve.admission import Shed
    from deep_vision_tpu.serve.batch_sched import BatchScheduler
    from deep_vision_tpu.serve.faults import Quarantined
    from deep_vision_tpu.serve.engine import (BatchingEngine,
                                              sharded_buckets)
    from deep_vision_tpu.serve.jobs import JobStore
    from deep_vision_tpu.serve.registry import CheckpointServingModel
    from deep_vision_tpu.serve.replicas import local_devices

    cfg = get_config(model_name)
    with tempfile.TemporaryDirectory() as td:
        model, state = load_state(cfg, td,
                                  log=lambda m: print(m, file=sys.stderr))
    sm = CheckpointServingModel(model_name, cfg, model, state,
                                wire_dtype="uint8")
    img = np.random.RandomState(0).randint(
        0, 256, size=sm.input_shape, dtype=np.uint8)
    n_data, n_model = int(mesh[0]), int(mesh[1])
    grid = make_mesh({"data": n_data, "model": n_model},
                     devices=local_devices(n_data * n_model))
    shard = int(shard_size or max_batch)

    def manifest(n):
        return [{"pixels": np.random.RandomState(i).randint(
            0, 256, size=sm.input_shape).tolist()} for i in range(n)]

    def mfu_snap(engine):
        m = engine.stats().get("mfu") or {}
        return (m.get("flops_total") or 0.0, m.get("compute_s") or 0.0,
                m.get("peak_flops_per_s"))

    with BatchingEngine(
            sm.for_mesh(grid, min_shard_dim=mesh_min_shard_dim),
            max_batch=max_batch, max_wait_ms=max_wait_ms,
            buckets=sharded_buckets(max_batch, n_data),
            pipeline_depth=pipeline_depth) as engine:
        engine.warmup()  # compiles excluded from both phases

        def run_job(n, sched_kwargs=None):
            store = JobStore(shard_size=shard)
            sched = BatchScheduler(store, lambda name: (sm, engine),
                                   interval_s=0.002,
                                   **(sched_kwargs or {}))
            jid = store.submit(model_name, sm.workload.verb,
                               manifest(n))["job_id"]
            sched.start()
            return store, sched, jid

        def interactive_window(clients):
            latencies: list = []
            errors = [0]
            lock = threading.Lock()
            stop_at = time.perf_counter() + duration_s

            def client():
                local, local_err = [], 0
                while time.perf_counter() < stop_at:
                    t0 = time.perf_counter()
                    r = engine.infer(img, timeout=60)
                    if isinstance(r, (Shed, Quarantined)):
                        local_err += 1
                        continue
                    local.append(time.perf_counter() - t0)
                with lock:
                    latencies.extend(local)
                    errors[0] += local_err

            threads = [threading.Thread(target=client)
                       for _ in range(clients)]
            t_start = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t_start
            if not latencies:  # every request shed: report the errors
                return {"requests": 0, "errors": errors[0],
                        "img_per_sec": 0.0, "p50_ms": None,
                        "p99_ms": None}
            lat_ms = np.asarray(latencies) * 1e3
            return {"requests": len(latencies), "errors": errors[0],
                    "img_per_sec": round(len(latencies) / elapsed, 1),
                    "p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
                    "p99_ms": round(float(np.percentile(lat_ms, 99)), 2)}

        # -- phase 1: bulk-only drain ---------------------------------
        f0, c0, peak = mfu_snap(engine)
        store, sched, jid = run_job(n_images)
        t0 = time.perf_counter()
        while store.status(jid)["state"] not in ("done", "failed"):
            time.sleep(0.005)
        drain_s = time.perf_counter() - t0
        sched.stop()
        st = store.status(jid)
        assert st["state"] == "done", st
        f1, c1, peak = mfu_snap(engine)
        occ_drain = min(1.0, (c1 - c0) / drain_s) if drain_s > 0 else None
        mfu_drain = ((f1 - f0) / (c1 - c0)) / peak \
            if peak and c1 > c0 else None
        sched_stats = sched.stats()
        bulk = {
            "img_per_sec": round(n_images / drain_s, 1),
            "drain_s": round(drain_s, 3),
            "occupancy": round(occ_drain, 4)
            if occ_drain is not None else None,
            "occupancy_rolling": engine.stats()["pipeline"]["occupancy"],
            "serving_mfu": round_mfu(mfu_drain)
            if mfu_drain is not None else None,
            "mfu_occupancy_weighted": round_mfu(mfu_drain * occ_drain)
            if mfu_drain is not None and occ_drain is not None else None,
            "shards_done": sched_stats["shards_done"],
            "shards_shed": sched_stats["shards_shed"],
            "deferred": sched_stats["deferred"]}

        # -- phase 2: interactive-vs-batch interference sweep ---------
        table = []
        for clients in loads:
            base = interactive_window(clients)
            store, sched, jid = run_job(4 * n_images)
            done_before = store.status(jid)["images_done"]
            contended = interactive_window(clients)
            sched.stop()
            batch_done = store.status(jid)["images_done"] - done_before
            contended["batch_img_per_sec"] = round(
                batch_done / duration_s, 1)
            ratio = None
            if base["p99_ms"] and contended["p99_ms"]:
                ratio = round(contended["p99_ms"] / base["p99_ms"], 3)
            table.append({
                "clients": clients, "baseline": base,
                "with_batch": contended, "p99_ratio": ratio})
        stats = engine.stats()
    return {"metric": f"serve_batch_{model_name}_img_per_sec",
            "value": bulk["img_per_sec"], "unit": "img/s",
            "model": model_name, "mesh": f"{n_data}x{n_model}",
            "n_images": n_images, "shard_size": shard,
            "max_batch": max_batch, "buckets": stats["buckets"],
            "wire_dtype": stats["wire_dtype"],
            "bulk": bulk, "interference": table,
            "param_shard_bytes": stats.get("param_shard_bytes"),
            "device_kind": jax.devices()[0].device_kind}


def bench_serve_wire(**kwargs) -> dict:
    """Wire-format comparison sweep (``make bench-serve-wire``): the
    serve bench across all six wire × compute cells — f32/uint8 wire ×
    f32/bf16/int8 device compute — so the uint8 wire's 4× H2D-byte cut,
    bf16's latency effect, and int8's ~4× weight-HBM cut are measured
    side by side (docs/PERF.md "Serving wire format").  Emits the full
    detail of the last cell (uint8 + int8, the smallest-footprint
    configuration) plus ``wire_sweep``: p50/p95/p99, img/s, H2D
    bytes/batch, and resident weight bytes per cell.
    ``weight_hbm_ratio_int8_over_f32`` is the acceptance number for the
    int8 quantization path (≤ 0.27 expected; serve/quant.py keeps
    biases and BN f32, so the ratio sits just above 0.25)."""
    table, last = [], None
    for wire in ("float32", "uint8"):
        for infer in ("float32", "bfloat16", "int8"):
            last = bench_serve(wire_dtype=wire, infer_dtype=infer,
                               **kwargs)
            top = last["loads"][-1]
            table.append({
                "wire_dtype": wire, "infer_dtype": infer,
                "img_per_sec": top["img_per_sec"],
                "p50_ms": top["p50_ms"], "p95_ms": top["p95_ms"],
                "p99_ms": top["p99_ms"], "errors": top["errors"],
                "h2d_mib": last["h2d"]["mib"],
                "h2d_bytes_per_batch": last["h2d"]["bytes_per_batch"],
                "weight_hbm_bytes": last.get("weight_hbm_bytes"),
                "calib_batches": last.get("calib_batches")})
    f32w = [r for r in table if r["wire_dtype"] == "float32"]
    u8w = [r for r in table if r["wire_dtype"] == "uint8"]
    if f32w and u8w and u8w[0]["h2d_bytes_per_batch"]:
        last["h2d_bytes_ratio_f32_over_u8"] = round(
            f32w[0]["h2d_bytes_per_batch"]
            / u8w[0]["h2d_bytes_per_batch"], 2)
    f32c = [r for r in table if r["infer_dtype"] == "float32"
            and r["weight_hbm_bytes"]]
    i8c = [r for r in table if r["infer_dtype"] == "int8"
           and r["weight_hbm_bytes"]]
    if f32c and i8c:
        last["weight_hbm_ratio_int8_over_f32"] = round(
            i8c[0]["weight_hbm_bytes"] / f32c[0]["weight_hbm_bytes"], 4)
    last["wire_sweep"] = table
    return last


def bench_serve_obs(**kwargs) -> dict:
    """Observability-overhead comparison (``bench.py --serve
    --serve-obs``; docs/PERF.md "Observability overhead"): the serve
    bench twice — per-request tracing OFF, then ON — same engine
    parameters, fresh engine each run.  Emits the traced run's full
    detail plus ``obs_overhead``: img/s and p99 at the top load point
    for both runs and the on-vs-off deltas in percent (the acceptance
    bar is < 2% on both)."""
    kwargs.pop("trace", None)
    off = bench_serve(trace=False, **kwargs)
    on = bench_serve(trace=True, **kwargs)
    t_off, t_on = off["loads"][-1], on["loads"][-1]
    on["obs_overhead"] = {
        "img_per_sec_off": t_off["img_per_sec"],
        "img_per_sec_on": t_on["img_per_sec"],
        "img_per_sec_delta_pct": round(
            100.0 * (t_off["img_per_sec"] - t_on["img_per_sec"])
            / max(1e-9, t_off["img_per_sec"]), 2),
        "p99_ms_off": t_off["p99_ms"],
        "p99_ms_on": t_on["p99_ms"],
        "p99_delta_pct": round(
            100.0 * (t_on["p99_ms"] - t_off["p99_ms"])
            / max(1e-9, t_off["p99_ms"]), 2)}
    return on


def bench_serve_mix(models: tuple = ("lenet5", "yolov3_toy",
                                     "hourglass_toy", "dcgan"),
                    loads: tuple = (8,), duration_s: float = 2.0,
                    max_batch: int = 8, max_wait_ms: float = 2.0,
                    pipeline_depth: int = 2,
                    hbm_budget_mb: float = 0.0,
                    zipf_s: float = 1.1,
                    cascade: str | None = None, **_ignored) -> dict:
    """Mixed-WORKLOAD serving mix (``bench.py --serve-mix``): every
    model in ``models`` deployed behind one control plane
    (serve/models.py) sharing a weight cache, closed-loop clients
    picking a model per request from a Zipf-ish popularity
    distribution (weight ∝ 1/rank^s in list order — the first model
    is the hot one, the tail is the long tail that keeps getting
    evicted).  The default mix spans ALL FOUR workloads — classify
    (lenet5), detect (yolov3_toy), pose (hourglass_toy), generate
    (dcgan) — so the bench exercises the workload adapters' input
    codecs (latent vectors for DCGAN) and fused epilogues
    (serve/workloads.py).  The JSON reports per-model/per-workload
    p50/p95/p99 + img/s per load point, per-engine D2H bytes/batch
    (where generate's on-device uint8 encode shows its 4× output-wire
    win and detect's fused decode ships K boxes instead of the dense
    pyramid), and the weight
    cache's hit rate / eviction / spill counters, so the latency tax
    of serving more models than the HBM budget holds is a tracked
    number, not folklore (docs/SERVING.md "Model lifecycle & weight
    cache", "Workloads").  ``hbm_budget_mb`` is the experiment knob:
    0 = uncapped (baseline), small enough to hold one model =
    worst-case thrash.

    ``cascade='front:big'`` (both names in ``models``) routes the big
    name's Zipf slot through the cascade router (serve/cascade.py):
    its requests land in a dedicated ``cascade`` column of the table —
    NOT under either tier — so per-model client img/s never counts a
    cascaded request twice; the engine-side table still shows each
    tier's own served counts."""
    import sys
    import tempfile
    import threading

    import numpy as np

    from deep_vision_tpu.core.config import get_config
    from deep_vision_tpu.core.restore import load_state
    from deep_vision_tpu.serve.admission import (AdmissionController,
                                                 Shed)
    from deep_vision_tpu.serve.engine import BatchingEngine
    from deep_vision_tpu.serve.faults import Quarantined
    from deep_vision_tpu.serve.models import (ModelControlPlane,
                                              WeightCache)
    from deep_vision_tpu.serve.registry import (CheckpointServingModel,
                                                ModelRegistry)

    registry = ModelRegistry()
    admissions: dict = {}

    cas_front = cas_big = None
    if cascade:
        cas_front, _, cas_big = str(cascade).partition(":")
        if cas_front not in models or cas_big not in models:
            raise ValueError(
                f"--cascade tiers {cascade!r} must both be in the mix "
                f"{list(models)}")

    def admission_for(name):
        if name not in admissions:
            admissions[name] = AdmissionController(name=name)
        return admissions[name]

    def engine_factory(sm):
        return BatchingEngine(sm, max_batch=max_batch,
                              max_wait_ms=max_wait_ms,
                              pipeline_depth=pipeline_depth,
                              admission=admission_for(sm.name))

    cache = WeightCache(int(float(hbm_budget_mb) * 2**20))
    plane = ModelControlPlane(registry, engine_factory, cache=cache,
                              admission_factory=admission_for)
    imgs = {}
    try:
        for name in models:
            cfg = get_config(name)
            with tempfile.TemporaryDirectory() as td:
                model, state = load_state(
                    cfg, td, log=lambda m: print(m, file=sys.stderr))
            sm = CheckpointServingModel(name, cfg, model, state)
            if name == cas_front:
                sm.cascade_topk = 5  # fuse the confidence epilogue
            plane.deploy(sm)
            # workload-aware input synthesis: the serving input shape
            # may be a latent vector (generate) and the wire dtype is
            # the model's, not assumed float32
            wire = np.dtype(str(sm.wire_dtype))
            rng0 = np.random.RandomState(0)
            if wire.kind in "ui":
                imgs[name] = rng0.randint(
                    0, 256, sm.input_shape).astype(wire)
            else:
                imgs[name] = rng0.randn(*sm.input_shape).astype(wire)
        plane.warmup()  # compiles excluded from every load point
        router = None
        if cascade:
            from deep_vision_tpu.serve.cascade import (CascadeRouter,
                                                       CascadeSpec)
            # small min_sample: the router calibrates organically from
            # its own dual-run sampling during the first load point
            router = CascadeRouter(plane, CascadeSpec(
                cas_front, cas_big, min_sample=30, sample_period=10,
                min_agreement=0.9))
        # the cascade column owns the big name's Zipf slot: a cascaded
        # request is recorded there and ONLY there (never under either
        # tier), so per-model client img/s can't double-count it
        cols = list(models) + (["cascade"] if cascade else [])

        # Zipf-ish popularity: weight ∝ 1/rank^s in `models` order
        weights = [1.0 / (r + 1) ** zipf_s for r in range(len(models))]
        total_w = sum(weights)
        cum, acc = [], 0.0
        for w in weights:
            acc += w / total_w
            cum.append(acc)

        def pick(rng):
            u = rng.random()
            for name, edge in zip(models, cum):
                if u <= edge:
                    return name
            return models[-1]

        points = []
        for clients in loads:
            per_model: dict = {name: [] for name in cols}
            errors = [0]
            retries = [0]
            lock = threading.Lock()
            stop_at = time.perf_counter() + duration_s

            def client(seed):
                # same well-behaved closed-loop client as bench_serve:
                # honor queue-full Retry-After hints with jittered
                # bounded backoff before counting an error
                rng = random.Random(seed)
                local = {name: [] for name in cols}
                local_err, local_retry = 0, 0
                while time.perf_counter() < stop_at:
                    name = pick(rng)
                    col = "cascade" if router is not None \
                        and name == cas_big else name
                    t0 = time.perf_counter()
                    r = None
                    try:
                        for _ in range(3):  # 1 attempt + 2 retries
                            if col == "cascade":
                                r = router.infer(imgs[name],
                                                 timeout=60)[1]
                            else:
                                r = plane.infer(name, imgs[name],
                                                timeout=60)
                            if not (isinstance(r, Shed)
                                    and r.retry_after_s):
                                break
                            local_retry += 1
                            time.sleep(min(r.retry_after_s, 0.25)
                                       * (0.5 + rng.random()))
                        if isinstance(r, (Shed, Quarantined)):
                            local_err += 1
                            continue
                    except Exception:  # noqa: BLE001
                        local_err += 1
                        continue
                    local[col].append(time.perf_counter() - t0)
                with lock:
                    for name in cols:
                        per_model[name].extend(local[name])
                    errors[0] += local_err
                    retries[0] += local_retry

            threads = [threading.Thread(target=client, args=(k,))
                       for k in range(clients)]
            t_start = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t_start
            total = sum(len(v) for v in per_model.values())
            row = {"clients": clients, "requests": total,
                   "errors": errors[0], "retries": retries[0],
                   "img_per_sec": round(total / elapsed, 1),
                   "models": {}}
            for name in cols:
                lat = np.asarray(per_model[name]) * 1e3
                if not len(lat):
                    row["models"][name] = {"requests": 0}
                    continue
                row["models"][name] = {
                    "workload": f"cascade({cascade})"
                    if name == "cascade"
                    else registry.get(name).workload.verb,
                    "requests": int(len(lat)),
                    "share": round(len(lat) / max(1, total), 3),
                    "p50_ms": round(float(np.percentile(lat, 50)), 2),
                    "p95_ms": round(float(np.percentile(lat, 95)), 2),
                    "p99_ms": round(float(np.percentile(lat, 99)), 2)}
            points.append(row)
        stats = plane.stats()
        cas_stats = router.stats() if router is not None else None
    finally:
        plane.stop()
    cstats = stats["cache"]
    lookups = cstats["hits"] + cstats["misses"]
    out = {"metric": "serve_mix_img_per_sec",
           "value": points[-1]["img_per_sec"], "unit": "img/s",
           "models": list(models), "zipf_s": zipf_s,
           "hbm_budget_mb": hbm_budget_mb,
           "max_batch": max_batch, "max_wait_ms": max_wait_ms,
           "pipeline_depth": pipeline_depth,
           "loads": points,
           "cache": {
               "budget_bytes": cstats["budget_bytes"],
               "resident_bytes": cstats["resident_bytes"],
               "hits": cstats["hits"], "misses": cstats["misses"],
               "hit_rate": round(cstats["hits"] / lookups, 3)
               if lookups else None,
               "evictions": cstats["evictions"],
               "admits": cstats["admits"],
               "over_budget": cstats["over_budget"],
               "spilled_mib": round(
                   cstats["spilled_bytes_total"] / 2**20, 3),
               "models": cstats["models"]},
           "plane": stats["plane"],
           "engines": {
               name: {"workload": m["engine"].get("workload"),
                      "batches": m["engine"]["batches"],
                      "compiles": m["engine"]["compiles"],
                      "served": m["engine"]["served"],
                      "admitted": m["engine"]["admission"]["admitted"],
                      # D2H payload of the bulk device_get, per batch
                      # and per served image — generate's fused uint8
                      # epilogue is 4× smaller than an f32 output here
                      "d2h_bytes_per_batch": round(
                          m["engine"]["pipeline"]["d2h_bytes"]
                          / max(1, m["engine"]["batches"]), 1),
                      "d2h_bytes_per_img": round(
                          m["engine"]["pipeline"]["d2h_bytes"]
                          / max(1, m["engine"]["served"]), 1)}
               for name, m in stats["models"].items()},
           "device_kind": jax.devices()[0].device_kind}
    if cas_stats is not None:
        out["cascade"] = {
            "front": cas_stats["front"], "big": cas_stats["big"],
            "threshold": cas_stats["threshold"],
            "calibrated": cas_stats["calibrated"],
            "served": cas_stats["served"],
            "escalations": cas_stats["escalations"],
            "escalation_rate": cas_stats["escalation_rate"],
            "samples": cas_stats["samples"]}
    return out


def bench_serve_cascade(front: str = "lenet5", big: str = "lenet5_big",
                        tiers: tuple | None = None,
                        quant_front: bool = False,
                        loads: tuple = (4, 8), duration_s: float = 2.0,
                        max_batch: int = 8, max_wait_ms: float = 2.0,
                        pipeline_depth: int = 2,
                        min_agreement: float = 0.95,
                        sample_period: int = 10,
                        min_sample: int = 50,
                        train_epochs: int = 2,
                        synthetic_size: int = 1024,
                        holdout: int = 256, **_ignored) -> dict:
    """Confidence-routed cascade A/B (``bench.py --serve-cascade``):
    big-model-only serving vs the cascade router (serve/cascade.py)
    over the same control plane, at matched top-1 quality.

    ``tiers`` names the whole chain (default the 2-tier
    ``front``/``big`` pair; ``--tiers 3`` on the CLI picks
    lenet5_nano:lenet5:lenet5_big) and ``quant_front`` serves tier 0
    int8-resident (``--cascade-quant-front``, synthetic-calibrated PTQ
    — the production boot path).

    Every tier TRAINS first — ``cli.train --synthetic`` called in THIS
    process, because a chip belongs to one process and this one serves
    from it afterwards — for a couple of epochs on the blob dataset: an
    untrained chain has no meaningful agreement structure, so the
    calibration story would be vacuous.  The cascade then calibrates
    EVERY hop from live dual-run
    samples exactly as in production (no histogram backdoor), a
    labeled held-out set scores top-1 accuracy for big-only vs cascade
    (the matched-quality check), and closed-loop clients sweep
    ``loads`` twice per point — big-only, then cascade — for the
    img/s ratio.  Reports escalation rate, per-hop thresholds,
    per-tier p50/p99, and the accuracy deltas; docs/PERF.md records
    the methodology."""
    import contextlib
    import os
    import sys
    import tempfile
    import threading

    import numpy as np

    from deep_vision_tpu.cli.train import main as train_main
    from deep_vision_tpu.core.config import get_config
    from deep_vision_tpu.data.synthetic import synthetic_classification
    from deep_vision_tpu.serve.admission import (AdmissionController,
                                                 Shed)
    from deep_vision_tpu.serve.cascade import CascadeRouter, CascadeSpec
    from deep_vision_tpu.serve.engine import BatchingEngine
    from deep_vision_tpu.serve.faults import Quarantined
    from deep_vision_tpu.serve.models import ModelControlPlane
    from deep_vision_tpu.serve.registry import ModelRegistry
    from deep_vision_tpu.serve.workloads import ClassifyWorkload

    top1 = ClassifyWorkload.top1
    if tiers is None:
        tiers = (front, big)
    tiers = tuple(tiers)
    front, big = tiers[0], tiers[-1]
    registry = ModelRegistry()
    admissions: dict = {}

    def admission_for(name):
        if name not in admissions:
            admissions[name] = AdmissionController(name=name)
        return admissions[name]

    def engine_factory(sm):
        return BatchingEngine(sm, max_batch=max_batch,
                              max_wait_ms=max_wait_ms,
                              pipeline_depth=pipeline_depth,
                              admission=admission_for(sm.name))

    plane = ModelControlPlane(registry, engine_factory,
                              admission_factory=admission_for)
    out: dict = {"metric": "serve_cascade_speedup", "unit": "x",
                 "front": front, "big": big, "tiers": list(tiers),
                 "quant_front": bool(quant_front),
                 "train_epochs": train_epochs,
                 "min_agreement": min_agreement,
                 "sample_period": sample_period,
                 "min_sample": min_sample,
                 "max_batch": max_batch, "max_wait_ms": max_wait_ms}
    with tempfile.TemporaryDirectory() as wd:
        for name in tiers:
            t0 = time.perf_counter()
            # stdout carries the one JSON line; the trainer's log goes
            # to stderr with everything else
            with contextlib.redirect_stdout(sys.stderr):
                train_main(["-m", name, "--synthetic",
                            "--synthetic-size", str(synthetic_size),
                            "--epochs", str(train_epochs),
                            "--workdir", os.path.join(wd, name)])
            print(f"[cascade] trained {name} in "
                  f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)
        # float32 wire: the tiers see the exact training distribution
        # (the synthetic blobs are float images, not 0-255 pixels).
        # Non-final tiers carry the fused confidence epilogue; tier 0
        # optionally serves int8-resident (synthetic-calibrated PTQ)
        sms = []
        for i, name in enumerate(tiers):
            sms.append(registry.load_checkpoint(
                name, os.path.join(wd, name),
                cascade_topk=5 if i < len(tiers) - 1 else 0,
                infer_dtype="int8" if quant_front and i == 0
                else "float32"))
        cfg = get_config(big)
        try:
            for sm in sms:
                plane.deploy(sm)
            plane.warmup()
            spec = CascadeSpec(*tiers,
                               min_agreement=min_agreement,
                               sample_period=sample_period,
                               min_sample=min_sample)
            router = CascadeRouter(plane, spec)
            data = synthetic_classification(
                holdout, cfg.image_size, cfg.channels,
                cfg.num_classes, seed=7)
            imgs = [np.ascontiguousarray(x) for x in data["image"]]
            labels = [int(y) for y in data["label"]]

            # -- quality: big-only reference answers ------------------
            big_cls = []
            for x in imgs:
                r = plane.infer(big, x, timeout=120)
                big_cls.append(top1(r)[0])
            big_acc = sum(c == y for c, y in zip(big_cls, labels)) \
                / len(labels)

            # -- calibrate EVERY hop through the REAL sampling path ---
            def uncalibrated():
                return [h.index for h in router.hops
                        if h.threshold is None]

            warm = 0
            cap = 40 * sample_period * min_sample * len(router.hops)
            while uncalibrated() and warm < cap:
                router.infer(imgs[warm % len(imgs)], timeout=120)
                warm += 1
            out["calibrated"] = not uncalibrated()
            out["threshold"] = router.threshold
            out["hop_thresholds"] = [h.threshold for h in router.hops]
            out["warm_requests"] = warm

            # -- quality: cascade answers on the same held-out set ----
            cas_cls, tier_counts = [], {}
            for x in imgs:
                tier, row = router.infer(x, timeout=120)
                tier_counts[tier] = tier_counts.get(tier, 0) + 1
                cas_cls.append(top1(row)[0])
            cas_acc = sum(c == y for c, y in zip(cas_cls, labels)) \
                / len(labels)
            matched = sum(c == b for c, b in zip(cas_cls, big_cls)) \
                / len(big_cls)
            out["quality"] = {
                "holdout": len(imgs),
                "big_top1_acc": round(big_acc, 4),
                "cascade_top1_acc": round(cas_acc, 4),
                "matched_top1": round(matched, 4),
                "holdout_tiers": tier_counts}

            # -- throughput: big-only vs cascade per load point -------
            def sweep(infer_one):
                lat: list = []
                errors = [0]
                lock = threading.Lock()
                stop_at = time.perf_counter() + duration_s

                def client(seed):
                    rng = random.Random(seed)
                    local, errs = [], 0
                    while time.perf_counter() < stop_at:
                        x = imgs[rng.randrange(len(imgs))]
                        t0 = time.perf_counter()
                        try:
                            r = infer_one(x)
                        except Exception:  # noqa: BLE001
                            errs += 1
                            continue
                        if isinstance(r, (Shed, Quarantined)):
                            errs += 1
                            continue
                        local.append(time.perf_counter() - t0)
                    with lock:
                        lat.extend(local)
                        errors[0] += errs
                threads = [threading.Thread(target=client, args=(k,))
                           for k in range(clients)]
                t_start = time.perf_counter()
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                elapsed = time.perf_counter() - t_start
                arr = np.asarray(lat) * 1e3
                return {"requests": len(lat), "errors": errors[0],
                        "img_per_sec": round(len(lat) / elapsed, 1),
                        "p50_ms": round(float(np.percentile(arr, 50)), 2)
                        if len(lat) else None,
                        "p99_ms": round(float(np.percentile(arr, 99)), 2)
                        if len(lat) else None}

            points = []
            for clients in loads:
                ref = sweep(lambda x: plane.infer(big, x, timeout=120))
                cas = sweep(
                    lambda x: router.infer(x, timeout=120)[1])
                speedup = cas["img_per_sec"] / ref["img_per_sec"] \
                    if ref["img_per_sec"] else None
                points.append({"clients": clients,
                               "big_only": ref, "cascade": cas,
                               "speedup": round(speedup, 2)
                               if speedup else None})
            rstats = router.stats()
            out.update({
                "value": points[-1]["speedup"],
                "loads": points,
                "cascade": {
                    "threshold": rstats["threshold"],
                    "served": rstats["served"],
                    "escalations": rstats["escalations"],
                    "escalation_rate": rstats["escalation_rate"],
                    "samples": rstats["samples"],
                    "agreement": rstats["agreement"],
                    "hops": [{"hop": h["hop"], "tier": h["tier"],
                              "threshold": h["threshold"],
                              "agreement": h["agreement"],
                              "escalations": h["escalations"]}
                             for h in rstats["hops"]],
                    "latency": rstats["latency"]},
                "device_kind": jax.devices()[0].device_kind})
        finally:
            plane.stop()
    return out


def bench_gateway(model_name: str = "lenet5", loads: tuple = (1, 8),
                  duration_s: float = 2.0, max_batch: int = 8,
                  max_wait_ms: float = 2.0, pipeline_depth: int = 2,
                  backends: int = 2, **_ignored) -> dict:
    """Gateway failover bench (``bench.py --gateway``): N in-process
    backend serve stacks (engine + HTTP front-end each) behind one
    ``serve/gateway.py`` front tier, closed-loop HTTP clients through
    the gateway — then, a third of the way into the TOP load point,
    backend 0 is hard-killed (sockets die mid-flight, the SIGKILL
    shape) while the load keeps running.

    The JSON's ``failover`` block is the methodology output
    (docs/PERF.md "Gateway failover latency"): client-visible errors
    after the kill (the contract says 0 — every admitted request fails
    over), how long until the breaker stopped routing to the corpse,
    and the worst client latency inside the 1 s post-kill window (the
    failover tax: connect-fail detection + jittered backoff + the
    retry on the survivor).  Load points carry ``errors`` and
    ``retries`` like the ``--serve`` bench, plus the gateway's own
    counters (retries, failovers, breaker transitions, hedges)."""
    import http.client
    import sys
    import tempfile
    import threading

    import numpy as np

    from deep_vision_tpu.core.config import get_config
    from deep_vision_tpu.core.restore import load_state
    from deep_vision_tpu.serve.engine import BatchingEngine
    from deep_vision_tpu.serve.gateway import Gateway, GatewayServer
    from deep_vision_tpu.serve.http import ServeServer
    from deep_vision_tpu.serve.registry import (CheckpointServingModel,
                                                ModelRegistry)

    cfg = get_config(model_name)
    with tempfile.TemporaryDirectory() as td:
        model, state = load_state(cfg, td,
                                  log=lambda m: print(m, file=sys.stderr))
    sm = CheckpointServingModel(model_name, cfg, model, state)
    registry = ModelRegistry()
    registry.add(sm)
    img = np.random.RandomState(0).randn(
        *sm.input_shape).astype(np.float32)
    body = json.dumps({"pixels": img.tolist()}).encode()
    engines = [BatchingEngine(sm, max_batch=max_batch,
                              max_wait_ms=max_wait_ms,
                              pipeline_depth=pipeline_depth).start()
               for _ in range(backends)]
    for eng in engines:
        eng.warmup()
    servers = [ServeServer(registry, {sm.name: eng},
                           port=0).start_background()
               for eng in engines]
    gw = Gateway([f"127.0.0.1:{s.port}" for s in servers],
                 probe_interval_s=0.05, retry_budget=3,
                 breaker_threshold=2, breaker_cooldown_s=30.0).start()
    gsrv = GatewayServer(gw, port=0).start_background()
    points = []
    failover: dict = {}
    try:
        for li, clients in enumerate(loads):
            kill_point = li == len(loads) - 1  # chaos at the top load
            latencies: list = []
            errors = [0]
            retries = [0]
            lock = threading.Lock()
            t_base = time.perf_counter()
            stop_at = t_base + duration_s
            t_kill = [None]

            def client(seed):
                rng = random.Random(seed)
                local, local_err, local_retry = [], 0, 0
                # ONE persistent keep-alive connection per worker (it
                # reconnects lazily after close()): the bench pays the
                # TCP handshake once, not once per request, matching
                # how production clients drive the edge
                conn = http.client.HTTPConnection(
                    "127.0.0.1", gsrv.port, timeout=60)
                while time.perf_counter() < stop_at:
                    t0 = time.perf_counter()
                    try:
                        for _ in range(3):
                            try:
                                conn.request(
                                    "POST", "/v1/classify", body,
                                    {"Content-Type":
                                     "application/json"})
                                r = conn.getresponse()
                                r.read()
                            except (OSError,
                                    http.client.HTTPException):
                                conn.close()  # stale conn: redial
                                raise
                            if r.will_close:
                                conn.close()
                            if r.status == 200:
                                break
                            if r.status != 429:
                                raise RuntimeError(f"HTTP {r.status}")
                            # cooperative retry budget: the gateway
                            # reports its remaining per-backend retry
                            # tokens on every response — when IT is out
                            # of budget, the client stops adding its
                            # own retries on top, so the two layers
                            # never jointly multiply offered load
                            # (docs/SERVING.md "Retry budgets")
                            budget = r.headers.get("X-DVT-Retry-Budget")
                            if budget is not None \
                                    and float(budget) < 1.0:
                                raise RuntimeError(
                                    "429 with retry budget exhausted")
                            local_retry += 1
                            ra = float(r.headers.get(
                                "Retry-After") or 1)
                            time.sleep(min(ra, 0.25)
                                       * (0.5 + rng.random()))
                        else:
                            local_err += 1
                            continue
                    except Exception:  # noqa: BLE001 — failover misses
                        local_err += 1
                        continue
                    local.append((t0 - t_base,
                                  time.perf_counter() - t0))
                conn.close()
                with lock:
                    latencies.extend(local)
                    errors[0] += local_err
                    retries[0] += local_retry

            def killer():
                time.sleep(duration_s / 3)
                t_kill[0] = time.perf_counter() - t_base
                servers[0].httpd.shutdown()
                servers[0].httpd.server_close()
                engines[0].stop(timeout=1)
                # breaker-open latency: poll until routing excludes it
                t0 = time.perf_counter()
                while gw.backends[0].routable() \
                        and time.perf_counter() - t0 < 5:
                    time.sleep(0.002)
                failover["breaker_open_ms"] = round(
                    (time.perf_counter() - t0) * 1e3, 1)

            threads = [threading.Thread(target=client, args=(k,))
                       for k in range(clients)]
            if kill_point:
                threads.append(threading.Thread(target=killer))
            t_start = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t_start
            lat_ms = np.asarray([x[1] for x in latencies]) * 1e3
            points.append({
                "clients": clients, "requests": len(latencies),
                "errors": errors[0], "retries": retries[0],
                "img_per_sec": round(len(latencies) / elapsed, 1),
                "p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
                "p95_ms": round(float(np.percentile(lat_ms, 95)), 2),
                "p99_ms": round(float(np.percentile(lat_ms, 99)), 2)})
            if kill_point and t_kill[0] is not None:
                after = [x for x in latencies if x[0] >= t_kill[0]]
                window = [x[1] * 1e3 for x in after
                          if x[0] < t_kill[0] + 1.0]
                failover.update({
                    "kill_at_s": round(t_kill[0], 3),
                    "requests_after_kill": len(after),
                    "errors_after_kill": errors[0],
                    "max_ms_in_1s_window": round(max(window), 2)
                    if window else None})
        counters = gw.counters()
        reports = {b.name: b.report() for b in gw.backends}
    finally:
        gsrv.shutdown()
        gw.stop()
        for srv in servers[1:]:
            srv.shutdown()
        for eng in engines[1:]:
            eng.stop()
    return {"metric": f"gateway_{model_name}_img_per_sec",
            "value": points[-1]["img_per_sec"], "unit": "img/s",
            "model": model_name, "backends": backends,
            "max_batch": max_batch, "max_wait_ms": max_wait_ms,
            "pipeline_depth": pipeline_depth,
            "loads": points, "failover": failover,
            "gateway": counters, "backend_reports": reports,
            "device_kind": jax.devices()[0].device_kind}


def _serve_stack(model_name: str, max_batch: int, max_wait_ms: float,
                 pipeline_depth: int):
    """One warmed engine + registry for the HTTP edge benches — built
    once and shared across server variants so the A/B isolates the
    front-end, not the compile."""
    import contextlib
    import sys
    import tempfile

    import numpy as np

    from deep_vision_tpu.serve.engine import BatchingEngine
    from deep_vision_tpu.serve.registry import ModelRegistry

    registry = ModelRegistry()
    with tempfile.TemporaryDirectory() as td, \
            contextlib.redirect_stdout(sys.stderr):
        # load_checkpoint (not bare load_state): it stamps
        # params_digest, without which the response cache has no
        # version identity and stays silently cold.  Its random-init
        # warning prints to stdout, which must stay JSON-only here.
        sm = registry.load_checkpoint(model_name, td)
    img = np.random.RandomState(0).randn(
        *sm.input_shape).astype(np.float32)
    body = json.dumps({"pixels": img.tolist()}).encode()
    eng = BatchingEngine(sm, max_batch=max_batch,
                         max_wait_ms=max_wait_ms,
                         pipeline_depth=pipeline_depth).start()
    eng.warmup()
    return registry, sm, eng, body


def bench_serve_edge(model_name: str = "lenet5",
                     loads: tuple = (4, 16, 32),
                     duration_s: float = 2.0, max_batch: int = 8,
                     max_wait_ms: float = 2.0,
                     pipeline_depth: int = 2, **_ignored) -> dict:
    """Edge A/B (``bench.py --serve-edge``): the selector event loop
    vs the thread-per-request baseline, same engine, real HTTP.

    For each front-end, C closed-loop clients with persistent
    keep-alive connections sweep the load points (p50/p99, img/s), and
    a single-threaded churn probe measures requests/s with a FRESH
    connection per request vs reusing one — the per-connection tax
    (accept + thread spawn on the baseline; accept only on the edge).
    The methodology claim (docs/PERF.md): the edge sustains the top
    load point at equal-or-better p99 without spawning a thread per
    connection, and its churn overhead is the smaller delta."""
    import http.client
    import threading

    import numpy as np

    from deep_vision_tpu.serve.http import ServeServer

    registry, sm, eng, body = _serve_stack(
        model_name, max_batch, max_wait_ms, pipeline_depth)
    variants = []
    try:
        for edge in (True, False):
            srv = ServeServer(registry, {sm.name: eng},
                              port=0, edge=edge).start_background()
            points = []
            try:
                for clients in loads:
                    latencies: list = []
                    errors = [0]
                    lock = threading.Lock()
                    stop_at = time.perf_counter() + duration_s

                    def client():
                        conn = http.client.HTTPConnection(
                            "127.0.0.1", srv.port, timeout=60)
                        local, local_err = [], 0
                        while time.perf_counter() < stop_at:
                            t0 = time.perf_counter()
                            try:
                                conn.request(
                                    "POST", "/v1/classify", body,
                                    {"Content-Type":
                                     "application/json"})
                                r = conn.getresponse()
                                r.read()
                                if r.will_close:
                                    conn.close()
                                if r.status != 200:
                                    local_err += 1
                                    continue
                            except (OSError,
                                    http.client.HTTPException):
                                conn.close()
                                local_err += 1
                                continue
                            local.append(time.perf_counter() - t0)
                        conn.close()
                        with lock:
                            latencies.extend(local)
                            errors[0] += local_err

                    threads = [threading.Thread(target=client)
                               for _ in range(clients)]
                    t_start = time.perf_counter()
                    for t in threads:
                        t.start()
                    for t in threads:
                        t.join()
                    elapsed = time.perf_counter() - t_start
                    lat = np.asarray(latencies) * 1e3
                    points.append({
                        "clients": clients,
                        "requests": len(latencies),
                        "errors": errors[0],
                        "img_per_sec": round(len(lat) / elapsed, 1),
                        "p50_ms": round(float(np.percentile(lat, 50)),
                                        2),
                        "p99_ms": round(float(np.percentile(lat, 99)),
                                        2)})
                # churn probe: sequential healthz, fresh vs reused conn
                churn = {}
                for mode in ("fresh", "reused"):
                    conn = None
                    n = 0
                    t0 = time.perf_counter()
                    while time.perf_counter() - t0 < min(duration_s,
                                                         1.0):
                        if conn is None or mode == "fresh":
                            if conn is not None:
                                conn.close()
                            conn = http.client.HTTPConnection(
                                "127.0.0.1", srv.port, timeout=10)
                        conn.request("GET", "/v1/healthz")
                        conn.getresponse().read()
                        n += 1
                    conn.close()
                    churn[f"{mode}_req_per_sec"] = round(
                        n / (time.perf_counter() - t0), 1)
                churn["overhead_pct"] = round(
                    (1 - churn["fresh_req_per_sec"]
                     / churn["reused_req_per_sec"]) * 100, 1)
                edge_stats = srv.httpd.stats() if edge else None
            finally:
                srv.shutdown()
            variants.append({
                "front_end": "edge" if edge else "thread",
                "loads": points, "churn": churn, "edge": edge_stats})
    finally:
        eng.stop()
    top = {v["front_end"]: v["loads"][-1] for v in variants}
    return {"metric": f"serve_edge_{model_name}_img_per_sec",
            "value": top["edge"]["img_per_sec"], "unit": "img/s",
            "model": model_name, "max_batch": max_batch,
            "max_wait_ms": max_wait_ms, "variants": variants,
            "top_load": top,
            "device_kind": jax.devices()[0].device_kind}


def bench_serve_trace(model_name: str = "lenet5",
                      duration_s: float = 4.0, rate: float = 60.0,
                      dup_frac: float = 0.4, max_batch: int = 8,
                      max_wait_ms: float = 2.0,
                      pipeline_depth: int = 2,
                      cache_mb: float = 64.0, **_ignored) -> dict:
    """Trace-driven OPEN-LOOP bench (``bench.py --serve-trace``):
    requests arrive on a generated schedule whether or not earlier ones
    finished — a diurnal sine envelope over the base ``rate`` with a 4×
    burst in the middle third, Poisson inter-arrivals throughout.

    ``dup_frac`` of arrivals draw from a small hot payload pool (the
    content-addressed cache's hit source, ≥30% per the methodology);
    the rest are unique.  Tenants split premium/standard/best_effort
    (2:6:2) through ``X-DVT-Tenant`` against a QoS spec whose
    best-effort knee is lowest.  Latency is measured from SCHEDULED
    arrival (queueing delay included — the open-loop honesty), per
    class.  The JSON carries per-class p50/p99 + sheds, the server's
    cache hit rate, and the edge's connection counters (accepted vs
    keep-alive reuses = churn avoided)."""
    import http.client
    import math
    import threading

    import numpy as np

    from deep_vision_tpu.serve.admission import TENANT_HEADER, TenantQoS
    from deep_vision_tpu.serve.cache import ResponseCache
    from deep_vision_tpu.serve.http import ServeServer

    registry, sm, eng, _ = _serve_stack(
        model_name, max_batch, max_wait_ms, pipeline_depth)
    qos = TenantQoS.parse(
        "premium:rate=0,shed_at=1.0,tenants=tenant-p;"
        "standard:rate=0,shed_at=0.85;"
        "best_effort:rate=0,shed_at=0.6,tenants=tenant-b;"
        "default=standard")
    srv = ServeServer(
        registry, {sm.name: eng}, port=0,
        response_cache=ResponseCache(int(cache_mb * 2**20)),
        qos=qos).start_background()

    rng = random.Random(0)
    n_hot = 4  # hot payload pool: what the response cache can reuse
    pool = []
    for i in range(n_hot + 1):
        img = np.random.RandomState(i).randn(
            *sm.input_shape).astype(np.float32)
        pool.append(json.dumps({"pixels": img.tolist()}).encode())
    unique_base = np.random.RandomState(99).randn(
        *sm.input_shape).astype(np.float32)

    # arrival schedule: diurnal sine envelope + midday burst, Poisson
    arrivals = []
    t = 0.0
    while t < duration_s:
        envelope = 0.55 + 0.45 * math.sin(
            2 * math.pi * t / duration_s - math.pi / 2)
        r = rate * envelope
        if duration_s / 3 <= t < duration_s * 2 / 3:
            r *= 4.0  # the burst window
        t += rng.expovariate(max(r, 1e-3))
        if t >= duration_s:
            break
        tenant = rng.choices(
            ["tenant-p", "tenant-s", "tenant-b"],
            weights=(2, 6, 2))[0]
        if rng.random() < dup_frac:
            body = pool[rng.randrange(n_hot)]
        else:
            # unique payload: mutate one pixel deterministically
            u = unique_base.copy()
            u.flat[len(arrivals) % u.size] += len(arrivals) + 1
            body = json.dumps({"pixels": u.tolist()}).encode()
        arrivals.append((t, tenant, body))

    results: dict = {c: {"lat": [], "shed": 0, "errors": 0}
                     for c in ("premium", "standard", "best_effort")}
    cls_of = {"tenant-p": "premium", "tenant-s": "standard",
              "tenant-b": "best_effort"}
    lock = threading.Lock()
    conns = threading.local()

    # service latency (send → response, excluding open-loop queueing)
    # split by the X-DVT-Cache header: the hit-vs-compute comparison
    hit_svc: list = []
    miss_svc: list = []

    def fire(t_sched, tenant, body, t_base):
        try:
            conn = getattr(conns, "c", None)
            if conn is None:
                conn = conns.c = http.client.HTTPConnection(
                    "127.0.0.1", srv.port, timeout=60)
            t_send = time.perf_counter()
            try:
                conn.request("POST", "/v1/classify", body,
                             {"Content-Type": "application/json",
                              TENANT_HEADER: tenant})
                r = conn.getresponse()
                r.read()
                if r.will_close:
                    conn.close()
                    conns.c = None
            except (OSError, http.client.HTTPException):
                conn.close()
                conns.c = None
                raise
            done = time.perf_counter()
            with lock:
                row = results[cls_of[tenant]]
                if r.status == 200:
                    row["lat"].append(done - t_base - t_sched)
                    if r.headers.get("X-DVT-Cache") == "hit":
                        hit_svc.append(done - t_send)
                    else:
                        miss_svc.append(done - t_send)
                elif r.status == 429:
                    row["shed"] += 1
                else:
                    row["errors"] += 1
        except Exception:  # noqa: BLE001 — open loop: count, continue
            with lock:
                results[cls_of[tenant]]["errors"] += 1

    from concurrent.futures import ThreadPoolExecutor

    futures = []
    try:
        with ThreadPoolExecutor(max_workers=64) as pool_exec:
            t_base = time.perf_counter()
            for t_sched, tenant, body in arrivals:
                delay = t_sched - (time.perf_counter() - t_base)
                if delay > 0:
                    time.sleep(delay)
                futures.append(pool_exec.submit(
                    fire, t_sched, tenant, body, t_base))
            for f in futures:
                f.result()
        import urllib.request
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/v1/stats",
                timeout=10) as r:
            stats = json.loads(r.read())
    finally:
        srv.shutdown()
        eng.stop()
    classes = {}
    for name, row in results.items():
        lat = np.asarray(row["lat"]) * 1e3
        classes[name] = {
            "served": len(lat), "shed": row["shed"],
            "errors": row["errors"],
            "p50_ms": round(float(np.percentile(lat, 50)), 2)
            if len(lat) else None,
            "p99_ms": round(float(np.percentile(lat, 99)), 2)
            if len(lat) else None}
    edge_stats = stats.get("edge", {})
    cache_stats = stats.get("response_cache", {})

    def _svc(vals):
        a = np.asarray(vals) * 1e3
        return {"count": len(a),
                "p50_ms": round(float(np.percentile(a, 50)), 2)
                if len(a) else None,
                "p99_ms": round(float(np.percentile(a, 99)), 2)
                if len(a) else None}

    return {"metric": f"serve_trace_{model_name}_cache_hit_rate",
            "value": round(cache_stats.get("hit_rate", 0.0), 3),
            "unit": "hit_rate", "model": model_name,
            "offered": len(arrivals), "rate": rate,
            "dup_frac": dup_frac, "duration_s": duration_s,
            "classes": classes,
            "service": {"cache_hit": _svc(hit_svc),
                        "compute": _svc(miss_svc)},
            "cache": cache_stats,
            "edge": {k: edge_stats.get(k) for k in
                     ("accepted", "keepalive_reuses", "requests",
                      "open_connections")},
            "qos": stats.get("qos", {}),
            "device_kind": jax.devices()[0].device_kind}


def bench_deploy(model_name: str = "lenet5",
                 watch_interval_s: float = 0.05, **_ignored) -> dict:
    """Continuous-deploy reaction bench (``bench.py --deploy``).

    Two numbers, both end to end (docs/PERF.md "Deploy reaction"):

    ``deploy_reaction_ms``  a REAL async-Orbax checkpoint becomes
        durable mid-load → the new version is ACTIVE and serving: the
        watcher's two-poll debounce, the candidate restore, the
        synthetic accuracy-gate eval, and the shadow/canary/promote
        rollout under a live closed-loop client (the canary gates need
        traffic to clear).  The structural floor is 2× the watch
        interval (debounce) plus the canary dwell.  The ledger's
        wall-clock timestamps decompose the total.

    ``scale_up_reaction_ms`` / ``scale_down_reaction_ms``  sustained
        queue pressure → ``add_replica()`` returned, and first
        observed idle → ``remove_replica()`` drained and returned.
        The autoscaler is driven synchronously (``tick()`` per
        interval, the documented bench seam) so the numbers measure
        the hysteresis windows + the engine's replica build/drain
        cost, not a daemon thread's scheduling jitter."""
    import os
    import sys
    import tempfile
    import threading

    import numpy as np

    from deep_vision_tpu.core.checkpoint import Checkpointer
    from deep_vision_tpu.core.config import get_config
    from deep_vision_tpu.core.restore import load_state
    from deep_vision_tpu.deploy import (AccuracyGate, CheckpointWatcher,
                                        DeploymentHistory,
                                        ReplicaAutoscaler)
    from deep_vision_tpu.serve.admission import Shed
    from deep_vision_tpu.serve.engine import BatchingEngine
    from deep_vision_tpu.serve.models import (CanaryPolicy,
                                              ModelControlPlane,
                                              WeightCache)
    from deep_vision_tpu.serve.registry import ModelRegistry
    from deep_vision_tpu.serve.replicas import ReplicatedEngine

    out: dict = {"metric": "deploy_reaction_ms", "unit": "ms",
                 "model": model_name,
                 "watch_interval_s": watch_interval_s,
                 "debounce_floor_ms": round(2 * watch_interval_s * 1e3,
                                            1),
                 "device_kind": jax.devices()[0].device_kind}

    # -- part 1: checkpoint durable → new version ACTIVE ---------------
    reg = ModelRegistry()
    with tempfile.TemporaryDirectory() as workdir:
        sm = reg.load_checkpoint(model_name, workdir)
        plane = ModelControlPlane(
            reg, lambda m: BatchingEngine(m, buckets=[8], max_wait_ms=2),
            cache=WeightCache(budget_bytes=0),
            policy=CanaryPolicy(canary_frac=0.5, min_requests=3,
                                max_p99_ratio=None, phase_timeout_s=60.0))
        plane.deploy(sm, workdir=workdir)
        history = DeploymentHistory()
        watcher = CheckpointWatcher(
            plane, history, interval_s=watch_interval_s,
            gate=AccuracyGate()).watch(model_name)
        img = np.random.RandomState(0).randn(
            *sm.input_shape).astype(np.float32)
        errors: list = []
        stop = threading.Event()

        def load_loop():
            while not stop.is_set():
                try:
                    r = plane.infer(model_name, img, timeout=30)
                    if isinstance(r, Shed):
                        errors.append(repr(r))
                except Exception as e:  # noqa: BLE001 — every failure is a lost request
                    errors.append(repr(e))

        client = threading.Thread(target=load_loop, daemon=True)
        client.start()
        ckpt = None
        try:
            # warm the infer path before the clock starts
            time.sleep(0.2)
            cfg = get_config(model_name)
            with tempfile.TemporaryDirectory() as seed_dir:
                _, state = load_state(cfg, seed_dir,
                                      log=lambda *a, **k: None)
            ckpt = Checkpointer(os.path.join(workdir, "checkpoints"))
            watcher.start()
            ckpt.save(1, state)
            ckpt.wait_until_finished()
            t0 = time.perf_counter()
            deadline = t0 + 120.0
            while plane.active_version(model_name).version < 2:
                if time.perf_counter() > deadline:
                    raise SystemExit("deploy bench: promotion timed out")
                time.sleep(0.002)
            out["value"] = round((time.perf_counter() - t0) * 1e3, 1)
            out["deploy_reaction_ms"] = out["value"]
            # the ledger's wall-clock stamps decompose the reaction:
            # durable→candidate is debounce+restore, candidate→
            # gate_passed the held-out eval, gate_passed→promoted the
            # shadow/canary rollout.  The promoted record lands just
            # after the version flips, so give it a beat
            t_led = time.perf_counter() + 5.0
            while history.last_outcome(model_name) != "promoted" \
                    and time.perf_counter() < t_led:
                time.sleep(0.002)
            ts = {e["outcome"]: e["ts"]
                  for e in history.entries(model_name)}
            if {"candidate", "gate_passed", "promoted"} <= ts.keys():
                out["gate_eval_ms"] = round(
                    (ts["gate_passed"] - ts["candidate"]) * 1e3, 1)
                out["rollout_ms"] = round(
                    (ts["promoted"] - ts["gate_passed"]) * 1e3, 1)
        finally:
            stop.set()
            client.join(30)
            watcher.stop()
            if ckpt is not None:
                ckpt.close()
            plane.stop(drain_deadline=5.0)
        if errors:
            print(f"# deploy bench: {len(errors)} client errors: "
                  f"{errors[:3]}", file=sys.stderr)
        out["client_errors"] = len(errors)

    # -- part 2: load step → replica added, idle → replica drained -----
    if len(jax.devices()) < 2:
        # add_replica() needs a spare device; main() forces 2 host
        # devices, so this only trips when the backend initialized
        # before the flag could land
        out["autoscale_skipped"] = \
            f"{len(jax.devices())} device(s): add_replica needs a spare"
        return out
    # fresh model: part 1's v1 weights were freed when the promoted v2
    # retired it (the plane reclaims retired versions' HBM)
    with tempfile.TemporaryDirectory() as td:
        sm = ModelRegistry().load_checkpoint(model_name, td)
    tick_s = 0.02
    scaler_cfg = dict(min_replicas=1, max_replicas=2, interval_s=tick_s,
                      high_water_ms=5.0, up_window=3, down_window=10,
                      cooldown_s=0.2, drain_deadline_s=10.0)
    eng = ReplicatedEngine(sm, devices=jax.devices()[:1], buckets=[8],
                           max_wait_ms=2).start()
    eng.warmup()
    scaler = ReplicaAutoscaler(eng, name=model_name, **scaler_cfg)
    futures: list = []
    feeding = threading.Event()
    feeding.set()

    def feeder():
        # keep a standing backlog so pressure survives the ticks — the
        # bench measures the scaler's reaction, not a burst's drain
        while feeding.is_set():
            if eng._queue.qsize() < 32:
                try:
                    futures.append(eng.submit(img))
                except Exception:  # noqa: BLE001 — shed under pressure is expected here
                    pass
            else:
                time.sleep(0.001)

    feed = threading.Thread(target=feeder, daemon=True)
    feed.start()
    try:
        t_load = time.perf_counter()
        deadline = t_load + 60.0
        action = None
        while action is None or action["action"] != "scale_up":
            if time.perf_counter() > deadline:
                raise SystemExit("deploy bench: scale-up timed out")
            action = scaler.tick()
            time.sleep(tick_s)
        out["scale_up_reaction_ms"] = round(
            (time.perf_counter() - t_load) * 1e3, 1)
        out["scale_up_floor_ms"] = round(
            scaler_cfg["up_window"] * tick_s * 1e3, 1)
        feeding.clear()
        feed.join(10)
        for f in futures:
            f.result(timeout=30)
        while eng._queue.qsize() or eng.total_inflight():
            time.sleep(0.002)
        t_idle = time.perf_counter()
        deadline = t_idle + 60.0
        action = None
        while action is None or action["action"] != "scale_down":
            if time.perf_counter() > deadline:
                raise SystemExit("deploy bench: scale-down timed out")
            action = scaler.tick()
            time.sleep(tick_s)
        out["scale_down_reaction_ms"] = round(
            (time.perf_counter() - t_idle) * 1e3, 1)
        # the cooldown usually elapses during the drain, so the
        # structural floor is the hysteresis window alone
        out["scale_down_floor_ms"] = round(
            scaler_cfg["down_window"] * tick_s * 1e3, 1)
        out["autoscaler"] = {k: scaler_cfg[k] for k
                             in ("up_window", "down_window",
                                 "cooldown_s", "high_water_ms")}
        out["autoscaler"]["tick_s"] = tick_s
        out["scale_requests"] = len(futures)
    finally:
        feeding.clear()
        eng.stop()
    return out


def _child_bench_line(label: str, extra: list[str]) -> str | None:
    """Run ``bench.py <extra>`` in a child and return its JSON line, or
    None after printing the child's stderr.

    A chip belongs to one process: the child needs it, so this parent
    must never have opened it.  Importing jax and setting config values
    does not; the first ``jax.devices()`` or device array would — and
    the child would then fail or hang — so that is checked, not assumed."""
    import subprocess
    import sys

    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            "bench.py parent initialized a JAX backend before starting "
            f"the '{label}' child; the child cannot get the chip")
    proc = subprocess.run([sys.executable, __file__] + extra,
                          capture_output=True, text=True)
    line = next((ln for ln in reversed(proc.stdout.splitlines())
                 if ln.startswith("{")), None)
    if line is None:
        print(f"# {label} FAILED:\n{proc.stderr[-2000:]}", flush=True)
    else:
        print(line, flush=True)
    return line


def bench_all() -> list[dict]:
    """Run every task bench in its own subprocess (fresh process ⇒
    per-model peak-HBM stats and no cross-compile interference)."""
    results, failed = [], []
    for task in ("resnet50", "yolo", "centernet", "hourglass", "cyclegan",
                 "dcgan", "infer:resnet50", "infer:yolo"):
        if task == "resnet50":
            extra = []
        elif task.startswith("infer:"):
            extra = ["--infer", task.split(":", 1)[1]]
        else:
            extra = ["--task", task]
        line = _child_bench_line(task, extra)
        if line is None:
            failed.append(task)
            continue
        results.append(json.loads(line))
    if failed:
        raise SystemExit(f"task benches failed: {', '.join(failed)}")
    return results


def _make_synthetic_imagenet(tmp: str, n_images: int, jpeg_size: int,
                             val_images: int = 0) -> tuple[str, str, str]:
    """Synthetic flat-ImageNet tree shared by the pipeline/coupled benches:
    8 synsets, 8 distinct base images saved as JPEGs, labels.txt.
    Returns (train_dir, labels_path, val_dir_or_empty)."""
    import os

    import numpy as np
    from PIL import Image

    root = os.path.join(tmp, "train")
    os.makedirs(root)
    rng = np.random.default_rng(0)
    synsets = [f"n{i:08d}" for i in range(8)]
    labels = os.path.join(tmp, "labels.txt")
    with open(labels, "w") as f:
        for sn in synsets:
            f.write(f"{sn} synthetic\n")
    base = rng.integers(0, 255, (8, jpeg_size, jpeg_size, 3), dtype=np.uint8)
    for i in range(n_images):
        Image.fromarray(base[i % 8]).save(
            os.path.join(root, f"{synsets[i % 8]}_{i}.JPEG"), quality=85)
    val_root = ""
    if val_images:
        val_root = os.path.join(tmp, "val")
        os.makedirs(val_root)
        for i in range(val_images):
            Image.fromarray(base[i % 8]).save(
                os.path.join(val_root, f"{synsets[i % 8]}_{i}.JPEG"),
                quality=85)
    return root, labels, val_root


def bench_coupled(batch: int = 256, epochs: int = 13,
                  n_images: int = 10240, image_size: int = 224) -> dict:
    """The COUPLED end-to-end number (VERDICT r3 #2): a real ``cli.train``
    run — raw-store dvrec records → host batch assembly → H2D prefetch →
    scan-dispatched train steps → logging → per-epoch eval + checkpoint —
    not a decoupled step bench.  Sustained rate = images trained in
    epochs 2..N over the wall time from epoch 2's first log record to the
    run's last record (epoch 1 absorbs compiles; with one scan group per
    epoch the first post-epoch-1 record lands at epoch 2's END, so the
    window covers epochs 3..N), INCLUDING eval and checkpoint pauses.

    Defaults: 10,240 synthetic 400² JPEGs packed once with
    ``prepare_data imagenet --store raw`` (40 steps/epoch = one
    scan_steps=40 group), EMA on — the production recipe shape.
    """
    import os
    import shutil
    import tempfile

    tmp = tempfile.mkdtemp(prefix="bench_coupled_")
    try:
        root, labels, val_root = _make_synthetic_imagenet(
            tmp, n_images, 400, val_images=1024)

        from deep_vision_tpu.data.prep import prepare_imagenet
        from deep_vision_tpu.data.transforms import imagenet_resize_for

        recs = os.path.join(tmp, "recs")
        for split, src in (("train", root), ("val", val_root)):
            prepare_imagenet(src, labels, recs,
                             split=split, num_shards=8, num_workers=1,
                             store="raw",
                             resize=imagenet_resize_for(image_size))
        shutil.rmtree(root)
        shutil.rmtree(val_root)

        from deep_vision_tpu.cli.train import main as train_main

        workdir = os.path.join(tmp, "run")
        rc = train_main([
            "-m", "resnet50", "--data-root", recs, "--data-format",
            "records", "--epochs", str(epochs), "--batch-size", str(batch),
            "--image-size", str(image_size),
            "--scan-steps", "40", "--ema-decay", "0.9999",
            "--num-workers", "0", "--workdir", workdir])
        assert rc == 0, f"cli.train failed rc={rc}"

        # parse metrics.jsonl: epoch-1 records absorb compiles; measure
        # from the FIRST record whose step falls in epoch 2 to the last
        # record of the run (includes evals, checkpoints, logging)
        recs_log = []
        with open(os.path.join(workdir, "metrics.jsonl")) as f:
            recs_log = [json.loads(ln) for ln in f if ln.strip()]
        steps_per_epoch = n_images // batch
        first = min((r for r in recs_log if r["step"] > steps_per_epoch),
                    key=lambda r: r["time"])
        t_end = max(r["time"] for r in recs_log)
        last_step = max(r["step"] for r in recs_log)
        # scan-mode logs land at each group's END, so the first record
        # past epoch 1 already includes its own steps' wall time — count
        # images only from that record's step to keep window and
        # numerator aligned
        images = (last_step - first["step"]) * batch
        rate = images / (t_end - first["time"])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return {
        "metric": "resnet50_coupled_train_images_per_sec",
        "value": round(rate, 1),
        "unit": "images/sec/chip",
        "vs_baseline": round(rate / BASELINE_IMG_PER_SEC_PER_CHIP, 2),
        "epochs_measured": (last_step - first["step"]) // steps_per_epoch,
        "steps_measured": last_step - first["step"],
        "batch": batch,
        "image_size": image_size,
        "ema_decay": 0.9999,
        "scan_steps": 40,
        "includes": "host pipeline + prefetch + logging + eval + checkpoint",
    }


def bench_cyclegan_live(steps: int = 20, size: int = 256,
                        batch: int = 1) -> dict:
    """LIVE CycleGAN rate: real ``AdversarialTrainer`` steps INCLUDING
    the per-step host ImagePool exchange (host_prepare → jitted 4-network
    step → host_update fetch of both fake batches), which the pure step
    bench excludes — replaces PERF.md's "a live run is somewhat slower
    still" caveat with a number (VERDICT r3 #6b)."""
    import numpy as np

    from deep_vision_tpu.core.adversarial import AdversarialTrainer
    from deep_vision_tpu.core.config import get_config
    from deep_vision_tpu.data.gan import UnpairedLoader, synthetic_unpaired
    from deep_vision_tpu.models.gan import (
        CycleGANGenerator,
        PatchGANDiscriminator,
    )
    from deep_vision_tpu.parallel import make_mesh
    from deep_vision_tpu.tasks.gan import CycleGANTask

    cfg = get_config("cyclegan")
    cfg.batch_size = batch
    cfg.image_size = size
    a, b = synthetic_unpaired(max(4 * batch, 8), size)
    loader = UnpairedLoader(a, b, batch, seed=0)
    # bf16 like the step bench (bench_task "cyclegan"), so live-vs-step
    # deltas isolate the host exchange, not a dtype change
    task = CycleGANTask(lambda: CycleGANGenerator(dtype=jnp.bfloat16),
                        lambda: PatchGANDiscriminator(dtype=jnp.bfloat16))
    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    import tempfile

    with tempfile.TemporaryDirectory() as wd:
        trainer = AdversarialTrainer(cfg, task, mesh=mesh, workdir=wd)
        rng = jax.random.PRNGKey(0)
        states = trainer.init_states(next(iter(loader)))
        batches = []
        it = iter(loader)
        while len(batches) < steps + 3:
            try:
                batches.append(next(it))
            except StopIteration:
                it = iter(loader)

        def one(states, rng, batch):
            rng, step_rng = jax.random.split(rng)
            batch = task.host_prepare(batch)
            states, outputs, metrics = trainer.train_step(
                states, batch, step_rng)
            task.host_update(outputs)  # device_get of both fake batches
            return states, rng, metrics

        for warm in batches[:3]:  # compile + pool warm
            states, rng, m = one(states, rng, warm)
        float(jax.device_get(m["g_loss"]))
        t0 = time.perf_counter()
        for bt in batches[3:3 + steps]:
            states, rng, m = one(states, rng, bt)
        float(jax.device_get(m["g_loss"]))
        dt = time.perf_counter() - t0
    rate = steps * batch / dt
    return {
        "metric": "cyclegan_live_images_per_sec",
        "value": round(rate, 2),
        "unit": "images/sec/chip",
        "vs_baseline": 0.0,
        "steps": steps,
        "batch": batch,
        "image_size": size,
        "ms_per_step": round(1000 * dt / steps, 1),
        "includes": "host ImagePool exchange (host_prepare/host_update)",
    }


def bench_recipe(batch: int | None = None, steps: int | None = None):
    """Recipe-overhead rows at the ResNet-50 shape: what EMA and
    gradient accumulation actually COST (VERDICT r3 #3) — one fresh
    process per combo so compile caches don't cross-talk."""
    combos = [[],
              ["--ema-decay", "0.9999"],
              ["--grad-accum", "2"],
              ["--grad-accum", "4"],
              ["--ema-decay", "0.9999", "--grad-accum", "2"]]
    common = []
    if batch:
        common += ["--batch", str(batch)]
    if steps:
        common += ["--steps", str(steps)]
    failed = []
    for extra in combos:
        label = " ".join(extra) or "base"
        if _child_bench_line(label, common + extra) is None:
            failed.append(label)
    if failed:
        raise SystemExit(f"recipe benches failed: {', '.join(failed)}")


def bench_pipeline(num_workers: int = 16, batch: int = 256,
                   n_images: int = 4096, jpeg_size: int = 400,
                   image_size: int = 224,
                   device_normalize: bool = True,
                   source: str = "raw") -> dict:
    """Host input-pipeline throughput: synthetic images on disk through the
    REAL ImageNetLoader (read + [decode] + augment + batch assembly), no
    device work.

    SURVEY §7 hard-part #1: this number must meet or beat the chip's
    train-step rate or the chip starves.  ``source`` picks the storage:

    - ``raw``     train-ready uint8 dvrec shards (``prepare_data imagenet
                  --store raw``) — decode-free reads, the production path
                  for 1-core TPU-VM hosts;
    - ``records`` sanitized-JPEG dvrec shards (archival format);
    - ``folder``  flat JPEG dir (the reference's torch-loader layout).
    """
    import os
    import shutil
    import tempfile

    from deep_vision_tpu.data.imagenet import ImageNetLoader

    tmp = tempfile.mkdtemp(prefix="bench_pipeline_")
    try:
        # realistic decode cost: ImageNet train JPEGs average ~400×350
        root, labels_path, _ = _make_synthetic_imagenet(
            tmp, n_images, jpeg_size)

        common = dict(train=True, image_size=image_size,
                      num_workers=num_workers, process_index=0,
                      process_count=1, device_normalize=device_normalize)
        if source in ("raw", "records"):
            from deep_vision_tpu.data.prep import prepare_imagenet

            recs = os.path.join(tmp, "recs")
            prepare_imagenet(root, labels_path, recs,
                             split="train", num_shards=8,
                             num_workers=min(8, os.cpu_count() or 1),
                             store="jpeg" if source == "records" else "raw")
            loader = ImageNetLoader.from_records(recs, "train", batch,
                                                 **common)
        else:
            loader = ImageNetLoader(
                root, labels_path, batch, **common)
        # warm one batch (pool spin-up), then measure a full epoch
        it = iter(loader)
        next(it)
        t0 = time.perf_counter()
        n = batch  # the warm batch came from this epoch's budget
        for b in it:
            n += len(b["label"])
        dt = time.perf_counter() - t0
        loader.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    img_per_sec = (n - batch) / dt
    return {
        "metric": "imagenet_pipeline_images_per_sec",
        "value": round(img_per_sec, 1),
        "unit": "images/sec/host",
        "vs_baseline": round(img_per_sec / BASELINE_IMG_PER_SEC_PER_CHIP, 2),
        "source": source,
        "num_workers": num_workers,
        "jpeg_size": jpeg_size,
        "device_normalize": device_normalize,
        "host_cores": os.cpu_count(),
    }


def bench_input(batch: int = 64, size: int = 96, steps: int = 24,
                depths: tuple = (1, 2, 4), workers: tuple = (0,),
                jpeg_size: int = 160) -> dict:
    """Train-input goodput sweep: wire dtype × prefetch depth × workers.

    Every cell drives the SAME jitted conv step through a
    ``DevicePrefetcher`` (data/pipeline.py) and reports the trainer's
    input-goodput block per cell: sustained img/s, ``input_stall_frac``
    (fraction of consumer wall time spent waiting on input), and H2D
    bytes/step split by batch key.  The only things that change between
    cells are what crosses the wire (uint8 bytes vs host-normalized
    float32 — 4.0× the image DMA) and how many batches are staged ahead.

    ``workers=0`` cells stream in-memory synthetic classification
    arrays (pure wire/prefetch plumbing, no decode cost); ``workers>0``
    cells read synthetic JPEGs through the real ``ImageNetLoader``
    decode/augment pool, so the depth axis shows whether staging hides
    a real producer.
    """
    import os
    import shutil
    import tempfile

    import numpy as np

    from deep_vision_tpu.data.pipeline import DevicePrefetcher
    from deep_vision_tpu.parallel import make_mesh

    mesh = make_mesh()
    n = max(2 * batch, 256)

    from deep_vision_tpu.data.synthetic import synthetic_classification

    data = synthetic_classification(n, size, 3, 10, seed=0)
    lo, span = data["image"].min(), np.ptp(data["image"]) + 1e-9
    u8 = np.round((data["image"] - lo) / span * 255).astype(np.uint8)
    wires = {"uint8": u8, "float32": u8.astype(np.float32) / 255.0}
    labels = data["label"]

    rng = np.random.default_rng(0)
    w0 = jnp.asarray(rng.normal(0, 0.1, (3, 3, 3, 16)).astype(np.float32))

    @functools.partial(jax.jit, donate_argnums=(1,))
    def step(w, b):
        x = b["image"]
        if x.dtype == jnp.uint8:
            x = x.astype(jnp.float32) / 255.0
        else:
            x = x.astype(jnp.float32)
        y = jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jnp.mean(y * y) + 0.0 * jnp.sum(b["label"])

    def memory_batches(images):
        for i in range(steps):
            s = (i * batch) % (n - batch + 1)
            yield {"image": images[s:s + batch],
                   "label": labels[s:s + batch]}

    def run_cell(batch_iter_factory, depth):
        pf = DevicePrefetcher(mesh, depth=depth)
        try:
            # warm compile outside the timed window
            jax.block_until_ready(step(
                w0, next(iter(batch_iter_factory()))))
            t0 = time.perf_counter()
            stream = pf.iterate(batch_iter_factory())
            last, n_batches = None, 0
            for b in stream:
                last = step(w0, b)
                n_batches += 1
            jax.block_until_ready(last)
            dt = time.perf_counter() - t0
            st = stream.stats()
        finally:
            pf.close()
        per_key = {k: int(v / max(1, n_batches))
                   for k, v in st["h2d_bytes_by_key"].items()}
        return {
            "images_per_sec": round(n_batches * batch / dt, 1),
            "input_stall_frac": round(st["input_stall_frac"], 4),
            "h2d_bytes_per_step": int(st["h2d_bytes_per_step"]),
            "h2d_bytes_per_step_by_key": per_key,
            "batches": n_batches,
        }

    cells = []
    tmp = None
    try:
        for nw in workers:
            if nw == 0:
                for wire, images in wires.items():
                    for depth in depths:
                        cell = run_cell(
                            lambda im=images: memory_batches(im), depth)
                        cell.update(wire=wire, depth=depth, workers=0)
                        cells.append(cell)
                continue
            # real decode/augment pool over synthetic JPEGs
            from deep_vision_tpu.data.imagenet import ImageNetLoader

            if tmp is None:
                tmp = tempfile.mkdtemp(prefix="bench_input_")
                root, labels_path, _ = _make_synthetic_imagenet(
                    tmp, max(2 * batch, 256), jpeg_size)
            for wire in ("uint8", "float32"):
                for depth in depths:
                    loader = ImageNetLoader(
                        root, labels_path, batch, train=True,
                        image_size=size, num_workers=nw,
                        device_normalize=wire == "uint8")
                    try:
                        cell = run_cell(lambda ld=loader: iter(ld), depth)
                    finally:
                        loader.close()
                    cell.update(wire=wire, depth=depth, workers=nw)
                    cells.append(cell)
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)

    def _img_bytes(wire, nw):
        for c in cells:
            if c["wire"] == wire and c["workers"] == nw:
                return c["h2d_bytes_per_step_by_key"].get("image", 0)
        return 0

    ratios = {nw: round(_img_bytes("float32", nw)
                        / max(1, _img_bytes("uint8", nw)), 2)
              for nw in workers}
    return {
        "metric": "train_input_goodput",
        "unit": "images/sec",
        "batch": batch, "image_size": size, "steps": steps,
        "backend": jax.default_backend(),
        "host_cores": os.cpu_count(),
        # acceptance: uint8 image DMA is exactly 1/4 of the f32 wire
        "f32_over_u8_image_h2d_ratio": ratios,
        "cells": cells,
    }


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--pipeline", action="store_true",
                   help="measure host input-pipeline throughput instead")
    p.add_argument("--input", action="store_true",
                   help="train-input goodput sweep: wire dtype × prefetch "
                        "depth × workers → img/s, input_stall_frac, H2D "
                        "bytes/step (docs/PERF.md 'Input pipeline')")
    p.add_argument("--input-depths", default="1,2,4",
                   help="prefetch depths to sweep with --input")
    p.add_argument("--input-workers", default="0",
                   help="decode-pool sizes to sweep with --input (0 = "
                        "in-memory arrays, >0 = ImageNetLoader JPEG pool)")
    p.add_argument("--profile", action="store_true")
    p.add_argument("--batch", type=int, default=None,
                   help="per-chip batch (default: 256 for the ResNet "
                        "bench/pipeline; per-model defaults for "
                        "--task/--infer)")
    p.add_argument("--steps", type=int, default=None,
                   help="total train steps to time (default: 80 for the "
                        "ResNet bench, rounded down to whole scan blocks; "
                        "per-task defaults for --task)")
    p.add_argument("--scan-steps", type=int, default=40,
                   help="steps per device dispatch (1 = per-step dispatch)")
    p.add_argument("--num-workers", type=int, default=None,
                   help="worker processes (default: 0 for --source raw — "
                   "decode-free reads are faster inline than through pool "
                   "IPC — else 16)")
    p.add_argument("--host-normalize", action="store_true")
    p.add_argument("--source", choices=("raw", "records", "folder"),
                   default="raw", help="--pipeline storage variant")
    p.add_argument("--task", choices=("yolo", "centernet", "hourglass",
                                      "cyclegan", "dcgan"), default=None,
                   help="bench one non-classification task's train step at "
                        "its reference production shape")
    p.add_argument("--all", action="store_true",
                   help="bench every task (one subprocess each; one JSON "
                        "line per task)")
    p.add_argument("--infer", choices=("resnet50", "yolo"), default=None,
                   help="forward-only serving throughput (yolo includes "
                        "on-device decode + NMS)")
    p.add_argument("--serve", action="store_true",
                   help="closed-loop load generator against the dynamic-"
                        "batching engine (deep_vision_tpu/serve): "
                        "p50/p95/p99 latency + img/s per offered load")
    p.add_argument("--serve-model", default="lenet5",
                   help="config to serve (--serve)")
    p.add_argument("--serve-loads", default="1,8",
                   help="comma-separated closed-loop client counts "
                        "(--serve offered-load points)")
    p.add_argument("--serve-duration", type=float, default=2.0,
                   help="seconds per offered-load point (--serve)")
    p.add_argument("--faults", default="",
                   help="fault-injection spec for --serve (e.g. "
                        "'compute:exception:p=0.05'): benchmark the "
                        "failure paths under load (docs/SERVING.md)")
    p.add_argument("--fault-seed", type=int, default=0,
                   help="seed for probabilistic fault firing (--faults)")
    p.add_argument("--serve-pipeline-depth", type=int, default=2,
                   help="in-flight batch window (--serve): 1 = the "
                        "synchronous comparison path, 2 = overlap batch "
                        "formation/H2D with device compute")
    p.add_argument("--serve-obs", action="store_true",
                   help="observability-overhead comparison (--serve): "
                        "tracing off then on at identical parameters, "
                        "one JSON with the on-run detail + img/s and "
                        "p99 deltas (docs/PERF.md)")
    p.add_argument("--serve-no-trace", action="store_true",
                   help="disable per-request span collection for a "
                        "single --serve run")
    p.add_argument("--serve-wire", action="store_true",
                   help="wire-format comparison sweep (--serve): f32 vs "
                        "uint8 wire x f32 vs bf16 compute, one JSON "
                        "with per-cell latency/throughput/H2D bytes "
                        "(make bench-serve-wire)")
    p.add_argument("--wire-dtype", choices=("float32", "uint8"),
                   default="float32",
                   help="serving wire format for a single --serve run "
                        "(uint8 = raw pixels, on-device normalization)")
    p.add_argument("--infer-dtype", choices=("float32", "bfloat16"),
                   default="float32",
                   help="on-device compute dtype for a single --serve "
                        "run (outputs stay float32)")
    p.add_argument("--serve-mix", action="store_true",
                   help="mixed-workload mix bench: every "
                        "--serve-mix-models config behind one control "
                        "plane sharing a --hbm-budget-mb weight cache, "
                        "Zipf-distributed model popularity; per-model/"
                        "per-workload p99 + D2H bytes/batch + cache "
                        "hit rate per load point (docs/SERVING.md)")
    p.add_argument("--serve-mix-models",
                   default="lenet5,yolov3_toy,hourglass_toy,dcgan",
                   help="comma-separated configs for --serve-mix "
                        "(list order = popularity rank; default spans "
                        "all four workloads: classify/detect/pose/"
                        "generate)")
    p.add_argument("--hbm-budget-mb", type=float, default=0.0,
                   help="weight-cache device-byte budget for "
                        "--serve-mix (0 = uncapped)")
    p.add_argument("--zipf-s", type=float, default=1.1,
                   help="Zipf exponent for --serve-mix model "
                        "popularity (higher = hotter head)")
    p.add_argument("--serve-cascade", action="store_true",
                   help="confidence-routed cascade A/B: train both "
                        "tiers on synthetic data, calibrate the "
                        "escalation threshold from live dual-run "
                        "samples, then sweep --serve-loads big-only vs "
                        "cascaded — img/s ratio at matched held-out "
                        "top-1, escalation rate, per-tier p50/p99 "
                        "(docs/PERF.md, serve/cascade.py)")
    p.add_argument("--cascade", default="",
                   help="'t0:...:big' chain — the tiers for "
                        "--serve-cascade (default lenet5:lenet5_big, "
                        "or the 3-tier nano chain with --tiers 3) "
                        "and, when set, the cascade column source for "
                        "--serve-mix (both names must be in "
                        "--serve-mix-models; '' = no cascade column)")
    p.add_argument("--tiers", type=int, default=2,
                   help="chain length for --serve-cascade when "
                        "--cascade is unset: 3 picks "
                        "lenet5_nano:lenet5:lenet5_big, anything else "
                        "the 2-tier pair")
    p.add_argument("--cascade-quant-front", action="store_true",
                   help="serve the --serve-cascade tier 0 "
                        "int8-resident (PTQ at load, synthetic "
                        "calibration) — the --cascade-quant-front "
                        "production boot path")
    p.add_argument("--cascade-min-agreement", type=float, default=0.95,
                   help="calibration agreement floor for "
                        "--serve-cascade")
    p.add_argument("--cascade-sample-period", type=int, default=10,
                   help="dual-run every Nth request per hop during "
                        "--serve-cascade calibration (larger = less "
                        "sampling tax, slower calibration)")
    p.add_argument("--cascade-min-sample", type=int, default=50,
                   help="dual-run samples a --serve-cascade hop needs "
                        "before it derives a threshold")
    p.add_argument("--cascade-train-epochs", type=int, default=2,
                   help="synthetic training epochs per tier for "
                        "--serve-cascade (more epochs tightens "
                        "front-vs-big agreement)")
    p.add_argument("--serve-edge", action="store_true",
                   help="HTTP front-end A/B: selector event loop "
                        "(keep-alive + pipelining + bounded conns) vs "
                        "thread-per-request baseline on one shared "
                        "engine, plus a fresh-vs-reused connection "
                        "churn probe per variant (docs/PERF.md)")
    p.add_argument("--serve-trace", action="store_true",
                   help="trace-driven OPEN-LOOP bench: diurnal+burst "
                        "Poisson arrivals, duplicate-heavy payload "
                        "pool against the response cache, tenant mix "
                        "against QoS classes; per-class p50/p99 from "
                        "scheduled arrival + cache hit rate + edge "
                        "connection churn (docs/PERF.md)")
    p.add_argument("--trace-rate", type=float, default=60.0,
                   help="base arrival rate (req/s) for --serve-trace "
                        "before the diurnal envelope and burst apply")
    p.add_argument("--trace-dup-frac", type=float, default=0.4,
                   help="fraction of --serve-trace arrivals drawn from "
                        "the hot payload pool (the cache-hit source)")
    p.add_argument("--gateway", action="store_true",
                   help="gateway failover bench: backend serve stacks "
                        "behind serve/gateway.py, HTTP clients through "
                        "the gateway, one backend hard-killed mid-way "
                        "through the top load point; reports failover "
                        "latency + breaker-open time (docs/PERF.md)")
    p.add_argument("--gateway-backends", type=int, default=2,
                   help="backend count for --gateway")
    p.add_argument("--deploy", action="store_true",
                   help="continuous-deploy reaction bench: real async-"
                        "Orbax checkpoint durable → new version ACTIVE "
                        "under live load (watcher debounce + gate + "
                        "canary rollout), plus autoscale scale-up/"
                        "scale-down reaction times (docs/PERF.md)")
    p.add_argument("--watch-interval-s", type=float, default=0.05,
                   help="watcher poll interval for --deploy (the "
                        "debounce floor is 2x this)")
    p.add_argument("--serve-devices", type=int, default=1,
                   help="device-scaling sweep (--serve): bench replica "
                        "counts 1, 2, 4, ... N and emit the scaling "
                        "table (img/s + p99 per count) plus the "
                        "per-replica block of the widest run")
    p.add_argument("--serve-mesh", type=int, default=0,
                   help="mesh-cell sweep over N devices: 1×1 baseline, "
                        "N×1 data-parallel, 1×N model-parallel, and "
                        "the squarest 2-D data×model cell — img/s, "
                        "p99, per-chip param_shard_bytes per cell "
                        "(docs/PERF.md \"Mesh scaling\"); forces N "
                        "host devices when the platform exposes fewer")
    p.add_argument("--serve-batch", action="store_true",
                   help="offline batch tier bench on a forced-host 2x2 "
                        "data×model mesh: bulk-job drain (batch img/s, "
                        "occupancy, occupancy-weighted MFU) plus the "
                        "interactive-vs-batch interference sweep over "
                        "--serve-loads (docs/PERF.md \"Batch tier\", "
                        "docs/BATCH.md)")
    p.add_argument("--batch-images", type=int, default=256,
                   help="bulk-job manifest size for --serve-batch")
    p.add_argument("--ema-decay", type=float, default=0.0,
                   help="measure the train step with the params-EMA "
                        "update in it (the Trainer's --ema-decay)")
    p.add_argument("--grad-accum", type=int, default=1,
                   help="measure with N-microbatch gradient accumulation "
                        "(the Trainer's --grad-accum)")
    p.add_argument("--momentum-dtype", choices=("bfloat16",), default=None,
                   help="store the SGD momentum accumulator in bf16 "
                        "(OptimizerConfig.momentum_dtype) — the optimizer-"
                        "state bandwidth experiment, docs/PERF.md")
    p.add_argument("--recipe", action="store_true",
                   help="one line per recipe-overhead combo (base, EMA, "
                        "grad-accum 2/4, EMA+ga2), each in a fresh process")
    p.add_argument("--coupled", action="store_true",
                   help="full cli.train run on raw records (host pipeline "
                        "+ prefetch + eval + checkpoints), sustained img/s")
    p.add_argument("--live-gan", action="store_true",
                   help="live CycleGAN AdversarialTrainer steps incl. the "
                        "host ImagePool exchange")
    args = p.parse_args()
    from deep_vision_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.all:
        bench_all()
        return
    if args.recipe:
        bench_recipe(batch=args.batch, steps=args.steps)
        return
    if args.input:
        print(json.dumps(bench_input(
            batch=args.batch or 64, steps=args.steps or 24,
            depths=tuple(int(d) for d in args.input_depths.split(",")),
            workers=tuple(int(w) for w in args.input_workers.split(",")))))
        return
    if args.coupled:
        print(json.dumps(bench_coupled(batch=args.batch or 256)))
        return
    if args.live_gan:
        print(json.dumps(bench_cyclegan_live(steps=args.steps or 20,
                                             batch=args.batch or 1)))
        return
    if args.serve_mix:
        print(json.dumps(bench_serve_mix(
            models=tuple(m.strip() for m in
                         args.serve_mix_models.split(",") if m.strip()),
            loads=tuple(int(c) for c in args.serve_loads.split(",")),
            duration_s=args.serve_duration, max_batch=args.batch or 8,
            pipeline_depth=args.serve_pipeline_depth,
            hbm_budget_mb=args.hbm_budget_mb, zipf_s=args.zipf_s,
            cascade=args.cascade or None)))
        return
    if args.serve_cascade:
        if args.cascade:
            chain = tuple(t.strip() for t in args.cascade.split(":"))
        elif args.tiers >= 3:
            chain = ("lenet5_nano", "lenet5", "lenet5_big")
        else:
            chain = ("lenet5", "lenet5_big")
        print(json.dumps(bench_serve_cascade(
            tiers=chain, quant_front=args.cascade_quant_front,
            loads=tuple(int(c) for c in args.serve_loads.split(",")),
            duration_s=args.serve_duration, max_batch=args.batch or 8,
            pipeline_depth=args.serve_pipeline_depth,
            min_agreement=args.cascade_min_agreement,
            sample_period=args.cascade_sample_period,
            min_sample=args.cascade_min_sample,
            train_epochs=args.cascade_train_epochs)))
        return
    if args.deploy:
        # the autoscale half needs a spare device for add_replica();
        # force a second host device before the backend initializes
        # when the platform would otherwise expose one (the `make
        # serve-multi` trick, applied automatically)
        import os
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=2"
            ).strip()
        print(json.dumps(bench_deploy(
            model_name=args.serve_model,
            watch_interval_s=args.watch_interval_s)))
        return
    if args.serve_edge:
        print(json.dumps(bench_serve_edge(
            model_name=args.serve_model,
            loads=tuple(int(c) for c in args.serve_loads.split(",")),
            duration_s=args.serve_duration, max_batch=args.batch or 8,
            pipeline_depth=args.serve_pipeline_depth)))
        return
    if args.serve_trace:
        print(json.dumps(bench_serve_trace(
            model_name=args.serve_model,
            duration_s=args.serve_duration, rate=args.trace_rate,
            dup_frac=args.trace_dup_frac, max_batch=args.batch or 8,
            pipeline_depth=args.serve_pipeline_depth)))
        return
    if args.gateway:
        print(json.dumps(bench_gateway(
            model_name=args.serve_model,
            loads=tuple(int(c) for c in args.serve_loads.split(",")),
            duration_s=args.serve_duration, max_batch=args.batch or 8,
            pipeline_depth=args.serve_pipeline_depth,
            backends=args.gateway_backends)))
        return
    if args.serve_batch:
        # the 2x2 batch-tier mesh needs 4 addressable devices — force
        # host devices before the backend initializes (the --serve-mesh
        # trick), honoring an operator-set XLA_FLAGS
        import os
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4"
            ).strip()
        print(json.dumps(bench_serve_batch(
            model_name=args.serve_model, n_images=args.batch_images,
            max_batch=args.batch or 8,
            pipeline_depth=args.serve_pipeline_depth,
            loads=tuple(int(c) for c in args.serve_loads.split(",")),
            duration_s=args.serve_duration)))
        return
    if args.serve or args.serve_mesh:
        serve_kwargs = dict(
            model_name=args.serve_model,
            loads=tuple(int(c) for c in args.serve_loads.split(",")),
            duration_s=args.serve_duration, max_batch=args.batch or 8,
            pipeline_depth=args.serve_pipeline_depth,
            faults=args.faults, fault_seed=args.fault_seed,
            trace=not args.serve_no_trace)
        if args.serve_mesh:
            # the sweep needs N addressable devices — force host
            # devices before the backend initializes (the --deploy
            # trick), honoring an operator-set XLA_FLAGS
            import os
            flags = os.environ.get("XLA_FLAGS", "")
            if "--xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags + " --xla_force_host_platform_device_count="
                    f"{args.serve_mesh}").strip()
            print(json.dumps(bench_serve_mesh(
                args.serve_mesh, wire_dtype=args.wire_dtype,
                infer_dtype=args.infer_dtype, **serve_kwargs)))
        elif args.serve_obs:
            print(json.dumps(bench_serve_obs(**serve_kwargs)))
        elif args.serve_wire:
            print(json.dumps(bench_serve_wire(**serve_kwargs)))
        elif args.serve_devices > 1:
            print(json.dumps(bench_serve_scaling(
                args.serve_devices, wire_dtype=args.wire_dtype,
                infer_dtype=args.infer_dtype, **serve_kwargs)))
        else:
            print(json.dumps(bench_serve(wire_dtype=args.wire_dtype,
                                         infer_dtype=args.infer_dtype,
                                         **serve_kwargs)))
        return
    if args.infer:
        print(json.dumps(bench_infer(args.infer, steps=args.steps,
                                     batch=args.batch)))
        return
    if args.task:
        print(json.dumps(bench_task(args.task, steps=args.steps,
                                    batch=args.batch,
                                    profile=args.profile)))
        return
    if args.pipeline:
        nw = args.num_workers if args.num_workers is not None \
            else (0 if args.source == "raw" else 16)
        out = bench_pipeline(num_workers=nw, batch=args.batch or 256,
                             device_normalize=not args.host_normalize,
                             source=args.source)
    else:
        out = bench_train_step(batch=args.batch or 256,
                               steps=args.steps or 80,
                               profile=args.profile,
                               scan_steps=args.scan_steps,
                               ema_decay=args.ema_decay,
                               grad_accum=args.grad_accum,
                               momentum_dtype=args.momentum_dtype)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
