"""Training CLI — the one entry point replacing every per-model ``train.py``.

Parity with ``python train.py -m <model> [-c]`` (ResNet/pytorch/train.py:541-562)
plus dataset/workdir flags that the reference hard-coded per directory.

Usage:
    python -m deep_vision_tpu.cli.train -m lenet5 --data-root ~/mnist
    python -m deep_vision_tpu.cli.train -m lenet5 --synthetic --epochs 2
    python -m deep_vision_tpu.cli.train -m resnet50 --resume
"""

from __future__ import annotations

import argparse

#: --profile: past step 20, so that a logged step's ``fetch`` (a log every
#: 10 steps) falls inside the traced span and ``benchmark/spans.py`` can
#: number the executions by it instead of refusing the spans
PROFILE_STEPS = (10, 25)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="deep_vision_tpu trainer")
    p.add_argument("-m", "--model", required=True,
                   help="config name (see --list)")
    p.add_argument("--data-root", default=None, help="dataset directory")
    p.add_argument("--data-format", choices=("folder", "records"),
                   default="folder",
                   help="classification input: flat image dir (folder) or "
                        "prepare_data imagenet dvrec shards (records)")
    p.add_argument("--synthetic", action="store_true",
                   help="synthetic data smoke run (no dataset needed)")
    p.add_argument("--synthetic-size", type=int, default=1024)
    p.add_argument("-c", "--resume", action="store_true",
                   help="resume from latest checkpoint in workdir")
    p.add_argument("--workdir", default=None)
    p.add_argument("--epochs", type=int, default=None, help="override config")
    p.add_argument("--batch-size", type=int, default=None, help="override config")
    p.add_argument("--grad-accum", type=int, default=None,
                   help="gradient-accumulation microbatches per optimizer "
                        "update (full recipe batch on a fraction of HBM)")
    p.add_argument("--ema-decay", type=float, default=None,
                   help="params EMA decay (e.g. 0.9999); eval/serving "
                        "use the averaged copy")
    p.add_argument("--momentum-dtype", choices=("bfloat16",), default=None,
                   help="store the SGD momentum accumulator in bf16 "
                        "(halves optimizer-state HBM; ~1e-3 update "
                        "numerics change — OFF for parity recipes)")
    p.add_argument("--image-size", type=int, default=None,
                   help="override config (smoke runs at low res)")
    p.add_argument("--mesh", default=None,
                   help="mesh spec like 'data=8', 'data=4,model=2', "
                        "'data=2,spatial=4' (image rows sharded over "
                        "'spatial'; GSPMD inserts the conv halo exchanges "
                        "— the activation-memory lever, docs/PERF.md), or "
                        "'data=2,pipe=4' (GPipe pipeline over the stacked "
                        "families: hourglass pose, CenterNet detection)")
    p.add_argument("--microbatches", type=int, default=None,
                   help="pipeline microbatches per step (with a pipe mesh "
                        "axis; default = pipe axis size)")
    p.add_argument("--num-workers", type=int, default=16,
                   help="decode/augment worker processes (ImageNet, "
                        "detection, and pose loaders; 0 = inline prep, "
                        "which also switches record datasets to "
                        "decode-once caching)")
    p.add_argument("--host-normalize", action="store_true",
                   help="float32 jitter+normalize on the HOST (reference "
                        "semantics) instead of fused device preprocessing")
    p.add_argument("--prefetch-depth", type=int, default=None,
                   help="staged H2D prefetch depth: batches resident on "
                        "device ahead of the consuming step (default 2; "
                        "1 = classic double buffering)")
    p.add_argument("--tf-preprocessing", action="store_true",
                   help="TF 'ResNet preprocessing' pipeline (aspect-"
                        "preserving resize + mean subtraction, no jitter) "
                        "instead of the cv2/torch one")
    p.add_argument("--upload", default=None,
                   help="sync checkpoints to this URI after each save "
                        "(path, file://, or gs://)")
    p.add_argument("--pretrained", default=None,
                   help="torch-format state_dict (.pth) to start from "
                        "(the load_model_weights role; any published-"
                        "accuracy arch — see models/pretrained.py); head "
                        "kept only when the class count matches")
    p.add_argument("--override", action="append", default=[],
                   metavar="KEY=JSON",
                   help="set a key of the config's extra block or of its "
                        "architecture (language models: "
                        "num_hidden_layers=10, vocab_size=8192, "
                        "sequence_length=1024; a routed model's share of "
                        "each layer's experts: expert_first=0, "
                        "expert_count=16; a prediction module's "
                        "mtp_loss_weight=0.3); repeatable")
    p.add_argument("--profile", action="store_true",
                   help="jax.profiler trace of steps 10-25 → workdir/profile, "
                        "with the loop's spans (spans.jsonl) and the launch "
                        "record (launch.jsonl) beside it; the span holds a "
                        "logged step (one every 10), whose fetch numbers the "
                        "device's executions for a reader")
    p.add_argument("--list", action="store_true", help="list configs and exit")
    return p


def parse_mesh_spec(spec: str | None):
    from deep_vision_tpu.parallel import make_mesh

    if spec is None:
        return make_mesh()
    sizes = {}
    for part in spec.split(","):
        k, v = part.split("=")
        sizes[k.strip()] = int(v)
    return make_mesh(sizes)


def main(argv=None):
    args = build_parser().parse_args(argv)

    from deep_vision_tpu.core.config import get_config, list_configs

    if args.list:
        print("\n".join(list_configs()))
        return 0

    from deep_vision_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()

    cfg = get_config(args.model)
    if args.epochs is not None:
        cfg.total_epochs = args.epochs
    if args.batch_size is not None:
        cfg.batch_size = cfg.eval_batch_size = args.batch_size
    if args.grad_accum is not None:
        cfg.grad_accum_steps = args.grad_accum
    if args.ema_decay is not None:
        cfg.ema_decay = args.ema_decay
    if args.momentum_dtype is not None:
        cfg.optimizer.momentum_dtype = args.momentum_dtype
    if args.image_size is not None:
        cfg.image_size = args.image_size
    if args.prefetch_depth is not None:
        cfg.prefetch_depth = args.prefetch_depth
    apply_overrides(cfg, args.override)

    from deep_vision_tpu.core.trainer import Trainer
    from deep_vision_tpu.data.loader import ArrayLoader
    from deep_vision_tpu.tasks.classification import ClassificationTask

    mesh = parse_mesh_spec(args.mesh)
    print(f"devices: {mesh.devices.ravel().tolist()} mesh={dict(mesh.shape)}")

    if cfg.task in ("detection", "centernet"):
        return _main_detection(args, cfg, mesh)
    if cfg.task == "pose":
        return _main_pose(args, cfg, mesh)
    if cfg.task.startswith("gan_"):
        return _main_gan(args, cfg, mesh)
    if cfg.task == "language_modeling":
        return _main_language(args, cfg, mesh)
    if cfg.task != "classification":
        raise NotImplementedError(
            f"task '{cfg.task}' CLI wiring lands with its stack")

    task = ClassificationTask(cfg.num_classes, cfg.label_smoothing)
    preprocess_fn = None

    if args.synthetic:
        from deep_vision_tpu.data.synthetic import synthetic_classification

        train_data = synthetic_classification(
            args.synthetic_size, cfg.image_size, cfg.channels,
            cfg.num_classes, seed=1)
        val_data = synthetic_classification(
            max(args.synthetic_size // 4, cfg.batch_size), cfg.image_size,
            cfg.channels, cfg.num_classes, seed=2)
        train_loader = ArrayLoader(train_data, cfg.batch_size, seed=cfg.seed)
        val_loader = ArrayLoader(val_data, cfg.eval_batch_size, shuffle=False,
                                 drop_last=False, pad_last=True)
    elif args.model == "lenet5":
        from deep_vision_tpu.data.mnist import load_mnist

        assert args.data_root, "--data-root required without --synthetic"
        # uint8 wire by default: raw padded bytes cross H2D (4× smaller),
        # the /255 normalize runs as the traced prologue
        dev_norm = not args.host_normalize
        train_data = load_mnist(args.data_root, "train",
                                device_normalize=dev_norm)
        val_data = load_mnist(args.data_root, "test",
                              device_normalize=dev_norm)
        train_loader = ArrayLoader(train_data, cfg.batch_size, seed=cfg.seed)
        val_loader = ArrayLoader(val_data, cfg.eval_batch_size, shuffle=False,
                                 drop_last=False, pad_last=True)
        if dev_norm:
            from deep_vision_tpu.ops.preprocess import make_mnist_preprocess

            preprocess_fn = make_mnist_preprocess()
    else:
        # ImageNet flattened-dir layout (Datasets/ILSVRC2012 prep output):
        # <root>/train/, <root>/val/, <root>/imagenet_2012_metadata.txt
        import os

        from deep_vision_tpu.data.imagenet import ImageNetLoader
        from deep_vision_tpu.data.transforms import imagenet_resize_for

        assert args.data_root, "--data-root required without --synthetic"
        labels = os.path.join(args.data_root, "imagenet_2012_metadata.txt")
        resize = imagenet_resize_for(cfg.image_size)
        # uint8 host pipeline + device-side jitter/normalize (fused into
        # the jit step): 4× less H2D, ~30% less host CPU per image
        if args.tf_preprocessing and args.host_normalize:
            raise SystemExit("--tf-preprocessing and --host-normalize pick "
                             "contradictory pipelines; pass only one")
        preprocessing = "tf" if args.tf_preprocessing else "torch"
        dev_norm = not args.host_normalize and preprocessing == "torch"
        common = dict(train=True, seed=cfg.seed, image_size=cfg.image_size,
                      resize=resize, num_workers=args.num_workers,
                      device_normalize=dev_norm, preprocessing=preprocessing)
        if args.data_format == "records":
            # dvrec shard consumption (the reference's TFRecord trainer path)
            train_loader = ImageNetLoader.from_records(
                args.data_root, "train", cfg.batch_size, **common)
        else:
            train_loader = ImageNetLoader(
                os.path.join(args.data_root, "train"), labels,
                cfg.batch_size, **common)
        val_loader, _ = build_classification_val_loader(
            cfg, args.data_root, "val", cfg.eval_batch_size,
            num_workers=args.num_workers, preprocessing=preprocessing,
            device_normalize=dev_norm, data_format=args.data_format)
        if dev_norm:
            from deep_vision_tpu.ops.preprocess import make_imagenet_preprocess

            # fused Pallas train-ingest (decode+jitter+normalize in one
            # VMEM pass), checked against the XLA path at the REAL
            # per-shard compiled shape before the step bakes it in.
            # cfg.batch_size is per-host; the data axis spans all hosts.
            import jax as _jax

            global_batch = cfg.batch_size * _jax.process_count()
            per_shard = max(
                global_batch // mesh.shape.get("data", 1), 1)
            preprocess_fn = make_imagenet_preprocess(
                use_fused=True,
                fused_shape=(per_shard, cfg.image_size, cfg.image_size, 3),
                mesh=mesh)
            print(f"[input] train ingest: "
                  f"{'fused pallas' if preprocess_fn.fused else 'xla'}")

    trainer = Trainer(cfg, cfg.model(), task, mesh=mesh, workdir=args.workdir,
                      preprocess_fn=preprocess_fn, upload=args.upload)
    if args.profile:
        trainer.profile_steps = PROFILE_STEPS
    state = None
    try:
        if args.pretrained:
            state = _load_pretrained_state(args, cfg, trainer, train_loader)
        state = trainer.fit(train_loader, val_loader, state=state,
                            resume=args.resume)
        final = trainer.evaluate(state, val_loader)
    finally:
        # the ImageNet loaders own decode worker pools: a caller that
        # outlives main() (chip_smoke.py) must not inherit them
        for loader in (train_loader, val_loader):
            if hasattr(loader, "close"):
                loader.close()
    print("final:", " ".join(f"{k}={v:.4f}" for k, v in final.items()))
    return 0


def apply_overrides(cfg, overrides: list):
    """``KEY=JSON`` pairs onto ``cfg.extra`` or, where the key is one of its
    architecture's, onto that; an unknown key is an error, not a new one."""
    import json

    for item in overrides:
        key, _, text = item.partition("=")
        arch = cfg.extra.get("architecture", {})
        target = arch if key in arch else cfg.extra
        if key not in target:
            raise SystemExit(f"--override {key}: config '{cfg.name}' has no "
                             f"such key; have {sorted({*arch, *cfg.extra})}")
        target[key] = json.loads(text)


def build_classification_val_loader(cfg, data_root: str, split: str,
                                    batch: int, num_workers: int = 4,
                                    preprocessing: str = "torch",
                                    device_normalize: bool = False,
                                    data_format: str | None = None):
    """One place for the records-vs-folder/labels/resize wiring shared by
    the train CLI's val loader and ``infer eval`` (so the two can't
    drift).  ``data_format=None`` autodetects dvrec shards; lenet5/MNIST
    roots (idx-ubyte files) get the MNIST loader.
    Returns ``(loader, dataset_size)``."""
    import os

    from deep_vision_tpu.data.imagenet import ImageNetLoader
    from deep_vision_tpu.data.records import list_shards
    from deep_vision_tpu.data.transforms import imagenet_resize_for

    import glob as _glob

    # MNIST root sniff: any idx-ubyte naming variant load_mnist accepts
    # (plain / .gz / dot-idx)
    if _glob.glob(os.path.join(data_root, "t10k-images*idx3-ubyte*")):
        from deep_vision_tpu.data.loader import ArrayLoader
        from deep_vision_tpu.data.mnist import load_mnist

        data = load_mnist(data_root, "train" if split == "train" else "test")
        loader = ArrayLoader(data, batch, shuffle=False, drop_last=False,
                             pad_last=True)
        return loader, len(next(iter(data.values())))
    common = dict(train=False, image_size=cfg.image_size,
                  resize=imagenet_resize_for(cfg.image_size),
                  num_workers=num_workers, preprocessing=preprocessing,
                  device_normalize=device_normalize)
    use_records = data_format == "records" or (
        data_format is None and list_shards(data_root, split))
    if use_records:
        loader = ImageNetLoader.from_records(data_root, split, batch,
                                             **common)
    else:
        labels = os.path.join(data_root, "imagenet_2012_metadata.txt")
        loader = ImageNetLoader(os.path.join(data_root, split), labels,
                                batch, **common)
    return loader, len(loader.ds)


def _load_pretrained_state(args, cfg, trainer, train_loader):
    """Initialize, overlay a torch-format checkpoint, re-place on mesh —
    the reference's pretrained start (resnet50v2.py:137-153)."""
    import jax

    from deep_vision_tpu.models.pretrained import (
        ARCH_IMPORTERS,
        import_pretrained,
    )
    from deep_vision_tpu.parallel import replicate

    if args.model not in ARCH_IMPORTERS:
        raise SystemExit(
            f"--pretrained supports {sorted(ARCH_IMPORTERS)} (torch-format "
            f"checkpoints); '{args.model}' has a different param tree")
    state = trainer.init_state(next(iter(train_loader)))
    merged, head_kept = import_pretrained(
        args.pretrained, args.model,
        {"params": jax.device_get(state.params),
         "batch_stats": jax.device_get(state.batch_stats)})
    print(f"[pretrained] loaded {args.model} weights from {args.pretrained} "
          f"(head {'kept' if head_kept else 'fresh'})")
    return replicate(
        state.replace(params=merged["params"],
                      batch_stats=merged["batch_stats"]), trainer.mesh)


def _maybe_pipelined(model, mesh, args):
    """Wrap ``model`` for pipeline-parallel training when the mesh has a
    pipe axis; clean CLI error for families with no stage sequence."""
    if mesh.shape.get("pipe", 1) <= 1:
        return model
    from deep_vision_tpu.parallel.pipelined import PipelinedModel

    try:
        model = PipelinedModel.for_model(
            model, mesh, num_microbatches=args.microbatches)
    except TypeError as e:
        raise SystemExit(f"--mesh pipe axis: {e}") from e
    print(f"[pipeline] {model.num_stages} stages over pipe="
          f"{mesh.shape['pipe']}, {model.num_microbatches} microbatches")
    return model


def _main_detection(args, cfg, mesh):
    from deep_vision_tpu.core.trainer import Trainer
    from deep_vision_tpu.data.detection import synthetic_detection_dataset
    if cfg.task == "centernet":
        from deep_vision_tpu.data.detection import CenterNetLoader as LoaderCls
        from deep_vision_tpu.tasks.centernet import CenterNetTask

        task = CenterNetTask(cfg.num_classes)
    else:
        import jax

        from deep_vision_tpu.data.detection import DetectionLoader as LoaderCls
        from deep_vision_tpu.tasks.detection import YoloTask

        # pallas ignore-mask kernel: TPU only; sharded meshes route it
        # through a data-axis shard_map (best_iou_max_sharded), so
        # multi-chip keeps the fused path
        use_pallas = jax.default_backend() == "tpu"
        if use_pallas:
            from deep_vision_tpu.ops.pallas_ops import best_iou_parity
            from deep_vision_tpu.tasks.detection import MAX_BOXES

            # run it once against the XLA path at the REAL compiled shapes
            # — Mosaic tiling/VMEM limits are shape-dependent, so toy
            # shapes prove nothing; the loss calls the kernel once PER
            # SCALE with that scale's n_pred, and under shard_map the
            # kernel sees the PER-SHARD batch.  A refusal or a mismatch
            # stops the run here with the reason.
            # cfg.batch_size is per-HOST, the data axis spans all hosts —
            # the global batch is per-host × process_count; grad accum then
            # splits each shard into microbatches INSIDE the step, so the
            # kernel's real compiled batch divides by that too
            global_batch = cfg.batch_size * jax.process_count()
            accum = max(1, getattr(cfg, "grad_accum_steps", 1))
            per_shard = max(
                global_batch // mesh.shape.get("data", 1) // accum, 1)
            for s in (8, 16, 32):
                best_iou_parity(batch=per_shard,
                                n_pred=3 * (cfg.image_size // s) ** 2,
                                n_gt=MAX_BOXES)
        print(f"[loss] ignore mask: {'pallas' if use_pallas else 'xla'}")
        task = YoloTask(cfg.num_classes, use_pallas=use_pallas,
                        mesh=mesh if mesh.devices.size > 1 else None)
    if args.synthetic:
        train_samples = synthetic_detection_dataset(
            args.synthetic_size, cfg.image_size,
            min(cfg.num_classes, 3), seed=1)
        val_samples = synthetic_detection_dataset(
            max(args.synthetic_size // 4, cfg.batch_size), cfg.image_size,
            min(cfg.num_classes, 3), seed=2)
    else:
        from deep_vision_tpu.data.records import load_detection_records

        assert args.data_root, "--data-root required without --synthetic"
        # train split decodes in the worker pool (bounded memory); the val
        # split is revisited every epoch with no pool, so cache decodes
        train_samples = load_detection_records(
            args.data_root, "train", cache_decoded=args.num_workers == 0)
        val_samples = load_detection_records(args.data_root, "val",
                                             cache_decoded=True)
    # uint8 host batches + on-device /255 by default (4× smaller H2D,
    # no host f32 convert); --host-normalize restores the all-host path
    dev_norm = not args.host_normalize
    preprocess_fn = None
    if dev_norm:
        from deep_vision_tpu.ops.preprocess import make_scale_preprocess

        preprocess_fn = make_scale_preprocess()
    train_loader = LoaderCls(train_samples, cfg.batch_size,
                             cfg.num_classes, cfg.image_size,
                             train=True, seed=cfg.seed,
                             device_normalize=dev_norm,
                             # synthetic samples are in-memory (no decode)
                             # — a pool only adds pickle traffic
                             num_workers=0 if args.synthetic
                             else args.num_workers)
    val_loader = LoaderCls(val_samples, cfg.batch_size,
                           cfg.num_classes, cfg.image_size, train=False,
                           device_normalize=dev_norm)
    # pipeline-parallel training mode (stacked families only — CenterNet
    # here; YOLO has no same-shape stage sequence and exits cleanly)
    model = _maybe_pipelined(cfg.model(), mesh, args)
    trainer = Trainer(cfg, model, task, mesh=mesh, workdir=args.workdir,
                      preprocess_fn=preprocess_fn, upload=args.upload)
    try:
        state = trainer.fit(train_loader, val_loader, resume=args.resume)
        final = trainer.evaluate(state, val_loader)
    finally:
        train_loader.close()
    print("final:", " ".join(f"{k}={v:.4f}" for k, v in final.items()))
    return 0


def _main_pose(args, cfg, mesh):
    from deep_vision_tpu.core.trainer import Trainer
    from deep_vision_tpu.data.pose import PoseLoader, synthetic_pose_dataset
    from deep_vision_tpu.tasks.pose import PoseTask

    task = PoseTask()
    heatmap_size = cfg.image_size // 4
    if args.synthetic:
        train_samples = synthetic_pose_dataset(
            args.synthetic_size, cfg.image_size, cfg.num_classes, seed=1)
        val_samples = synthetic_pose_dataset(
            max(args.synthetic_size // 4, cfg.batch_size), cfg.image_size,
            cfg.num_classes, seed=2)
    else:
        from deep_vision_tpu.data.records import load_pose_records

        assert args.data_root, "--data-root required without --synthetic"
        # train split decodes in the worker pool (bounded memory); the val
        # split is revisited every epoch with no pool, so cache decodes
        train_samples = load_pose_records(
            args.data_root, "train", cache_decoded=args.num_workers == 0)
        val_samples = load_pose_records(args.data_root, "val",
                                        cache_decoded=True)
    dev_norm = not args.host_normalize
    preprocess_fn = None
    if dev_norm:
        from deep_vision_tpu.ops.preprocess import make_scale_preprocess

        preprocess_fn = make_scale_preprocess()
    train_loader = PoseLoader(train_samples, cfg.batch_size, cfg.image_size,
                              heatmap_size, cfg.num_classes, train=True,
                              seed=cfg.seed, device_normalize=dev_norm,
                              num_workers=0 if args.synthetic
                              else args.num_workers)
    val_loader = PoseLoader(val_samples, cfg.batch_size, cfg.image_size,
                            heatmap_size, cfg.num_classes, train=False,
                            device_normalize=dev_norm)
    # pipeline-parallel training mode: a pipe mesh axis shards the
    # hourglass stacks over devices (GPipe microbatch pipeline) — the
    # monolithic config's num_stack/filters/order carry over unchanged
    model = _maybe_pipelined(cfg.model(), mesh, args)
    trainer = Trainer(cfg, model, task, mesh=mesh, workdir=args.workdir,
                      preprocess_fn=preprocess_fn, upload=args.upload)
    try:
        state = trainer.fit(train_loader, val_loader, resume=args.resume)
        final = trainer.evaluate(state, val_loader)
    finally:
        train_loader.close()
    print("final:", " ".join(f"{k}={v:.4f}" for k, v in final.items()))
    return 0


def _main_language(args, cfg, mesh):
    from deep_vision_tpu.core.trainer import Trainer
    from deep_vision_tpu.data.loader import ArrayLoader
    from deep_vision_tpu.data.text import pack_documents, synthetic_corpus
    from deep_vision_tpu.tasks.language_modeling import LanguageModelingTask

    if not args.synthetic:
        raise SystemExit("language models train on --synthetic documents "
                         "here; no tokenised corpus reader exists yet")
    length = int(cfg.extra["sequence_length"])
    vocab = int(cfg.extra["architecture"]["vocab_size"])

    def rows(n, seed):
        docs = synthetic_corpus((n + 1) * length, vocab, seed=seed,
                                max_length=length)
        return {k: v[:n] for k, v in pack_documents(docs, length).items()}

    train_loader = ArrayLoader(rows(args.synthetic_size, 1), cfg.batch_size,
                               seed=cfg.seed)
    val_loader = ArrayLoader(
        rows(max(args.synthetic_size // 4, cfg.eval_batch_size), 2),
        cfg.eval_batch_size, shuffle=False)
    task = LanguageModelingTask(cfg.extra.get("mtp_loss_weight", 0.0))
    trainer = Trainer(cfg, cfg.model(), task, mesh=mesh,
                      workdir=args.workdir, upload=args.upload)
    if args.profile:
        trainer.profile_steps = PROFILE_STEPS
    state = trainer.fit(train_loader, val_loader, resume=args.resume)
    final = trainer.evaluate(state, val_loader)
    print("final:", " ".join(f"{k}={v:.4f}" for k, v in final.items()))
    return 0


def _main_gan(args, cfg, mesh):
    import jax.numpy as jnp

    from deep_vision_tpu.core.adversarial import AdversarialTrainer
    from deep_vision_tpu.models import gan as gan_models
    from deep_vision_tpu.tasks.gan import CycleGANTask, DCGANTask

    dtype = jnp.bfloat16 if cfg.half_precision else jnp.float32
    # uint8 wire by default: the loaders ship raw 0–255 bytes and the
    # (x-127.5)/127.5 scaling runs as the traced GAN prologue — 4× less
    # H2D per step; --host-normalize restores the all-host f32 wire
    dev_norm = not args.host_normalize
    preprocess_fn = None
    if dev_norm:
        from deep_vision_tpu.ops.preprocess import make_gan_preprocess

        preprocess_fn = make_gan_preprocess()
    if cfg.task == "gan_dcgan":
        from deep_vision_tpu.data.gan import GANLoader, mnist_gan_data

        if not args.synthetic:
            assert args.data_root, "--data-root required without --synthetic"
        images = mnist_gan_data(None if args.synthetic else args.data_root,
                                n_synthetic=args.synthetic_size,
                                device_normalize=dev_norm)
        loader = GANLoader(images, cfg.batch_size, seed=cfg.seed)
        task = DCGANTask(gan_models.DCGANGenerator(dtype=dtype),
                         gan_models.DCGANDiscriminator(dtype=dtype),
                         opt=cfg.optimizer)
    else:
        from deep_vision_tpu.data.gan import UnpairedLoader, synthetic_unpaired

        if args.synthetic:
            a, b = synthetic_unpaired(args.synthetic_size, cfg.image_size,
                                      device_normalize=dev_norm)
        else:
            a, b = _load_unpaired_records(args.data_root, cfg.image_size,
                                          device_normalize=dev_norm)
        loader = UnpairedLoader(a, b, cfg.batch_size, seed=cfg.seed)
        task = CycleGANTask(
            lambda: gan_models.CycleGANGenerator(dtype=dtype),
            lambda: gan_models.PatchGANDiscriminator(dtype=dtype),
            opt=cfg.optimizer)

    trainer = AdversarialTrainer(cfg, task, mesh=mesh, workdir=args.workdir,
                                 preprocess_fn=preprocess_fn,
                                 upload=args.upload)
    states = trainer.fit(loader, epochs=cfg.total_epochs, resume=args.resume)
    print("done: trained", ", ".join(states))
    return 0


def _load_unpaired_records(data_root, image_size,
                           device_normalize: bool = False):
    """train_a/train_b dvrec shards (cli.prepare_data unpaired) →
    two [-1,1] float arrays, or raw uint8 0–255 arrays when
    ``device_normalize`` defers the scaling to the traced prologue."""
    import io

    import numpy as np
    from PIL import Image

    from deep_vision_tpu.data.detection import resize_square
    from deep_vision_tpu.data.records import list_shards, read_records

    assert data_root, "--data-root required without --synthetic"
    out = []
    for tag in ("a", "b"):
        shards = list_shards(data_root, f"train_{tag}")
        if not shards:
            raise FileNotFoundError(
                f"no train_{tag}-*.dvrec under {data_root} "
                "(run cli.prepare_data unpaired)")
        imgs = []
        for sh in shards:
            for _, payload in read_records(sh):
                img = np.asarray(Image.open(io.BytesIO(payload))
                                 .convert("RGB"))
                sq = resize_square(img, image_size)
                imgs.append(sq.astype(np.uint8) if device_normalize
                            else sq.astype(np.float32) / 127.5 - 1.0)
        out.append(np.stack(imgs))
    return out[0], out[1]


if __name__ == "__main__":
    raise SystemExit(main())
