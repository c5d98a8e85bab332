"""Device-side input preprocessing (jitter + normalize inside the jit step).

TPU-first split of the reference's cv2/torch host pipeline
(ResNet/pytorch/data_load.py:72-296): the host keeps only what must be
dynamic-shaped (JPEG decode, aspect-preserving rescale, crop — all uint8),
and the float work (ColorJitter :213-296, Normalize :197-210) moves into
the jitted train step where XLA fuses it into the first conv's HBM read.
Shipping uint8 instead of float32 also cuts host→device transfer 4×.

Semantics vs the host path: identical factor ranges; the three jitter ops
apply in a fixed order (brightness→contrast→saturation) instead of the
reference's shuffled order — a no-op in expectation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from deep_vision_tpu.data.mnist import MEAN as MNIST_MEAN
from deep_vision_tpu.data.mnist import STD as MNIST_STD
from deep_vision_tpu.data.transforms import IMAGENET_MEAN, IMAGENET_STD

# NumPy, not jnp: a device array here would initialize the JAX backend —
# and claim the chip — in every process that merely imports this module
_GRAY = np.asarray([0.299, 0.587, 0.114], np.float32)

#: normalization families the serving wire supports (docs/SERVING.md
#: "Wire format & inference dtype"); "unit" is plain [0,1] scaling,
#: "gan" the reference GAN pipelines' [-1,1] scaling
SERVE_KINDS = ("imagenet", "mnist", "unit", "gan")


def serve_preprocess_kind(task: str, channels: int) -> str:
    """Which normalization family a model's uint8 serving wire needs —
    derived from config metadata so the device prologue matches the
    host path that trained the model: classification RGB models were
    trained on ImageNet-standardized inputs (data/transforms.py),
    grayscale classification on MNIST stats (data/mnist.py), the
    detection/pose tasks on plain [0,1] images, and the GAN tasks on
    [-1,1] images (``make_gan_preprocess`` — the image-in CycleGAN
    serving wire reuses exactly that scaling)."""
    if task == "classification":
        return "mnist" if channels == 1 else "imagenet"
    if str(task).startswith("gan_"):
        return "gan"
    return "unit"


def serve_normalize(x, kind: str):  # dvtlint: traced
    """uint8 wire batch → normalized float32, IDENTICAL math to the host
    preprocess for ``kind`` (scale first, then standardize — same op
    order as data/transforms.normalize and data/mnist.preprocess, so
    uint8-wire outputs stay allclose to the float32 wire)."""
    if kind not in SERVE_KINDS:
        raise ValueError(f"unknown serve preprocess kind '{kind}' "
                         f"(have {SERVE_KINDS})")
    if kind == "gan":
        # GAN convention: (x - 127.5)/127.5, same op as the trainer's
        # make_gan_preprocess — NOT the /255-then-standardize chain
        return x.astype(jnp.float32) / 127.5 - 1.0
    x = x.astype(jnp.float32) / 255.0
    if kind == "imagenet":
        return (x - jnp.asarray(IMAGENET_MEAN)) / jnp.asarray(IMAGENET_STD)
    if kind == "mnist":
        return (x - MNIST_MEAN) / MNIST_STD
    return x  # "unit": [0,1] inputs (YOLO/CenterNet/hourglass)


def make_serve_preprocess(kind: str, wire_dtype, compute_dtype=jnp.float32):
    """Traced prologue for serving bucket programs (serve/registry.py).

    An integer ``wire_dtype`` means the client shipped raw 0–255 pixels
    and the server owns normalization: cast + scale + standardize run on
    device, fused by XLA into the first conv's HBM read (the H2D carried
    4× fewer bytes).  A float wire passes through untouched — those
    clients already normalized on the host (the pre-uint8 contract).
    Either way the batch lands in ``compute_dtype`` (bfloat16 for
    ``--infer-dtype bfloat16``, else float32)."""
    wire_is_int = jnp.issubdtype(jnp.dtype(wire_dtype), jnp.integer)

    def fn(x):  # dvtlint: traced
        if wire_is_int:
            x = serve_normalize(x, kind)
        return x.astype(compute_dtype)

    return fn


def quantize_activations(x, act_scale: float):  # dvtlint: traced
    """Normalized float activations → symmetric int8 with the per-tensor
    calibration scale (serve/quant.py): ``round(x/act_scale)`` clipped
    to ±127.  The XLA half of the int8 ingest — same math as the fused
    Pallas kernel, kept for parity testing and the float32 wire."""
    q = jnp.clip(jnp.round(x / act_scale), -127.0, 127.0)
    return q.astype(jnp.int8)


def make_int8_ingest(kind: str, wire_dtype, act_scale: float,
                     use_pallas: bool = True):
    """Traced int8 serve-prologue (``--infer-dtype int8`` bucket
    programs, serve/registry.py): the batch leaves as int8 activations
    the program dequantizes into its first conv.

    A uint8 wire takes the FUSED path — decode + normalize + quantize in
    one VMEM pass (ops/pallas_ops.serve_ingest; interpret-mode off-TPU)
    so the wire bytes never materialize as an f32 HWC tensor in HBM —
    unless ``use_pallas`` is False (the XLA prologue, kept as the
    reference the kernel is checked against).  A float wire was
    normalized by the client, so only the quantize runs.  The "gan"
    kind always takes the XLA path — the fused kernel's constant table
    (ops/pallas_ops._ingest_norm_constants) only bakes the mean/std
    families, and int8 generative serving is untested territory."""
    wire_is_int = jnp.issubdtype(jnp.dtype(wire_dtype), jnp.integer)
    if wire_is_int and use_pallas and kind != "gan":
        from deep_vision_tpu.ops.pallas_ops import serve_ingest_auto

        def fn(x):  # dvtlint: traced
            return serve_ingest_auto(x, kind, act_scale=act_scale)

        return fn

    def fn(x):  # dvtlint: traced
        if wire_is_int:
            x = serve_normalize(x, kind)
        return quantize_activations(x, act_scale)

    return fn


def jitter_normalize(images, rng, train: bool,
                     mean=IMAGENET_MEAN, std=IMAGENET_STD,
                     brightness: float = 0.2, contrast: float = 0.2,
                     saturation: float = 0.2):
    """uint8 (B,H,W,3) → normalized float32, with train-time color jitter.

    Already-float inputs pass through normalization only (so the same step
    works with host-normalized loaders — their floats are already
    standardized and this fn must NOT run; callers gate on dtype).
    """
    x = images.astype(jnp.float32) / 255.0
    if train:
        b = images.shape[0]
        kb, kc, ks = jax.random.split(rng, 3)
        fb = jax.random.uniform(kb, (b, 1, 1, 1),
                                minval=max(0.0, 1 - brightness),
                                maxval=1 + brightness)
        x = x * fb
        m = x.mean(axis=(1, 2, 3), keepdims=True)
        fc = jax.random.uniform(kc, (b, 1, 1, 1),
                                minval=max(0.0, 1 - contrast),
                                maxval=1 + contrast)
        x = (x - m) * fc + m
        gray = (x * _GRAY).sum(-1, keepdims=True)
        fs = jax.random.uniform(ks, (b, 1, 1, 1),
                                minval=max(0.0, 1 - saturation),
                                maxval=1 + saturation)
        x = gray + (x - gray) * fs
        x = jnp.clip(x, 0.0, 1.0)
    return (x - jnp.asarray(mean)) / jnp.asarray(std)


def make_scale_preprocess():
    """Trainer ``preprocess_fn`` for [0,1]-input tasks (YOLO, CenterNet):
    uint8 image batches scale to float32/255 inside the jitted step (4×
    smaller H2D payload — the loaders' ``device_normalize`` path); float
    batches (host-normalized) pass through untouched."""

    def fn(batch: dict, rng, train: bool) -> dict:
        img = batch["image"]
        if img.dtype != jnp.uint8:
            return batch
        out = dict(batch)
        out["image"] = img.astype(jnp.float32) / 255.0
        return out

    return fn


def make_imagenet_preprocess(brightness: float = 0.2, contrast: float = 0.2,
                             saturation: float = 0.2,
                             use_fused: bool = False,
                             fused_shape: tuple | None = None,
                             mesh=None):
    """Trainer ``preprocess_fn``: applied to uint8 image batches inside the
    jitted step; float batches (host-normalized path) pass through.

    With ``use_fused`` and a concrete ``fused_shape`` (the per-shard
    (B, H, W, C) train batch the step compiles), the train-time jitter
    chain goes through the fused Pallas ``train_ingest`` kernel instead
    of the multi-op XLA ``jitter_normalize``.  The kernel is first run
    once at that exact shape against the XLA path
    (ops/pallas_ops.train_ingest_parity): a kernel Mosaic refuses, or one
    that diverges, stops the run with the reason — it never quietly
    trains through the other path.  On a multi-device ``mesh`` the kernel
    runs under shard_map per batch shard with globally-drawn factors.
    The eval path is always the plain normalize (no jitter — nothing to
    fuse).
    """
    fused = bool(use_fused and fused_shape is not None)
    if fused:
        from deep_vision_tpu.ops.pallas_ops import train_ingest_parity

        train_ingest_parity(
            tuple(fused_shape), "imagenet", brightness, contrast,
            saturation, interpret=jax.default_backend() != "tpu")
    multi = mesh is not None and mesh.devices.size > 1

    # dvtlint: hot
    def fn(batch: dict, rng, train: bool) -> dict:  # dvtlint: traced
        img = batch["image"]
        if img.dtype != jnp.uint8:
            return batch
        out = dict(batch)
        if fused and train:
            from deep_vision_tpu.ops.pallas_ops import (
                train_ingest_auto, train_ingest_factors,
                train_ingest_sharded)

            factors = train_ingest_factors(img, rng, brightness, contrast,
                                           saturation)
            if multi:
                out["image"] = train_ingest_sharded(img, factors, mesh)
            else:
                out["image"] = train_ingest_auto(img, factors)
        else:
            out["image"] = jitter_normalize(
                img, rng, train, brightness=brightness, contrast=contrast,
                saturation=saturation)
        return out

    fn.fused = fused  # introspectable: tests + CLI log which path runs
    return fn


def make_mnist_preprocess():
    """Trainer ``preprocess_fn`` for the grayscale classification path:
    uint8 wire batches (data/mnist.load_mnist ``device_normalize=True``)
    standardize with the MNIST stats inside the jitted step — the H2D
    carried 1 byte/pixel and XLA fuses the normalize into the first
    conv's read; float batches (host-normalized) pass through."""

    def fn(batch: dict, rng, train: bool) -> dict:  # dvtlint: traced
        img = batch["image"]
        if img.dtype != jnp.uint8:
            return batch
        out = dict(batch)
        out["image"] = serve_normalize(img, "mnist")
        return out

    return fn


def make_gan_preprocess():
    """Trainer ``preprocess_fn`` for the GAN tasks (DCGAN/CycleGAN): the
    reference pipelines ship float32 in [-1, 1] (``(x - 127.5)/127.5``);
    the uint8 wire defers exactly that scaling to a traced prologue, so
    the host batches, prefetch queue, and H2D DMA carry 1 byte/pixel.
    Applies to every ``image*`` key (``image``, ``image_a``, ``image_b``
    — the unpaired loader carries two domains); float keys and non-image
    keys (pooled fakes, masks) pass through untouched."""

    def fn(batch: dict, rng, train: bool) -> dict:  # dvtlint: traced
        out = dict(batch)
        for key, val in batch.items():
            if key.startswith("image") and val.dtype == jnp.uint8:
                out[key] = val.astype(jnp.float32) / 127.5 - 1.0
        return out

    return fn
