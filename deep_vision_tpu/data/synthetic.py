"""Synthetic datasets for smoke runs and tests.

The reference's equivalent is CycleGAN's commented-out random-tensor dry-run
path (CycleGAN/tensorflow/train.py:338-342); here it is a first-class surface
(`--synthetic`) that works for every registered config: class-conditional
Gaussian blobs that a real network can overfit, so smoke runs exercise the
full train/eval/checkpoint path AND show a falling loss.
"""

from __future__ import annotations

import numpy as np


def synthetic_classification(n: int, image_size: int = 32, channels: int = 1,
                             num_classes: int = 10, seed: int = 0
                             ) -> dict[str, np.ndarray]:
    """Learnable synthetic images: one blob location per class + noise."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, num_classes, size=n).astype(np.int32)
    images = rng.normal(0, 0.3, size=(n, image_size, image_size, channels))
    images = images.astype(np.float32)
    ys, xs = np.mgrid[0:image_size, 0:image_size]
    grid = max(2, int(np.ceil(np.sqrt(num_classes))))
    step = image_size / (grid + 1)
    sigma = max(image_size / 10.0, 1.5)
    for c in range(np.minimum(num_classes, grid * grid)):
        cy = step * (1 + c // grid)
        cx = step * (1 + c % grid)
        blob = np.exp(-(((ys - cy) ** 2 + (xs - cx) ** 2) / (2 * sigma**2)))
        images[labels == c] += 2.0 * blob[..., None].astype(np.float32)
    return {"image": images, "label": labels}


def synthetic_images(n: int, image_size: int, channels: int = 3, seed: int = 0
                     ) -> np.ndarray:
    """Plain random images in [-1, 1] (GAN smoke data)."""
    rng = np.random.default_rng(seed)
    return rng.uniform(-1, 1, size=(n, image_size, image_size, channels)
                       ).astype(np.float32)


def make_synthetic_imagenet(tmp: str, n_images: int, jpeg_size: int,
                            val_images: int = 0) -> tuple[str, str, str]:
    """Synthetic flat-ImageNet tree: 8 synsets, 8 distinct base images
    saved as JPEGs, labels.txt — what ``ImageNetLoader`` and
    ``prepare_data imagenet`` read.
    Returns (train_dir, labels_path, val_dir_or_empty)."""
    import os

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
