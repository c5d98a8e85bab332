"""Unified experiment configuration.

Replaces the reference's scattered config surfaces — per-file
``training_config`` dicts (ResNet/pytorch/train.py:26-215,
ResNet/tensorflow/train.py:21-62), module constants
(YOLO/tensorflow/train.py:13-17), click CLIs (Hourglass/tensorflow/main.py:21-40)
and ``tf.app.flags`` (build_imagenet_tfrecord.py:104-160) — with one dataclass
registry keyed by experiment name.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

from deep_vision_tpu.core.optim import OptimizerConfig


@dataclasses.dataclass
class SchedulerConfig:
    name: str = "constant"  # see core.optim.SCHEDULERS
    kwargs: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class TrainConfig:
    name: str
    model: Callable[[], Any]  # zero-arg ctor, like the reference's config dicts
    task: str = "classification"
    batch_size: int = 128  # GLOBAL batch (split over the data mesh axis)
    eval_batch_size: int | None = None
    total_epochs: int = 90
    optimizer: OptimizerConfig = dataclasses.field(default_factory=OptimizerConfig)
    scheduler: SchedulerConfig = dataclasses.field(default_factory=SchedulerConfig)
    label_smoothing: float = 0.0
    half_precision: bool = True  # bf16 activations/compute on TPU
    image_size: int = 224
    channels: int = 3
    num_classes: int = 1000
    checkpoint_every_epochs: int = 1
    keep_checkpoints: int = 3
    log_every_steps: int = 10  # reference printed every 10 batches
    # divergence guard: non-finite steps are skipped + counted; the run
    # halts with a clear error once more than this many were skipped
    max_bad_steps: int = 100
    # gradient accumulation: split each global batch into this many
    # sequential microbatches inside the jitted step, averaging grads
    # before the single optimizer update — the full recipe batch on a
    # fraction of the HBM.  (The reference's answer to OOM was shrinking
    # the batch mid-run: ResNet/pytorch/train.py:141-148, VGG README's
    # "batch 128→64".)  1 = off.
    grad_accum_steps: int = 1
    # exponential moving average of params: eval/serving uses the EMA
    # copy (the modern-recipe trick for a ~0.2-0.5 top-1 bump at zero
    # training cost).  0 = off.  PARAMS ONLY: BN running stats are served
    # raw (tf.train.ExponentialMovingAverage semantics; timm's ModelEmaV2
    # averages buffers too — both are defensible, this one keeps the
    # stats a single source of truth).  The effective decay warms up as
    # min(decay, (1+step)/(10+step)) so short/seeded runs aren't
    # dominated by the init point.
    ema_decay: float = 0.0
    seed: int = 42
    extra: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        if self.eval_batch_size is None:
            self.eval_batch_size = self.batch_size


_REGISTRY: dict[str, Callable[[], TrainConfig]] = {}


def register_config(name: str):
    def deco(fn: Callable[[], TrainConfig]):
        _REGISTRY[name] = fn
        return fn
    return deco


def get_config(name: str) -> TrainConfig:
    # Import for side effects: each zoo module registers its configs.
    import deep_vision_tpu.zoo  # noqa: F401

    if name not in _REGISTRY:
        raise KeyError(f"unknown config '{name}'; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]()


def list_configs() -> list[str]:
    import deep_vision_tpu.zoo  # noqa: F401

    return sorted(_REGISTRY)
