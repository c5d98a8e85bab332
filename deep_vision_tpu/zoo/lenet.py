"""LeNet-5/MNIST experiment — config parity with
LeNet/pytorch/train.py:15-32 (Adam lr=1e-3, batch 64, 50 epochs,
ReduceLROnPlateau factor=0.1 mode='max')."""

from deep_vision_tpu.core.config import (
    OptimizerConfig,
    SchedulerConfig,
    TrainConfig,
    register_config,
)
from deep_vision_tpu.models.lenet import LeNet5, LeNet5Big, LeNet5Nano


@register_config("lenet5_nano")
def lenet5_nano() -> TrainConfig:
    """The N-tier cascade's tier 0 below lenet5: identical wire
    contract (32×32×1, 10 classes) at ~12× less compute than LeNet-5 —
    the front of the lenet5_nano:lenet5:lenet5_big chain the cascade
    tests run (serve/cascade.py)."""
    return TrainConfig(
        name="lenet5_nano",
        model=lambda: LeNet5Nano(),
        task="classification",
        batch_size=64,
        total_epochs=50,
        optimizer=OptimizerConfig(name="adam", learning_rate=1e-3),
        scheduler=SchedulerConfig(
            name="plateau", kwargs=dict(mode="max", factor=0.1, patience=10)),
        half_precision=False,
        image_size=32,
        channels=1,
        num_classes=10,
    )


@register_config("lenet5")
def lenet5() -> TrainConfig:
    return TrainConfig(
        name="lenet5",
        model=lambda: LeNet5(),
        task="classification",
        batch_size=64,
        total_epochs=50,
        optimizer=OptimizerConfig(name="adam", learning_rate=1e-3),
        scheduler=SchedulerConfig(
            name="plateau", kwargs=dict(mode="max", factor=0.1, patience=10)),
        half_precision=False,  # MNIST-scale; f32 is fine
        image_size=32,
        channels=1,
        num_classes=10,
    )


@register_config("lenet5_big")
def lenet5_big() -> TrainConfig:
    """The cascade's BIG tier opposite lenet5: identical wire contract
    (32×32×1, 10 classes) at ~50× the compute — the cheap-front /
    heavy-big pair the cascade smoke serves behind one plane
    (serve/cascade.py)."""
    return TrainConfig(
        name="lenet5_big",
        model=lambda: LeNet5Big(),
        task="classification",
        batch_size=64,
        total_epochs=50,
        optimizer=OptimizerConfig(name="adam", learning_rate=1e-3),
        scheduler=SchedulerConfig(
            name="plateau", kwargs=dict(mode="max", factor=0.1, patience=10)),
        half_precision=False,
        image_size=32,
        channels=1,
        num_classes=10,
    )
