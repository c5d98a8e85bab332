"""End-to-end slice: train/eval/checkpoint/resume on synthetic MNIST over an
8-device data-parallel mesh (SURVEY §7 step 1 accept test, scaled to CI)."""

import numpy as np
import pytest

from deep_vision_tpu.core.config import get_config
from deep_vision_tpu.core.trainer import Trainer
from deep_vision_tpu.data.loader import ArrayLoader
from deep_vision_tpu.data.mnist import synthetic_mnist
from deep_vision_tpu.tasks.classification import ClassificationTask


def make_trainer(tmp_path, mesh, epochs=2):
    cfg = get_config("lenet5")
    cfg.total_epochs = epochs
    cfg.batch_size = 64
    model = cfg.model()
    task = ClassificationTask(num_classes=10)
    return cfg, Trainer(cfg, model, task, mesh=mesh, workdir=str(tmp_path))


@pytest.mark.slow
def test_overfits_synthetic(tmp_path, mesh8):
    cfg, trainer = make_trainer(tmp_path, mesh8, epochs=3)
    data = synthetic_mnist(512)
    train = ArrayLoader(data, cfg.batch_size, seed=1)
    val = ArrayLoader(data, cfg.batch_size, shuffle=False)
    state = trainer.fit(train, val)
    metrics = trainer.evaluate(state, val)
    assert metrics["top1"] > 0.9, metrics  # learnable blobs → near-perfect
    assert trainer.logger.latest("val_top1") is not None


@pytest.mark.slow
def test_checkpoint_resume(tmp_path, mesh8):
    cfg, trainer = make_trainer(tmp_path, mesh8, epochs=2)
    data = synthetic_mnist(256)
    train = ArrayLoader(data, 64, seed=1)
    state = trainer.fit(train, None)
    step_after = int(np.asarray(state.step))

    # new trainer on same workdir resumes at epoch 3
    cfg2, trainer2 = make_trainer(tmp_path, mesh8, epochs=2)
    sample = next(iter(train))
    state2 = trainer2.init_state(sample)
    state2 = trainer2.maybe_resume(state2)
    assert int(np.asarray(state2.step)) == step_after
    assert trainer2.start_epoch == 3
    # params actually restored (not re-initialized)
    import jax

    p_trained = jax.device_get(state.params)
    p_restored = jax.device_get(state2.params)
    for a, b in zip(jax.tree_util.tree_leaves(p_trained),
                    jax.tree_util.tree_leaves(p_restored)):
        np.testing.assert_allclose(a, b)


def test_single_device_mesh(tmp_path, mesh1):
    """Everything must run unchanged on one device (the reference's CPU
    fallback `torch.device('cuda' if ... else 'cpu')`)."""
    cfg, trainer = make_trainer(tmp_path, mesh1, epochs=1)
    data = synthetic_mnist(128)
    train = ArrayLoader(data, 32, seed=1)
    state = trainer.fit(train, None)
    assert int(np.asarray(state.step)) == len(train)
