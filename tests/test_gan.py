"""GAN stack tests: model shapes, ImagePool semantics, DCGAN/CycleGAN
train steps (loss finite + params change), AdversarialTrainer smoke."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deep_vision_tpu.models.gan import (
    CycleGANGenerator,
    DCGANDiscriminator,
    DCGANGenerator,
    PatchGANDiscriminator,
)
from deep_vision_tpu.tasks.gan import CycleGANTask, DCGANTask, ImagePool


def test_dcgan_generator_shape():
    g = DCGANGenerator()
    z = jnp.zeros((2, 100))
    variables = g.init({"params": jax.random.PRNGKey(0)}, z, train=False)
    out = g.apply(variables, z, train=False)
    assert out.shape == (2, 28, 28, 1)
    assert float(out.min()) >= -1.0 and float(out.max()) <= 1.0


def test_cyclegan_generator_shape_and_discriminator_patch():
    g = CycleGANGenerator(n_blocks=2)
    x = jnp.zeros((1, 64, 64, 3))
    gv = jax.eval_shape(
        lambda a: g.init({"params": jax.random.PRNGKey(0)}, a, train=False), x)
    out = jax.eval_shape(lambda v, a: g.apply(v, a, train=False), gv, x)
    assert out.shape == (1, 64, 64, 3)
    d = PatchGANDiscriminator()
    dv = jax.eval_shape(
        lambda a: d.init({"params": jax.random.PRNGKey(0)}, a, train=False), x)
    patch = jax.eval_shape(lambda v, a: d.apply(v, a, train=False), dv, x)
    assert patch.shape == (1, 8, 8, 1)  # 3 stride-2 halvings of 64


def test_image_pool_replay():
    pool = ImagePool(pool_size=4, seed=0)
    first = np.ones((4, 2, 2, 1), np.float32)
    out1 = pool.query(first)
    np.testing.assert_array_equal(out1, first)  # buffer fills, passthrough
    second = np.full((4, 2, 2, 1), 2.0, np.float32)
    out2 = pool.query(second)
    # some of the second batch should be swapped for stored ones
    assert out2.shape == first.shape
    assert (out2 == 1.0).any() or (out2 == 2.0).all()
    # pool retains exactly pool_size images
    assert len(pool.pool) == 4


def test_dcgan_train_step_updates_both_models():
    task = DCGANTask(DCGANGenerator(), DCGANDiscriminator(), latent_dim=16)
    rng = jax.random.PRNGKey(0)
    batch = {"image": jnp.asarray(
        np.random.default_rng(0).uniform(-1, 1, (4, 28, 28, 1))
        .astype(np.float32))}
    states = task.init_states(rng, batch)
    new_states, outputs, metrics = jax.jit(task.train_step)(
        states, batch, rng)
    assert np.isfinite(float(metrics["g_loss"]))
    assert np.isfinite(float(metrics["d_loss"]))
    g0 = jax.tree_util.tree_leaves(states["generator"].params)[0]
    g1 = jax.tree_util.tree_leaves(new_states["generator"].params)[0]
    assert not np.allclose(g0, g1)
    d0 = jax.tree_util.tree_leaves(states["discriminator"].params)[0]
    d1 = jax.tree_util.tree_leaves(new_states["discriminator"].params)[0]
    assert not np.allclose(d0, d1)


@pytest.mark.slow
def test_cyclegan_train_step_four_networks():
    task = CycleGANTask(lambda: CycleGANGenerator(n_blocks=1),
                        lambda: PatchGANDiscriminator(), pool_size=4)
    rng = jax.random.PRNGKey(0)
    a = np.random.default_rng(0).uniform(-1, 1, (2, 32, 32, 3)).astype(np.float32)
    b = np.random.default_rng(1).uniform(-1, 1, (2, 32, 32, 3)).astype(np.float32)
    batch = {"image_a": jnp.asarray(a), "image_b": jnp.asarray(b)}
    states = task.init_states(rng, batch)
    prepared = task.host_prepare({"image_a": a, "image_b": b})
    prepared = {k: jnp.asarray(v) for k, v in prepared.items()}
    new_states, outputs, metrics = jax.jit(task.train_step)(
        states, prepared, rng)
    for k in ("g_loss", "d_loss", "cycle", "ident"):
        assert np.isfinite(float(metrics[k])), k
    assert outputs["fake_a2b"].shape == (2, 32, 32, 3)
    for name in states:
        p0 = jax.tree_util.tree_leaves(states[name].params)[0]
        p1 = jax.tree_util.tree_leaves(new_states[name].params)[0]
        assert not np.allclose(p0, p1), f"{name} did not update"
    # host pool integration
    task.host_update(outputs)
    prepared2 = task.host_prepare({"image_a": a, "image_b": b})
    assert float(prepared2["pool_valid"]) == 1.0
    assert prepared2["pool_a2b"].shape == (2, 32, 32, 3)


@pytest.mark.slow
def test_adversarial_trainer_smoke(tmp_path):
    from deep_vision_tpu.core.adversarial import AdversarialTrainer
    from deep_vision_tpu.core.config import get_config
    from deep_vision_tpu.data.gan import GANLoader, mnist_gan_data

    cfg = get_config("dcgan")
    cfg.batch_size = 8
    cfg.total_epochs = 1
    cfg.checkpoint_every_epochs = 1
    cfg.log_every_steps = 2
    images = mnist_gan_data(None, n_synthetic=24)
    loader = GANLoader(images, cfg.batch_size)
    task = DCGANTask(DCGANGenerator(), DCGANDiscriminator(), latent_dim=8)
    trainer = AdversarialTrainer(cfg, task, workdir=str(tmp_path))
    states = trainer.fit(loader, epochs=1)
    assert set(states) == {"generator", "discriminator"}
    # checkpoint written and resumable
    assert trainer.checkpointer.latest_step() is not None
    trainer2 = AdversarialTrainer(cfg, task, workdir=str(tmp_path))
    states2 = trainer2.init_states(next(iter(loader)))
    restored, extras = trainer2.checkpointer.restore_tree(states2)
    assert extras["epoch"] == 1
    # samples come out image-shaped
    img = task.sample(states, 2, jax.random.PRNGKey(1))
    assert img.shape == (2, 28, 28, 1)
