"""GAN tasks for the AdversarialTrainer.

- DCGANTask — twin simultaneous G/D step with BCE-from-logits
  (DCGAN/tensorflow/main.py:42-71).
- CycleGANTask — 4-network step: one gradient over BOTH generators
  (LSGAN/MSE gan loss + L1 cycle λ=10 + L1 identity λ=5,
  CycleGAN/tensorflow/train.py:150-205), then one gradient over both
  discriminators fed POOLED fakes (:207-255); the 50-image ImagePool replay
  buffer (utils.py:32-61) is host-side state applied between jitted steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deep_vision_tpu.core.optim import OptimizerConfig, build_optimizer
from deep_vision_tpu.core.state import TrainState


def _bce_logits(logits, target_ones: bool):
    t = jnp.ones_like(logits) if target_ones else jnp.zeros_like(logits)
    return optax.sigmoid_binary_cross_entropy(logits, t).mean()


def _mse(pred, target_ones: bool):
    t = jnp.ones_like(pred) if target_ones else jnp.zeros_like(pred)
    return jnp.square(pred - t).mean()


def _apply(state: TrainState, params, x, train, rng=None):
    variables = {"params": params}
    has_bn = bool(state.batch_stats)
    if has_bn:
        variables["batch_stats"] = state.batch_stats
    kwargs = dict(rngs={"dropout": rng}) if rng is not None else {}
    out = state.apply_fn(variables, x, train=train,
                         mutable=["batch_stats"] if (has_bn and train) else False,
                         **kwargs)
    if has_bn and train:
        out, new_vars = out
        return out, new_vars["batch_stats"]
    return out, state.batch_stats


class ImagePool:
    """50-image replay buffer (CycleGAN/tensorflow/utils.py:32-61): each
    fake is stored; with p=0.5 an older stored fake is returned instead.
    Host-side numpy — exactly as the reference keeps it eager-only."""

    def __init__(self, pool_size: int = 50, seed: int = 0):
        self.pool_size = pool_size
        self.pool: list[np.ndarray] = []
        self.rng = np.random.default_rng(seed)

    def query(self, images: np.ndarray) -> np.ndarray:
        if self.pool_size == 0:
            return images
        out = []
        for img in np.asarray(images):
            if len(self.pool) < self.pool_size:
                self.pool.append(img)
                out.append(img)
            elif self.rng.random() > 0.5:
                i = int(self.rng.integers(0, self.pool_size))
                out.append(self.pool[i])
                self.pool[i] = img
            else:
                out.append(img)
        return np.stack(out)


class DCGANTask:
    """models: generator (noise→image), discriminator (image→logit)."""

    # host_prepare is stateless (identity) → batches may be staged ahead
    # by the DevicePrefetcher (core/adversarial.py _epoch_steps)
    prefetch_safe = True

    def __init__(self, generator, discriminator, latent_dim: int = 100,
                 opt: OptimizerConfig | None = None):
        self.generator = generator
        self.discriminator = discriminator
        self.latent_dim = latent_dim
        # reference: Adam(1e-4) for both (DCGAN/tensorflow/main.py:31-32)
        self.opt = opt or OptimizerConfig(name="adam", learning_rate=1e-4)

    def init_states(self, rng, sample_batch) -> dict:
        g_rng, d_rng = jax.random.split(rng)
        z = jnp.zeros((1, self.latent_dim))
        img = jnp.asarray(sample_batch["image"][:1])
        g_vars = self.generator.init({"params": g_rng}, z, train=False)
        d_vars = self.discriminator.init({"params": d_rng}, img, train=False)
        tx_g, tx_d = build_optimizer(self.opt), build_optimizer(self.opt)
        return {
            "generator": TrainState.create(
                apply_fn=self.generator.apply, params=g_vars["params"],
                tx=tx_g, batch_stats=g_vars.get("batch_stats", {}), rng=g_rng),
            "discriminator": TrainState.create(
                apply_fn=self.discriminator.apply, params=d_vars["params"],
                tx=tx_d, batch_stats=d_vars.get("batch_stats", {}), rng=d_rng),
        }

    def host_prepare(self, batch):
        return batch

    def host_update(self, outputs):
        pass

    def train_step(self, states, batch, rng):
        """Twin-tape simultaneous update (main.py:55-71): both grads are
        computed against the CURRENT params, then both applied."""
        g, d = states["generator"], states["discriminator"]
        # independent dropout masks per discriminator application — the
        # reference's eager TF calls each draw fresh masks
        z_rng, drop_g, drop_real, drop_fake = jax.random.split(rng, 4)
        real = batch["image"]
        z = jax.random.normal(z_rng, (real.shape[0], self.latent_dim))

        def g_loss_fn(g_params):
            fake, g_bs = _apply(g, g_params, z, train=True)
            fake_logit, _ = _apply(d, d.params, fake, train=True,
                                   rng=drop_g)
            return _bce_logits(fake_logit, True), (g_bs, fake)

        def d_loss_fn(d_params, fake):
            real_logit, _ = _apply(d, d_params, real, train=True,
                                   rng=drop_real)
            fake_logit, _ = _apply(d, d_params, fake, train=True,
                                   rng=drop_fake)
            return _bce_logits(real_logit, True) + _bce_logits(fake_logit,
                                                               False)

        (g_loss, (g_bs, fake)), g_grads = jax.value_and_grad(
            g_loss_fn, has_aux=True)(g.params)
        d_loss, d_grads = jax.value_and_grad(d_loss_fn)(
            d.params, jax.lax.stop_gradient(fake))
        new_states = {
            "generator": g.apply_gradients(g_grads, batch_stats=g_bs),
            "discriminator": d.apply_gradients(d_grads),
        }
        return new_states, {}, {"g_loss": g_loss, "d_loss": d_loss}

    def sample(self, states, n: int, rng) -> np.ndarray:
        """Inference path (DCGAN/tensorflow/inference.py:7-32)."""
        g = states["generator"]
        z = jax.random.normal(rng, (n, self.latent_dim))
        img, _ = _apply(g, g.params, z, train=False)
        return np.asarray(jax.device_get(img))


class CycleGANTask:
    """models: gen_a2b, gen_b2a, disc_a, disc_b."""

    # the per-step host ImagePool exchange (host_prepare/host_update)
    # is semantic: host_prepare draws from the pool, so batches staged
    # ahead by the DevicePrefetcher would see it stale
    prefetch_safe = False

    LAMBDA_CYCLE = 10.0  # train.py:16
    LAMBDA_ID = 5.0      # train.py:17

    def __init__(self, make_generator, make_discriminator,
                 opt: OptimizerConfig | None = None, pool_size: int = 50):
        self.make_generator = make_generator
        self.make_discriminator = make_discriminator
        # reference: Adam(2e-4, β1=0.5) ×2 (train.py:126-131)
        self.opt = opt or OptimizerConfig(name="adam", learning_rate=2e-4,
                                          b1=0.5)
        self.pool_a2b = ImagePool(pool_size)
        self.pool_b2a = ImagePool(pool_size, seed=1)
        self._pending_fakes = None

    def init_states(self, rng, sample_batch) -> dict:
        img = jnp.asarray(sample_batch["image_a"][:1])
        states = {}
        models = {"gen_a2b": self.make_generator(),
                  "gen_b2a": self.make_generator(),
                  "disc_a": self.make_discriminator(),
                  "disc_b": self.make_discriminator()}
        for i, (name, model) in enumerate(models.items()):
            variables = model.init(
                {"params": jax.random.fold_in(rng, i)}, img, train=False)
            states[name] = TrainState.create(
                apply_fn=model.apply, params=variables["params"],
                tx=build_optimizer(self.opt),
                batch_stats=variables.get("batch_stats", {}),
                rng=jax.random.fold_in(rng, 100 + i))
        return states

    def host_prepare(self, batch):
        """Inject pooled fakes from the PREVIOUS step (host-side replay)."""
        batch = dict(batch)
        if self._pending_fakes is not None:
            fake_a2b, fake_b2a = self._pending_fakes
            batch["pool_a2b"] = self.pool_a2b.query(fake_a2b)
            batch["pool_b2a"] = self.pool_b2a.query(fake_b2a)
            batch["pool_valid"] = np.ones((), np.float32)
        else:
            batch["pool_a2b"] = np.zeros_like(batch["image_b"])
            batch["pool_b2a"] = np.zeros_like(batch["image_a"])
            batch["pool_valid"] = np.zeros((), np.float32)
        return batch

    def host_update(self, outputs):
        self._pending_fakes = (
            np.asarray(jax.device_get(outputs["fake_a2b"])),
            np.asarray(jax.device_get(outputs["fake_b2a"])))

    def train_step(self, states, batch, rng):
        real_a, real_b = batch["image_a"], batch["image_b"]
        g_ab, g_ba = states["gen_a2b"], states["gen_b2a"]
        d_a, d_b = states["disc_a"], states["disc_b"]

        # ---- generator step: ONE grad over both generators (:183-185)
        def gen_loss_fn(gen_params):
            p_ab, p_ba = gen_params
            fake_a2b, bs_ab = _apply(g_ab, p_ab, real_a, train=True)
            recon_a, bs_ba = _apply(g_ba, p_ba, fake_a2b, train=True)
            fake_b2a, bs_ba2 = _apply(g_ba, p_ba, real_b, train=True)
            recon_b, bs_ab2 = _apply(g_ab, p_ab, fake_b2a, train=True)
            ident_b, _ = _apply(g_ab, p_ab, real_b, train=True)
            ident_a, _ = _apply(g_ba, p_ba, real_a, train=True)
            logit_fake_b, _ = _apply(d_b, d_b.params, fake_a2b, train=True)
            logit_fake_a, _ = _apply(d_a, d_a.params, fake_b2a, train=True)
            gan = _mse(logit_fake_b, True) + _mse(logit_fake_a, True)
            cycle = jnp.abs(recon_a - real_a).mean() + \
                jnp.abs(recon_b - real_b).mean()
            ident = jnp.abs(ident_b - real_b).mean() + \
                jnp.abs(ident_a - real_a).mean()
            loss = gan + self.LAMBDA_CYCLE * cycle + self.LAMBDA_ID * ident
            return loss, (bs_ab2, bs_ba2, fake_a2b, fake_b2a,
                          {"gen_gan": gan, "cycle": cycle, "ident": ident})

        (g_loss, (bs_ab, bs_ba, fake_a2b, fake_b2a, g_metrics)), g_grads = \
            jax.value_and_grad(gen_loss_fn, has_aux=True)(
                (g_ab.params, g_ba.params))

        # ---- discriminator step with pooled fakes (:207-246); on the very
        # first step (empty pool) fall back to this step's fakes
        use_pool = batch["pool_valid"] > 0
        pool_a2b = jnp.where(use_pool, batch["pool_a2b"],
                             jax.lax.stop_gradient(fake_a2b))
        pool_b2a = jnp.where(use_pool, batch["pool_b2a"],
                             jax.lax.stop_gradient(fake_b2a))

        def disc_loss_fn(disc_params):
            p_a, p_b = disc_params
            logit_real_a, bs_a = _apply(d_a, p_a, real_a, train=True)
            logit_fake_a, _ = _apply(d_a, p_a, pool_b2a, train=True)
            logit_real_b, bs_b = _apply(d_b, p_b, real_b, train=True)
            logit_fake_b, _ = _apply(d_b, p_b, pool_a2b, train=True)
            loss_a = (_mse(logit_real_a, True) + _mse(logit_fake_a, False)) / 2
            loss_b = (_mse(logit_real_b, True) + _mse(logit_fake_b, False)) / 2
            return loss_a + loss_b, (bs_a, bs_b,
                                     {"disc_a": loss_a, "disc_b": loss_b})

        (d_loss, (bs_a, bs_b, d_metrics)), d_grads = jax.value_and_grad(
            disc_loss_fn, has_aux=True)((d_a.params, d_b.params))

        new_states = {
            "gen_a2b": g_ab.apply_gradients(g_grads[0], batch_stats=bs_ab),
            "gen_b2a": g_ba.apply_gradients(g_grads[1], batch_stats=bs_ba),
            "disc_a": d_a.apply_gradients(d_grads[0], batch_stats=bs_a),
            "disc_b": d_b.apply_gradients(d_grads[1], batch_stats=bs_b),
        }
        outputs = {"fake_a2b": jax.lax.stop_gradient(fake_a2b),
                   "fake_b2a": jax.lax.stop_gradient(fake_b2a)}
        metrics = {"g_loss": g_loss, "d_loss": d_loss,
                   **g_metrics, **d_metrics}
        return new_states, outputs, metrics

    def translate(self, states, images, direction: str = "a2b") -> np.ndarray:
        """Inference path (CycleGAN/tensorflow/inference.py:11-77)."""
        g = states["gen_a2b" if direction == "a2b" else "gen_b2a"]
        out, _ = _apply(g, g.params, jnp.asarray(images), train=False)
        return np.asarray(jax.device_get(out))
