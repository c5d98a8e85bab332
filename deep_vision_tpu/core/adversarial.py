"""AdversarialTrainer: multi-model / multi-optimizer training.

Generalizes the Trainer to the reference's GAN loops — DCGAN's twin-tape
simultaneous G/D step (DCGAN/tensorflow/main.py:55-71) and CycleGAN's
generator-step → ImagePool → discriminator-step sequence
(CycleGAN/tensorflow/train.py:150-265).

Design: the GAN *task* owns the math as a pure function
``task.train_step(states: dict[str, TrainState], batch, rng) ->
(new_states, host_outputs, metrics)`` which is jitted whole (donated states).
Host-side state between steps (the ImagePool, kept outside ``@tf.function``
in the reference, utils.py:31) lives in ``task.host_update(outputs)`` which
runs between jitted steps and can rewrite the next batch.
"""

from __future__ import annotations

import os
import time
from typing import Iterable

import jax

from deep_vision_tpu.core import checkpoint as ckpt_lib
from deep_vision_tpu.core.state import DivergenceGuard, all_finite
from deep_vision_tpu.core.config import TrainConfig
from deep_vision_tpu.core.metrics import MetricLogger, ThroughputMeter
from deep_vision_tpu.core.optim import build_scheduler, set_learning_rate
from deep_vision_tpu.parallel import (
    make_mesh,
    replicate,
    shard_batch,
)


class AdversarialTrainer:
    def __init__(self, config: TrainConfig, task, mesh=None,
                 workdir: str | None = None, upload: str | None = None,
                 preprocess_fn=None):
        self.config = config
        # optional device-side input preprocessing run INSIDE the jitted
        # step (the GAN uint8 wire: ops/preprocess.make_gan_preprocess
        # reverses the (x-127.5)/127.5 scaling as a traced prologue);
        # signature (batch, rng, train) — same contract as Trainer
        self.preprocess_fn = preprocess_fn
        if getattr(config, "grad_accum_steps", 1) > 1:
            raise NotImplementedError(
                "grad_accum_steps applies to the single-optimizer Trainer "
                "only; adversarial steps update G and D from the same "
                "forward, so accumulate by lowering batch_size instead")
        self.task = task  # owns models, optimizers, and the step math
        self.mesh = mesh if mesh is not None else make_mesh()
        self.workdir = workdir or os.path.join("runs", config.name)
        self.logger = MetricLogger(self.workdir)
        self.scheduler = build_scheduler(
            config.scheduler.name, config.optimizer.learning_rate,
            **config.scheduler.kwargs)
        self.checkpointer = ckpt_lib.Checkpointer(
            os.path.join(self.workdir, "checkpoints"),
            max_to_keep=config.keep_checkpoints)
        self.uploader = None
        if upload:
            from deep_vision_tpu.core.upload import ArtifactUploader

            self.uploader = ArtifactUploader(upload)
        self._jit_step = None
        self.start_epoch = 1
        self.start_step = 0
        self.guard = DivergenceGuard(config.max_bad_steps)
        self._preempted = False  # SIGTERM → step-boundary save + return
        # staged input pipeline — same DevicePrefetcher as the Trainer,
        # used by _epoch_steps for tasks that declare ``prefetch_safe``
        # (DCGAN: no host exchange between steps; CycleGAN's per-step
        # ImagePool injection must see the PREVIOUS step's fakes, so
        # staging its batches ahead would replay stale pools)
        self.prefetch_depth = max(1, int(getattr(config,
                                                 "prefetch_depth", 2)))
        self._prefetcher = None

    def init_states(self, sample_batch: dict) -> dict:
        if self.preprocess_fn is not None:
            # models must init on what the step actually feeds them
            # (uint8 wire batches decode inside the jitted step)
            sample_batch = self.preprocess_fn(
                sample_batch, jax.random.PRNGKey(0), train=False)
        states = self.task.init_states(
            jax.random.PRNGKey(self.config.seed), sample_batch)
        return {k: replicate(v, self.mesh) for k, v in states.items()}

    def _get_prefetcher(self):
        if self._prefetcher is None:
            from deep_vision_tpu.data.pipeline import DevicePrefetcher

            self._prefetcher = DevicePrefetcher(self.mesh,
                                                depth=self.prefetch_depth)
        return self._prefetcher

    def _log_input_stats(self, step: int, stats: dict, epoch: int):
        """Same input-goodput block as Trainer._log_input_stats — both
        trainers report identical series (docs/OBSERVABILITY.md)."""
        if not stats or not stats.get("batches"):
            return
        self.logger.log_input_block(step, stats)
        prod = stats.get("producer_ms", {})
        n = max(1, stats["batches"])
        print(f"[input] epoch {epoch} stall {stats['input_stall_frac']:.1%} "
              f"h2d {stats['h2d_bytes_per_step'] / 1e6:.2f} MB/step "
              f"prep {prod.get('prep_wait', 0.0) / n:.1f} "
              f"assemble {prod.get('assemble', 0.0) / n:.1f} "
              f"h2d {prod.get('h2d', 0.0) / n:.1f} ms/batch "
              f"(pool alloc {stats['pool']['allocated']} "
              f"reuse {stats['pool']['reused']})", flush=True)

    def maybe_resume(self, states: dict) -> dict:
        if self.checkpointer.latest_step() is None:
            return states
        states, extras = self.checkpointer.restore_tree(states)
        self.start_epoch = int(extras.get("epoch", 0)) + 1
        self.start_step = int(self.checkpointer.latest_step() or 0)
        if "scheduler" in extras:
            self.scheduler.load_state_dict(extras["scheduler"])
        first = next(iter(states.values()))
        self.guard.set_baseline(int(jax.device_get(first.bad_steps)))
        print(f"[resume] adversarial start_epoch={self.start_epoch} "
              f"step={self.start_step}")
        return {k: replicate(v, self.mesh) for k, v in states.items()}

    def _guarded_step(self, task_step):
        preprocess_fn = self.preprocess_fn

        def guarded(states, batch, rng):
            """Divergence guard around the task's multi-network step:
            if any loss or any updated network went non-finite, every
            network keeps its previous params/opt_state (GAN updates are
            coupled — applying half a step would unbalance G vs D).
            The optional traced preprocess prologue (uint8 wire decode)
            runs first; it consumes no randomness, so the task sees the
            SAME rng as the float-wire path."""
            if preprocess_fn is not None:
                batch = preprocess_fn(batch, rng, train=True)
            new_states, outputs, metrics = task_step(states, batch, rng)
            ok = all_finite(list(metrics.values())) & all_finite(
                {k: s.params for k, s in new_states.items()})
            merged = {k: new_states[k].keep_if(ok, states[k])
                      for k in new_states}
            first = next(iter(merged))
            metrics = dict(metrics, bad_steps=merged[first].bad_steps)
            return merged, outputs, metrics

        return guarded

    def train_step(self, states, batch, rng):
        if self._jit_step is None:
            # batch donated alongside the states (argnum 1): prefetched
            # device batches are single-use, so XLA may reuse their HBM;
            # host numpy batches (tests, the CycleGAN pool path) are
            # copied on device_put and unaffected
            self._jit_step = jax.jit(
                self._guarded_step(self.task.train_step),
                donate_argnums=(0, 1))
        return self._jit_step(states, shard_batch(batch, self.mesh), rng)

    def fit(self, train_data: Iterable, epochs: int | None = None,
            states: dict | None = None, resume: bool = False,
            sample_hook=None) -> dict:
        cfg = self.config
        epochs = epochs or cfg.total_epochs
        if states is None:
            states = self.init_states(next(iter(train_data)))
        if resume:
            states = self.maybe_resume(states)
        rng = jax.random.PRNGKey(cfg.seed + 17)
        step = self.start_step  # continues past-resume step numbering
        from deep_vision_tpu.core.trainer import install_sigterm_flag

        self._preempted = False  # stale flag must not abort a fresh fit()
        restore = install_sigterm_flag(
            lambda: setattr(self, "_preempted", True))
        try:
            return self._fit_epochs(train_data, epochs, states, rng, step,
                                    sample_hook)
        finally:
            restore()
            # abandoned epochs must not leave a producer thread parked on
            # the queue or device batches pinned in it
            if self._prefetcher is not None:
                self._prefetcher.close()

    def _preempt_save(self, step, states, epoch):
        self.checkpointer.save_tree(
            step, states,
            extras={"epoch": epoch - 1,
                    "scheduler": self.scheduler.state_dict()})
        # block until durable: the preempt grace window is the one
        # place an async save must not still be in flight
        self.checkpointer.wait_until_finished()
        if self.uploader is not None:
            # the VM disappears seconds after SIGTERM — the preempt
            # save is the one that MUST reach off-host
            self.uploader.sync(self.checkpointer.directory, "checkpoints")
        print(f"[preempt] checkpoint saved at step {step}; "
              f"rerun with --resume to continue", flush=True)

    def _fit_epochs(self, train_data, epochs, states, rng, step, sample_hook):
        cfg = self.config
        for epoch in range(self.start_epoch, epochs + 1):
            lr = self.scheduler.epoch_begin(epoch)
            states = {k: v.replace(
                opt_state=set_learning_rate(v.opt_state, lr))
                for k, v in states.items()}
            if hasattr(train_data, "set_epoch"):
                train_data.set_epoch(epoch)
            meter = ThroughputMeter()
            t0 = time.monotonic()
            states, rng, step, aborted = self._epoch_steps(
                train_data, states, rng, step, epoch, meter)
            if aborted:
                return states
            # drain the async dispatch chain (cheap scalar that depends on
            # every update) so the epoch time is wall truth, not queue depth
            int(jax.device_get(next(iter(states.values())).step))
            self.scheduler.step(epoch, None)
            print(f"Epoch {epoch} done in {time.monotonic() - t0:.1f}s", flush=True)
            self.logger.log("images_per_sec", step, meter.images_per_sec)
            if epoch % cfg.checkpoint_every_epochs == 0:
                self.checkpointer.save_tree(
                    step, states,
                    extras={"epoch": epoch,
                            "scheduler": self.scheduler.state_dict()})
                if self.uploader is not None:
                    # async save must land before the mirror copies it
                    self.checkpointer.wait_until_finished()
                    self.uploader.sync(self.checkpointer.directory,
                                       "checkpoints")
            if sample_hook is not None:
                sample_hook(epoch, states)
        return states

    def _log_step(self, epoch, step, metrics, meter):
        """Shared guard/log/print for one step's (host) metric dict."""
        m = {k: float(v) for k, v in jax.device_get(metrics).items()}
        self.guard.check(m)
        self.logger.log_dict(step, m)
        print(f"Epoch {epoch} Step {step} "
              + " ".join(f"{k}={v:.4f}" for k, v in m.items())
              + f" {meter.images_per_sec:.1f} img/s", flush=True)

    def _epoch_steps(self, train_data, states, rng, step, epoch, meter):
        """Per-step dispatch with the host_prepare/host_update exchange
        between steps (the CycleGAN ImagePool contract).

        Tasks that declare ``prefetch_safe`` (host_prepare is stateless —
        DCGAN) ride the staged ``DevicePrefetcher``: host_prepare runs
        producer-side before staging, batches arrive already on device,
        and the epoch reports the same input-goodput block as the
        Trainer.  Pool-coupled tasks (CycleGAN) keep direct per-step
        iteration — their host_prepare must see the fakes ``host_update``
        harvested from the IMMEDIATELY previous step, which depth-k
        staging would replay stale."""
        cfg = self.config
        stream = None
        if getattr(self.task, "prefetch_safe", False):
            stream = self._get_prefetcher().iterate(
                train_data, host_transform=self.task.host_prepare)
        try:
            for batch in (stream if stream is not None else train_data):
                rng, step_rng = jax.random.split(rng)
                if stream is None:
                    batch = self.task.host_prepare(batch)
                bs = len(next(iter(batch.values())))
                states, outputs, metrics = self.train_step(
                    states, batch, step_rng)
                self.task.host_update(outputs)
                meter.update(bs)
                step += 1
                if step % cfg.log_every_steps == 0:
                    self._log_step(epoch, step, metrics, meter)
                if self._preempted:
                    self._preempt_save(step, states, epoch)
                    return states, rng, step, True
            return states, rng, step, False
        finally:
            if stream is not None:
                self._log_input_stats(step, stream.stats(), epoch)
