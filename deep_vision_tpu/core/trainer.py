"""The unified Trainer.

One trainer replacing the reference's three generations (SURVEY §1): the
PyTorch imperative loop (``run_epochs``/``train``/``validate``,
ResNet/pytorch/train.py:310-520), TF1-Keras ``model.fit``
(ResNet/tensorflow/train.py:221-297), and TF2 MirroredStrategy custom loops
(YOLO/tensorflow/train.py:122-250).

TPU mapping:
- the whole train step (forward, loss, backward, optimizer) is ONE jitted
  function with donated state — XLA fuses elementwise ops into the conv/matmul
  MXU kernels and inserts the data-parallel gradient all-reduce from the
  batch's ``data``-axis sharding (GSPMD), the psum the reference got from NCCL
  inside DataParallel/MirroredStrategy;
- metrics come back as device scalars, fetched asynchronously so the host
  epoch loop (LR plateau logic, best-val checkpointing — the reference's
  host-side callbacks) never stalls the device pipeline;
- eval accumulates metric *sums* on device and normalizes on host, like the
  reference's running ``total_correct/total`` counters
  (ResNet/pytorch/train.py:488-520).
"""

from __future__ import annotations

import functools
import json
import os
import time
from typing import Any, Iterable

import jax
import jax.numpy as jnp

from deep_vision_tpu.core import checkpoint as ckpt_lib
from deep_vision_tpu.core.config import TrainConfig
from deep_vision_tpu.core.metrics import MetricLogger, ThroughputMeter
from deep_vision_tpu.core.optim import (
    build_optimizer,
    build_scheduler,
    set_learning_rate,
)
from deep_vision_tpu.core.state import DivergenceGuard, TrainState
from deep_vision_tpu.obs import launch
from deep_vision_tpu.parallel import make_mesh, replicate, shard_batch


def install_sigterm_flag(on_sigterm):
    """Install a SIGTERM → callback handler; returns a restore function.
    Safe when not on the main thread (no-op) and when the previous handler
    was installed outside Python (restores SIG_DFL, not None)."""
    import signal

    try:
        prev = signal.signal(signal.SIGTERM, lambda *_: on_sigterm())
    except ValueError:  # not the main thread: no handler, no-op restore
        return lambda: None
    restore_to = prev if prev is not None else signal.SIG_DFL
    return lambda: signal.signal(signal.SIGTERM, restore_to)


def _clock_pair() -> tuple[int, int]:
    """The spans' clock beside the trace's: ``Span`` marks read
    ``time.monotonic``, a profiler trace dates itself in ns since the
    epoch."""
    return time.monotonic_ns(), time.time_ns()


class Trainer:
    """Single-model/single-optimizer trainer (classification, detection,
    pose).  Adversarial multi-model training lives in
    :class:`deep_vision_tpu.core.adversarial.AdversarialTrainer`."""

    @launch.staged("build")
    def __init__(self, config: TrainConfig, model, task, mesh=None,
                 workdir: str | None = None, preprocess_fn=None,
                 upload: str | None = None):
        self.config = config
        ema = float(getattr(config, "ema_decay", 0.0))
        if not 0.0 <= ema < 1.0:
            raise ValueError(
                f"ema_decay={ema} must be in [0, 1): 1.0 would freeze the "
                f"EMA at its init forever, >1 diverges")
        self.model = model
        self.task = task
        # optional device-side input preprocessing run INSIDE the jitted
        # steps (e.g. uint8→jitter→normalize, ops/preprocess.py) — XLA
        # fuses it into the first conv; signature (batch, rng, train)
        self.preprocess_fn = preprocess_fn
        self.mesh = mesh if mesh is not None else make_mesh()
        self.workdir = workdir or os.path.join("runs", config.name)
        self.logger = MetricLogger(self.workdir)
        self.tx = build_optimizer(config.optimizer)
        self.scheduler = build_scheduler(
            config.scheduler.name, config.optimizer.learning_rate,
            **config.scheduler.kwargs)
        # optional off-host artifact sync after each checkpoint (the
        # Hourglass GCS-upload role, Hourglass/tensorflow/main.py:21-65)
        self.uploader = None
        if upload:
            from deep_vision_tpu.core.upload import ArtifactUploader

            self.uploader = ArtifactUploader(upload)
            # preemption recovery: a fresh host (empty workdir) with a
            # populated mirror pulls the checkpoints back down before the
            # Checkpointer (whose Orbax manager scans at construction) and
            # maybe_resume() look for them — without this, the first
            # post-checkpoint sync of the fresh run would instead wipe
            # the mirror's preempt-saved copies (the only ones left)
            ckpt_dir = os.path.join(self.workdir, "checkpoints")
            if not os.path.isdir(ckpt_dir) or not os.listdir(ckpt_dir):
                self.uploader.restore(ckpt_dir, "checkpoints")
                self.uploader.restore(
                    os.path.join(self.workdir, "checkpoints_best"),
                    "checkpoints_best")
        self.checkpointer = ckpt_lib.Checkpointer(
            os.path.join(self.workdir, "checkpoints"),
            max_to_keep=config.keep_checkpoints)
        self.best_checkpointer = ckpt_lib.Checkpointer(
            os.path.join(self.workdir, "checkpoints_best"), max_to_keep=1)
        self._has_bn: bool | None = None
        self._jit_train_step = None
        self._jit_eval_step = None
        self.start_epoch = 1
        self.guard = DivergenceGuard(config.max_bad_steps)
        # preemption safety: TPU VMs get SIGTERM before eviction; fit()
        # installs a handler that requests a step-boundary checkpoint +
        # clean return so a preempted run loses at most one step, not an
        # epoch (the reference could only resume from its last epoch save)
        self._preempted = False
        # profiling: trace steps [start, stop) of epoch 1 to
        # workdir/profile (the reference had only throughput prints —
        # SURVEY §5 tracing; TPU-native answer is a jax.profiler trace)
        self.profile_steps: tuple[int, int] | None = None
        # staged input pipeline (data/pipeline.DevicePrefetcher): built
        # lazily on the first train epoch, persists across epochs so the
        # host staging pool reuses its buffers, closed by fit()'s finally
        # path so abandoned epochs leak neither thread nor device batches
        self.prefetch_depth = max(1, int(getattr(config,
                                                 "prefetch_depth", 2)))
        self._prefetcher = None

    # ------------------------------------------------------------------ init

    @launch.staged("init")
    def init_state(self, sample_batch: dict) -> TrainState:
        rng = jax.random.PRNGKey(self.config.seed)
        init_rng, state_rng = jax.random.split(rng)
        if hasattr(self.task, "model_inputs"):
            first = {k: jnp.asarray(v[:1]) for k, v in sample_batch.items()}
        else:
            first = {"image": jnp.asarray(sample_batch["image"][:1])}
        if self.preprocess_fn is not None:
            first = self.preprocess_fn(first, init_rng, train=False)
        variables = jax.jit(
            functools.partial(self.model.init, train=False)
        )({"params": init_rng, "dropout": init_rng}, *self._model_inputs(first))
        params = variables["params"]
        batch_stats = variables.get("batch_stats", {})
        self._has_bn = "batch_stats" in variables
        state = TrainState.create(
            apply_fn=self.model.apply, params=params, tx=self.tx,
            batch_stats=batch_stats, rng=state_rng,
            ema=getattr(self.config, "ema_decay", 0.0) > 0)
        return self._place_state(state)

    def _model_inputs(self, batch: dict) -> tuple:
        """What the model is called with: a task says so through
        ``model_inputs(batch)`` (tokens and segment ids, say); the image
        tasks do not, and their model gets ``batch["image"]``."""
        inputs = getattr(self.task, "model_inputs", None)
        return inputs(batch) if inputs is not None else (batch["image"],)

    @staticmethod
    @functools.lru_cache(maxsize=None)
    def _resharder(sharding):
        """One jitted identity per DISTINCT target sharding (its own jit
        cache then keys on leaf shape/dtype), so a reshard-restore
        compiles O(distinct shardings), not O(leaves) — a fresh
        ``jax.jit`` per leaf never hits the compile cache."""
        return jax.jit(lambda a: a, out_shardings=sharding)

    def _place_state(self, state: TrainState) -> TrainState:
        """Place state on the mesh.  Models that partition their own state
        (e.g. pipeline stages over ``pipe`` —
        ``parallel.pipelined.PipelinedModel.state_partition_rule``) expose
        a per-leaf rule: (path string, leaf) → PartitionSpec; params, EMA
        copy, and optimizer moments all flow through it (the moments
        mirror the param tree, so path matching covers them).  Without a
        rule, everything is replicated (the dp/tp default)."""
        rule = getattr(self.model, "state_partition_rule", None)
        if rule is None:
            return replicate(state, self.mesh)
        from jax.sharding import NamedSharding

        multiproc = jax.process_count() > 1

        def place(path, leaf):
            spec = rule(jax.tree_util.keystr(path), leaf)
            sharding = NamedSharding(self.mesh, spec)
            if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
                # already a GLOBAL array — e.g. Orbax restored it into the
                # placed template's shardings on a multi-process mesh; its
                # remote shards can't be read host-side, and don't need to
                # be: keep it, or reshard device-side if the target differs
                if leaf.sharding.is_equivalent_to(sharding, leaf.ndim):
                    return leaf
                return self._resharder(sharding)(leaf)
            if multiproc:
                # device_put can't build a multi-host global array from a
                # host-local value; assemble it the way replicate() does.
                # global_shape=leaf.shape: every host holds the FULL leaf
                # (init/restore are replicated), so local data IS the global
                # array — without it, a rule axis spanning processes would
                # be inferred as a per-host chunk and double-counted
                return jax.make_array_from_process_local_data(
                    sharding, leaf, global_shape=leaf.shape)
            return jax.device_put(leaf, sharding)

        return jax.tree_util.tree_map_with_path(place, state)

    def maybe_resume(self, state: TrainState) -> TrainState:
        """Resume from the latest checkpoint if one exists (the reference's
        ``-c`` flag, ResNet/pytorch/train.py:381-388)."""
        if self.checkpointer.latest_step() is None:
            return state
        return self._restore(state)

    @launch.staged("restore")
    def _restore(self, state: TrainState) -> TrainState:
        # reconcile EMA with what the checkpoint actually stores: enabling
        # --ema-decay on a checkpoint trained without it must seed the EMA
        # from the RESTORED params (not the fresh random init the template
        # carries, and not crash on a {} / missing stored subtree)
        ema_on = float(getattr(self.config, "ema_decay", 0.0)) > 0
        if ema_on and not self.checkpointer.has_state_key("ema_params"):
            state, extras = self.checkpointer.restore(
                state.replace(ema_params={}))
            state = state.replace(ema_params=jax.tree_util.tree_map(
                jnp.array, state.params))
            print("[resume] checkpoint has no EMA — seeded from restored "
                  "params")
        else:
            state, extras = self.checkpointer.restore(state)
        self.start_epoch = int(extras.get("epoch", 0)) + 1
        if "scheduler" in extras:
            self.scheduler.load_state_dict(extras["scheduler"])
        if "history" in extras:
            self.logger.load_state_dict(extras["history"])
        # old skips must not count against the resumed run's budget
        self.guard.set_baseline(int(jax.device_get(state.bad_steps)))
        print(f"[resume] restored step={int(state.step)} "
              f"start_epoch={self.start_epoch}")
        return self._place_state(state)

    # ------------------------------------------------------------- jit steps

    def _build_steps(self):
        task, has_bn = self.task, self._has_bn
        preprocess_fn = self.preprocess_fn
        model_inputs = self._model_inputs

        accum = max(1, getattr(self.config, "grad_accum_steps", 1))
        ema_decay = float(getattr(self.config, "ema_decay", 0.0))

        def grad_one(apply_fn, params, batch_stats, dropout_rng, batch):
            """loss/grads/BN-update for ONE (micro)batch."""

            def loss_fn(params):
                variables = {"params": params}
                if has_bn:
                    variables["batch_stats"] = batch_stats
                # scopes name the step's phases in a device trace: what
                # runs under these two comes out as jvp(forward) and
                # jvp(loss), their gradients as transpose(jvp(...))
                with jax.named_scope("forward"):
                    out = apply_fn(
                        variables, *model_inputs(batch), train=True,
                        rngs={"dropout": dropout_rng},
                        mutable=["batch_stats"] if has_bn else False)
                if has_bn:
                    out, new_vars = out
                    new_bs = new_vars["batch_stats"]
                else:
                    new_bs = batch_stats
                with jax.named_scope("loss"):
                    loss, aux = task.loss(out, batch)
                return loss, (new_bs, aux)

            (loss, (new_bs, aux)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            return loss, new_bs, aux, grads

        def train_step(state: TrainState, batch: dict):
            step_rng = jax.random.fold_in(state.rng, state.step)
            if preprocess_fn is not None:
                with jax.named_scope("prologue"):
                    batch = preprocess_fn(
                        batch, jax.random.fold_in(step_rng, 1), train=True)

            if accum == 1:
                loss, new_bs, aux, grads = grad_one(
                    state.apply_fn, state.params, state.batch_stats,
                    step_rng, batch)
            else:
                # gradient accumulation: A sequential microbatches, one
                # optimizer update.  Interleaved split (microbatch a =
                # batch[a::A]) keeps every microbatch evenly spread over
                # the data-sharded batch dim, so each micro-step is the
                # same all-devices data-parallel step — GSPMD sees a
                # local reshape, no resharding.  Mean-reduced losses make
                # the averaged grads EXACTLY the full-batch grads for
                # BN-free models (tests/test_grad_accum.py); with BN,
                # stats thread through microbatches sequentially.
                b = jax.tree_util.tree_leaves(batch)[0].shape[0]
                if b % accum:
                    raise ValueError(
                        f"global batch {b} not divisible by "
                        f"grad_accum_steps={accum}")

                def split(x):
                    return jnp.swapaxes(
                        x.reshape(x.shape[0] // accum, accum,
                                  *x.shape[1:]), 0, 1)

                micro = jax.tree_util.tree_map(split, batch)
                gzero = jax.tree_util.tree_map(jnp.zeros_like, state.params)

                def body(carry, xs):
                    bs, gsum = carry
                    mb, i = xs
                    l, bs, a, g = grad_one(
                        state.apply_fn, state.params, bs,
                        jax.random.fold_in(step_rng, 2 + i), mb)
                    gsum = jax.tree_util.tree_map(jnp.add, gsum, g)
                    return (bs, gsum), (l, a)

                (new_bs, gsum), (losses, auxes) = jax.lax.scan(
                    body, (state.batch_stats, gzero),
                    (micro, jnp.arange(accum)))
                grads = jax.tree_util.tree_map(
                    lambda g: g / accum, gsum)
                loss = jnp.mean(losses)
                aux = jax.tree_util.tree_map(
                    lambda a: jnp.mean(a, axis=0), auxes)

            # divergence guard: a non-finite loss/grad step is skipped (not
            # applied) and counted; the epoch loop halts past
            # config.max_bad_steps (reference context: the NaN val losses
            # Hourglass/tensorflow/train.py:126-130 only TODO'd about)
            with jax.named_scope("optimizer"):
                new_state = state.apply_gradients_if_finite(
                    loss, grads, batch_stats=new_bs)
                if ema_decay:
                    # guard-aware: a skipped step reverted params, so the
                    # EMA merely re-averages toward the unchanged weights.
                    # Warmup (tf.train.ExponentialMovingAverage num_updates
                    # / timm ModelEmaV2 semantics): the effective decay
                    # ramps as min(d, (1+t)/(10+t)) so short or
                    # freshly-seeded runs aren't dominated by the seed
                    # point at high decays.
                    t = new_state.step.astype(jnp.float32)
                    d = jnp.minimum(ema_decay, (1.0 + t) / (10.0 + t))
                    new_state = new_state.replace(
                        ema_params=jax.tree_util.tree_map(
                            lambda e, p: d * e + (1 - d) * p,
                            new_state.ema_params, new_state.params))
            metrics = {"loss": loss, "bad_steps": new_state.bad_steps, **aux}
            return new_state, metrics

        # host-evaluator protocol (e.g. detection mAP): the task decodes
        # postprocessed outputs ON DEVICE (static shapes — decode+NMS stay
        # XLA-compiled) in the SAME forward pass as the loss metrics; the
        # host accumulates AP across the val set
        has_outputs = hasattr(task, "eval_outputs")

        def eval_step(state: TrainState, batch: dict):
            if preprocess_fn is not None:
                batch = preprocess_fn(batch, jax.random.PRNGKey(0),
                                      train=False)
            # modern-recipe semantics: with EMA on, validation/serving
            # scores the averaged copy (what gets deployed), not the raw
            # last-step weights.  Emptiness is pytree structure — static
            # at trace time — so a state without an EMA copy (old
            # checkpoint, external caller) falls back to raw params
            # instead of crashing.
            use_ema = ema_decay and bool(
                jax.tree_util.tree_leaves(state.ema_params))
            variables = {"params": state.ema_params if use_ema
                         else state.params}
            if has_bn:
                variables["batch_stats"] = state.batch_stats
            out = state.apply_fn(variables, *model_inputs(batch), train=False)
            sums = task.eval_metrics(out, batch)
            extra = None
            if has_outputs:
                extra = task.eval_outputs(out, batch)
                if "weight" in batch:
                    extra["weight"] = batch["weight"]
            return sums, extra

        # donate the BATCH too (argnum 1): the prefetcher's device batches
        # are consumed exactly once, so XLA may overwrite their HBM in
        # place — input buffers stop double-counting against HBM headroom.
        # Host numpy batches (tests, direct callers) are unaffected:
        # donation only claims committed jax.Arrays.
        self._jit_train_step = jax.jit(train_step, donate_argnums=(0, 1))
        self._jit_eval_step = jax.jit(eval_step)

    def train_step(self, state, batch):
        if self._jit_train_step is None:
            self._build_steps()
        return self._jit_train_step(state, shard_batch(batch, self.mesh))

    def eval_step(self, state, batch):
        """Metric sums for one batch (decoded-output extras, if the task
        produces them, are consumed by :meth:`evaluate`)."""
        if self._jit_eval_step is None:
            self._build_steps()
        sums, _ = self._jit_eval_step(state, shard_batch(batch, self.mesh))
        return sums

    # ------------------------------------------------------------------ loops

    def evaluate(self, state: TrainState, val_data: Iterable) -> dict:
        if self._has_bn is None:
            # evaluating a restored state without going through init_state
            # (e.g. cli.infer eval): derive BN presence from the state
            self._has_bn = bool(state.batch_stats)
        if self._jit_eval_step is None:
            self._build_steps()
        make_ev = getattr(self.task, "make_host_evaluator", None)
        evaluator = make_ev() if make_ev is not None else None
        totals: dict[str, float] = {}
        for batch in val_data:
            batch = shard_batch(batch, self.mesh)
            sums, extra = self._jit_eval_step(state, batch)
            sums = jax.device_get(sums)
            for k, v in sums.items():
                totals[k] = totals.get(k, 0.0) + float(v)
            if evaluator is not None and extra is not None:
                if jax.process_count() > 1:
                    # extras are batch-sharded over `data`, which spans
                    # processes — gather every host's shard (the gather
                    # is collective: every rank must call it) but feed
                    # the host-side accumulator on process 0 ONLY; the
                    # other ranks get the scalar metrics broadcast below
                    # instead of redoing the whole mAP sweep per rank
                    from jax.experimental import multihost_utils
                    extra = multihost_utils.process_allgather(extra,
                                                              tiled=True)
                    if jax.process_index() != 0:
                        continue
                else:
                    extra = jax.device_get(extra)
                evaluator.add_batch(extra)
        count = max(totals.pop("count", 1.0), 1.0)
        out = {k: v / count for k, v in totals.items()}
        if evaluator is not None:
            ev = evaluator.compute()
            if jax.process_count() > 1:
                # non-zero ranks hold an EMPTY accumulator: compute()
                # still yields the metric KEYS (zero-valued), which is
                # all they need to receive rank 0's values in a fixed
                # key order — every rank reports identical metrics while
                # only one ran the host-side mAP sweep
                import numpy as np
                from jax.experimental import multihost_utils
                keys = sorted(k for k, v in ev.items()
                              if isinstance(v, (int, float)))
                vals = multihost_utils.broadcast_one_to_all(
                    np.asarray([float(ev[k]) for k in keys], np.float32))
                ev = {k: float(v) for k, v in zip(keys, np.asarray(vals))}
            out.update(ev)
        return out

    def _get_prefetcher(self):
        if self._prefetcher is None:
            from deep_vision_tpu.data.pipeline import DevicePrefetcher

            self._prefetcher = DevicePrefetcher(self.mesh,
                                                depth=self.prefetch_depth)
        return self._prefetcher

    def _log_input_stats(self, step: int, stats: dict, epoch: int):
        """The input-goodput block: epoch-level stall fraction + per-step
        H2D traffic from the prefetcher's stage timers, logged to the
        MetricLogger series and echoed as one epoch line."""
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

    def _start_trace(self):
        """Device operations and Python frames, the host runtime's own
        events left out: with them the H2D linearize thread alone writes
        4.6 M events for a dozen steps and each transfer takes eight to ten
        times as long, so the traced steps would measure the tracer
        (PERF.md §6, PR 25)."""
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = 0
        jax.profiler.start_trace(os.path.join(self.workdir, "profile"),
                                 profiler_options=options)

    def _write_spans(self, stream, clock: list):
        """``workdir/spans.jsonl`` for a profiled epoch: a header, then one
        line per interval of the prefetcher's producer and of this loop,
        in ``time.time_ns`` terms.  A trace's ``Task Environment`` plane
        gives its start on that clock, so a reader can lay these on the
        device's timeline (docs/OBSERVABILITY.md has the schema).  The
        process's launch record goes beside it under the same clock rule,
        as ``workdir/launch.jsonl`` (obs/launch.py)."""
        t0 = time.perf_counter()
        launch.start().write(os.path.join(self.workdir, "launch.jsonl"), clock)
        t1 = time.perf_counter()
        stats = stream.stats()
        mono_ns, wall_ns = clock[0]
        path = os.path.join(self.workdir, "spans.jsonl")
        with open(path, "w") as f:
            f.write(json.dumps({
                "clock": clock, "profile_steps": list(self.profile_steps),
                "depth": stream.depth, "batches": stats["batches"],
                "h2d_bytes": stats["h2d_bytes"], **stats["counters"]}) + "\n")
            for thread, stage, batch, a, b in stream.intervals():
                f.write(json.dumps({
                    "thread": thread, "stage": stage, "batch": batch,
                    "t0_ns": round(a * 1e9) - mono_ns + wall_ns,
                    "t1_ns": round(b * 1e9) - mono_ns + wall_ns}) + "\n")
        print(f"[profile] spans written to {path} in "
              f"{(time.perf_counter() - t0) * 1e3:.1f} ms, launch.jsonl "
              f"{(t1 - t0) * 1e3:.1f} ms of it", flush=True)

    def train_epoch(self, state: TrainState, train_data: Iterable,
                    epoch: int) -> TrainState:
        with launch.start().listen().epoch() as record:
            return self._train_epoch(state, train_data, epoch, record)

    def _train_epoch(self, state: TrainState, train_data: Iterable,
                     epoch: int, record: launch.LaunchLog) -> TrainState:
        cfg = self.config
        meter = ThroughputMeter()
        pending = None  # async metric fetch: log step N-1 while N runs
        profiling = self.profile_steps if epoch == self.start_epoch else None
        trace_active = False
        clock = []  # _clock_pair() as the trace starts and as it stops
        # staged input pipeline: batch N+1 assembles/stages/transfers on
        # the producer thread while step N computes; the stream yields
        # already-placed device batches (shard_batch in train_step is a
        # no-op on them) that the jitted step consumes via donation
        stream = self._get_prefetcher().iterate(
            train_data, counters=getattr(self.task, "batch_counters", None))
        record.watch(stream)
        for i, batch in enumerate(stream):
            if profiling is not None:
                if i == profiling[0]:
                    clock.append(_clock_pair())
                    self._start_trace()
                    stream.mark("profile")
                    trace_active = True
                elif i == profiling[1]:
                    jax.profiler.stop_trace()
                    clock.append(_clock_pair())
                    stream.mark("profile")
                    trace_active = False
                    print(f"[profile] trace written to "
                          f"{self.workdir}/profile", flush=True)
                    profiling = None
            bs = len(jax.tree_util.tree_leaves(batch)[0])
            state, metrics = self.train_step(state, batch)
            # the consumer's span, split where the loop can wait: the
            # jitted call's return, the read of the last step's metrics
            # (the host's wait for the device), the guard and the logger;
            # the rest of the iteration closes as "step" at the next dequeue
            stream.mark("dispatch")
            if i == 0:
                record.first("first_dispatch")
            meter.update(bs)
            if pending is not None and (i % cfg.log_every_steps == 0):
                m = {k: float(v) for k, v in jax.device_get(pending).items()}
                stream.mark("fetch")
                record.first("first_fetch")
                self.guard.check(m)
                self.logger.log_dict(int(state.step) - 1,
                                     {f"train_{k}": v for k, v in m.items()})
                print(f"Epoch {epoch} Batch {i} loss {m['loss']:.4f} "
                      f"lr {self.scheduler.lr:.2e} "
                      f"{meter.images_per_sec:.1f} img/s", flush=True)
                stream.mark("log")
            pending = metrics
            if self._preempted:
                print("[preempt] SIGTERM — stopping at step boundary",
                      flush=True)
                break
        if trace_active:
            # epoch ended inside the trace window: flush what we have
            jax.profiler.stop_trace()
            clock.append(_clock_pair())
            print(f"[profile] short-epoch trace written to "
                  f"{self.workdir}/profile", flush=True)
        if pending is not None:
            m = {k: float(v) for k, v in jax.device_get(pending).items()}
            record.first("first_fetch")
            self.guard.check(m)
            self.logger.log_dict(int(state.step),
                                 {f"train_{k}": v for k, v in m.items()})
        if clock:
            self._write_spans(stream, clock)
        self.logger.log("images_per_sec", int(state.step), meter.images_per_sec)
        self._log_launch(int(state.step), epoch, record)
        self._log_input_stats(int(state.step), stream.stats(), epoch)
        return state

    def _log_launch(self, step: int, epoch: int, record: launch.LaunchLog):
        """What an operator gets without a trace: the process's first epoch
        prints where the time before its first step went, and any epoch in
        which a program compiled after its first dispatch names it."""
        if record.epochs == 1:
            print(record.summary(), flush=True)
        late = record.late
        self.logger.log("train_compiles", step, len(late))
        if late:
            print(f"[compile] epoch {epoch} " + "; ".join(
                f"batch {batch}: {fun} {secs:.1f}s {cache or 'uncached'}"
                for fun, secs, cache, batch in late), flush=True)

    def fit(self, train_data, val_data=None, state: TrainState | None = None,
            resume: bool = False, monitor: str | None = None) -> TrainState:
        """The reference's ``run_epochs`` (ResNet/pytorch/train.py:310-428):
        epoch loop of train → validate → scheduler.step(metric) → checkpoint."""
        cfg = self.config
        if state is None:
            sample = next(iter(train_data))
            state = self.init_state(sample)
        if resume:
            state = self.maybe_resume(state)
        monitor = monitor or getattr(self.task, "monitor", None)
        best = None
        restore_handler = self._install_preempt_handler()
        try:
            return self._fit_epochs(train_data, val_data, state, monitor,
                                    best)
        finally:
            restore_handler()
            # abandoned epochs (preemption, divergence abort, exception)
            # must not leave a producer thread parked on the queue or
            # device batches pinned in it
            if self._prefetcher is not None:
                self._prefetcher.close()
            # the last epoch's async saves must commit before the process
            # exits — interpreter shutdown kills orbax's background
            # executor mid-finalize, leaving a *.orbax-checkpoint-tmp-*
            # directory that restore() cannot see
            for ckpt in (self.checkpointer, self.best_checkpointer):
                try:
                    ckpt.wait_until_finished()
                except Exception:  # noqa: BLE001 — a failed async save already logged itself; don't mask the fit() result
                    pass

    def _install_preempt_handler(self):
        self._preempted = False  # stale flag must not abort a fresh fit()
        return install_sigterm_flag(
            lambda: setattr(self, "_preempted", True))

    def _fit_epochs(self, train_data, val_data, state, monitor, best):
        cfg = self.config
        for epoch in range(self.start_epoch, cfg.total_epochs + 1):
            # LR for THIS epoch (so warmup covers epoch 1); plateau-style
            # metric schedules adjust in scheduler.step() after validation.
            lr = self.scheduler.epoch_begin(epoch)
            state = state.replace(
                opt_state=set_learning_rate(state.opt_state, lr))
            if hasattr(train_data, "set_epoch"):
                train_data.set_epoch(epoch)
            t0 = time.monotonic()
            state = self.train_epoch(state, train_data, epoch)
            if self._preempted:
                # mid-epoch save as epoch-1: resume re-runs this epoch
                # from its start but keeps every applied step/param update
                self.save(state, epoch - 1)
                # the VM disappears seconds after SIGTERM: block until
                # the (possibly async) save is durable before reporting
                self.checkpointer.wait_until_finished()
                print(f"[preempt] checkpoint saved at step "
                      f"{int(jax.device_get(state.step))}; rerun with "
                      f"--resume to continue", flush=True)
                return state
            metric_val = None
            if val_data is not None:
                val_metrics = self.evaluate(state, val_data)
                self.logger.log_dict(
                    int(state.step),
                    {f"val_{k}": v for k, v in val_metrics.items()})
                if monitor is not None:
                    metric_val = val_metrics.get(monitor)
                print(f"Epoch {epoch} val "
                      + " ".join(f"{k}={v:.4f}" for k, v in val_metrics.items())
                      + f" ({time.monotonic() - t0:.1f}s)", flush=True)
            if self._preempted:
                # SIGTERM during validation: save NOW — the preemption
                # grace period is too short for best-ckpt/scheduler work
                self.save(state, epoch)
                self.checkpointer.wait_until_finished()  # durable first
                print(f"[preempt] checkpoint saved at step "
                      f"{int(jax.device_get(state.step))}; rerun with "
                      f"--resume to continue", flush=True)
                return state
            self.scheduler.step(epoch, metric_val)
            if epoch % cfg.checkpoint_every_epochs == 0:
                self.save(state, epoch)
            if metric_val is not None and (best is None or metric_val > best):
                # best-val checkpoint, kept separately from the rolling window
                # (the reference's save-best-by-val, YOLO/tensorflow/train.py:243-247)
                best = metric_val
                self.best_checkpointer.save(
                    int(jax.device_get(state.step)), state,
                    extras={"epoch": epoch, "metric": float(metric_val),
                            "monitor": monitor or ""})
                if self.uploader is not None:
                    # the async save must be on disk before the mirror
                    # copies the directory (else it uploads a partial)
                    self.best_checkpointer.wait_until_finished()
                    self.uploader.sync(self.best_checkpointer.directory,
                                       "checkpoints_best")
        return state

    def save(self, state: TrainState, epoch: int):
        self.checkpointer.save(
            int(jax.device_get(state.step)), state,
            extras={"epoch": epoch,
                    "scheduler": self.scheduler.state_dict(),
                    "history": self.logger.state_dict()})
        if self.uploader is not None:
            # durability barrier before the mirror walks the directory
            self.checkpointer.wait_until_finished()
            self.uploader.sync(self.checkpointer.directory, "checkpoints")
