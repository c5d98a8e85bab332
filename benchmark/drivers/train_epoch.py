"""Driver for training cells: the window is ONE call of
``Trainer.train_epoch`` on the harness's iterable.

The trainer is built as ``deep_vision_tpu/cli/train.py`` builds it (config
from the zoo, model, task, device-side prologue, ``Trainer``); the only
field of the config that is set is ``batch_size``, where the configuration
file lists it under ``reduced``.  The weights and the state's key are the
benchmark's own, made from the seed (``benchmark/weights.py``), so that the
plain reference can be handed the same ones.

Set-up drives the first ``check_steps`` steps through that same call, one
batch each, and keeps on the host what the comparison needs: the starting
parameters, the optimizer's first moment after step 1 (which gives the first
gradient as the optimizer got it) and the parameters after the last of
them.  The window then goes on from that state, on that compiled step.
"""

from __future__ import annotations

import json
import os
import shutil
import time

import jax
import numpy as np

from benchmark import compare, refnn, weights
from benchmark.byname import load_module


def flat(tree) -> dict:
    from flax import traverse_util

    return traverse_util.flatten_dict(dict(tree), sep="/")


def unflat(leaves: dict):
    from flax import traverse_util

    return traverse_util.unflatten_dict(leaves, sep="/")


# ---------------------------------------------------------------- the build

def build(config: dict, traffic: dict, env: dict) -> dict:
    """The seed-independent part: config, task, prologue, Trainer."""
    from deep_vision_tpu.core.config import get_config
    from deep_vision_tpu.core.trainer import Trainer
    from deep_vision_tpu.parallel import make_mesh

    cfg = get_config(config["zoo_config"])
    if cfg.batch_size != config["batch_size"]:
        if "batch_size" not in config["reduced"]:
            raise ValueError("batch_size differs from the zoo's and is not "
                             "listed under reduced")
        cfg.batch_size = cfg.eval_batch_size = int(config["batch_size"])
    for key in ("image_size", "num_classes", "channels"):
        if getattr(cfg, key) != config[key]:
            raise ValueError(f"{key}: the file says {config[key]}, the zoo "
                             f"{getattr(cfg, key)}")
    mesh = make_mesh(devices=jax.devices()[: env["chips"]])
    per_shard = max(cfg.batch_size // mesh.shape.get("data", 1), 1)
    on_tpu = jax.default_backend() == "tpu"

    if cfg.task == "classification":
        from deep_vision_tpu.tasks.classification import ClassificationTask

        task = ClassificationTask(cfg.num_classes, cfg.label_smoothing)
    elif cfg.task == "detection":
        from deep_vision_tpu.tasks.detection import MAX_BOXES, YoloTask

        if on_tpu:
            from deep_vision_tpu.ops.pallas_ops import best_iou_parity

            for stride in config["strides"]:
                best_iou_parity(batch=per_shard,
                                n_pred=3 * (cfg.image_size // stride) ** 2,
                                n_gt=MAX_BOXES)
        task = YoloTask(cfg.num_classes, use_pallas=on_tpu,
                        mesh=mesh if mesh.devices.size > 1 else None)
    else:
        raise ValueError(f"no train_epoch build for task {cfg.task!r}")

    from deep_vision_tpu.ops import preprocess

    prologue = config["prologue"]
    if prologue == "imagenet_fused":
        preprocess_fn = preprocess.make_imagenet_preprocess(
            use_fused=True,
            fused_shape=(per_shard, cfg.image_size, cfg.image_size, 3),
            mesh=mesh)
    elif prologue == "scale":
        preprocess_fn = preprocess.make_scale_preprocess()
    elif prologue == "mnist":
        preprocess_fn = preprocess.make_mnist_preprocess()
    else:
        raise ValueError(f"unknown prologue {prologue!r}")

    workdir = env["workdir"]
    shutil.rmtree(workdir, ignore_errors=True)
    trainer = Trainer(cfg, cfg.model(), task, mesh=mesh, workdir=workdir,
                      preprocess_fn=preprocess_fn)
    reference = load_module(os.path.join(env["config_dir"], config["name"] + ".py"),
                      "benchmark_reference").Reference(config)
    return {"trainer": trainer, "reference": reference, "config": config,
            "traffic": traffic}


def fresh_state(built: dict, seed: int, sample: dict):
    """The trainer's own state, with the benchmark's weights and key."""
    trainer = built["trainer"]
    state = trainer.init_state(sample)
    old = flat(state.params)
    new = weights.make({k: v.shape for k, v in old.items()}, seed,
                       {k: v.sharding for k, v in old.items()},
                       built["config"].get("init_scales"))
    key = jax.device_put(np.asarray(weights.seed_key(seed)),
                         state.rng.sharding)
    return state.replace(params=unflat(new), rng=key)


# -------------------------------------------------- what the program did

def _first_moment(opt_state) -> dict:
    """Momentum SGD's trace or Adam's mu, wherever the chain keeps it."""
    import optax

    def is_moment(node):
        return isinstance(node, (optax.TraceState, optax.ScaleByAdamState))

    found = [n for n in jax.tree_util.tree_leaves(opt_state, is_leaf=is_moment)
             if is_moment(n)]
    if len(found) != 1:
        raise ValueError(f"{len(found)} first moments in the optimizer state")
    node = found[0]
    return flat(node.trace if isinstance(node, optax.TraceState) else node.mu)


def _first_gradient(moment: dict, params0: dict, hyper: dict) -> dict:
    """The gradient the optimizer was handed at step 1 (after any clip),
    from its first moment after that step."""
    if hyper["name"] == "sgd":
        wd = hyper["weight_decay"]
        return {k: m - (wd * params0[k] if wd and refnn.decays(k) else 0.0)
                for k, m in moment.items()}
    if hyper["name"] == "adam":
        return {k: m / (1.0 - hyper["b1"]) for k, m in moment.items()}
    raise ValueError(f"no first gradient for optimizer {hyper['name']!r}")


def checked_steps(built: dict, state, batches: list):
    """Drive the first steps through ``train_epoch``, one batch each, and
    return the state after them with the program's record of them."""
    trainer = built["trainer"]
    hyper = built["config"]["optimizer"]
    params0 = jax.device_get(flat(state.params))
    losses, moment = [], None
    for i, batch in enumerate(batches):
        state = trainer.train_epoch(state, [batch], trainer.start_epoch)
        losses.append(trainer.logger.history["train_loss"]["values"][-1])
        if i == 0:
            moment = jax.device_get(_first_moment(state.opt_state))
    params_n = jax.device_get(flat(state.params))
    record = {
        "loss": losses,
        "grad": _first_gradient(moment, params0, hyper),
        "delta": {k: params_n[k] - params0[k] for k in params0},
    }
    return state, params0, record


# ------------------------------------------------------------- the harness

def enable_cache():
    """The program's cache, then every program under it, however short its
    compile: the init, the parity probes and the reference are cached too."""
    from deep_vision_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def setup(config: dict, traffic: dict, seed: int, env: dict) -> dict:
    generator = load_module(os.path.join(env["code"], "generators",
                                   traffic["generator"] + ".py"),
                      "benchmark_generator")
    enable_cache()
    t0 = time.perf_counter()
    pool = generator.make_pool(config, traffic, seed)
    t1 = time.perf_counter()
    built = build(config, traffic, env)
    state = fresh_state(built, seed, pool[0])
    t2 = time.perf_counter()
    n = int(traffic["check_steps"])
    state, params0, record = checked_steps(built, state, pool[:n])
    t3 = time.perf_counter()
    print(f"[setup] pool {t1 - t0:.1f}s  build+init {t2 - t1:.1f}s  "
          f"first {n} steps {t3 - t2:.1f}s  losses "
          + " ".join(f"{v:.4f}" for v in record["loss"]), flush=True)
    built.update(state=state, pool=pool, params0=params0, program=record,
                 seed=seed, checked=n)
    return built


class Feed:
    """Cycles the pool until the deadline, then stops.  The window opens at
    the first batch handed over."""

    def __init__(self, pool: list, start: int, seconds: float):
        self.pool, self.i, self.seconds = pool, start, seconds
        self.opened = None
        self.handed = 0

    def __iter__(self):
        return self

    def __next__(self):
        now = time.perf_counter()
        if self.opened is None:
            self.opened = now
        elif now - self.opened >= self.seconds:
            raise StopIteration
        batch = self.pool[self.i % len(self.pool)]
        self.i += 1
        self.handed += 1
        return batch


class quiet_host_tracer:
    """While open, ``jax.profiler.start_trace`` (which the trainer calls with
    a directory and nothing else) records the device and the Python frames
    and leaves the host runtime's own events out.  With them the runtime's
    H2D linearize thread alone writes 4.6 million events for a dozen steps
    (166 MB, at levels 2 and 1 alike) and each transfer takes eight to ten
    times as long, so the traced steps would measure the tracer."""

    def __enter__(self):
        self.original = jax.profiler.start_trace

        def start_trace(log_dir, *args, **kwargs):
            options = jax.profiler.ProfileOptions()
            options.host_tracer_level = 0
            kwargs.setdefault("profiler_options", options)
            return self.original(log_dir, *args, **kwargs)

        jax.profiler.start_trace = start_trace

    def __exit__(self, *exc):
        jax.profiler.start_trace = self.original


def window(ctx: dict, seconds: float, trace: bool) -> dict:
    trainer, state = ctx["trainer"], ctx.pop("state")
    trainer.profile_steps = (tuple(ctx["traffic"]["trace_steps"])
                             if trace else None)
    logged = len(trainer.logger.history["train_loss"]["values"])
    step0 = int(state.step)
    feed = Feed(ctx["pool"], ctx["checked"], seconds)
    with quiet_host_tracer():
        state = trainer.train_epoch(state, feed, trainer.start_epoch)
    closed = time.perf_counter()
    steps = int(state.step) - step0
    history = trainer.logger.history
    losses = history["train_loss"]["values"][logged:]
    skipped = int(history["train_bad_steps"]["values"][-1])
    print(f"[window] {steps} steps  loss first {losses[0]:.4f} last "
          f"{losses[-1]:.4f}  skipped {skipped}", flush=True)
    ctx["state"] = state
    ctx["final_loss"] = float(losses[-1])
    return {
        "opened": feed.opened,
        "seconds": closed - feed.opened,
        "steps": steps,
        "images": steps * int(ctx["config"]["batch_size"]),
        "attempted": steps,
        "failed": skipped,
        "workdir": trainer.workdir,
        "trace_dir": os.path.join(trainer.workdir, "profile") if trace else None,
        "step_module": "jit_train_step",
    }


def release(ctx: dict):
    """Stop the prefetcher and drop the program's state from the device."""
    trainer = ctx.pop("trainer")
    if trainer._prefetcher is not None:
        trainer._prefetcher.close()
    ctx.pop("state", None)
    del trainer
    jax.clear_caches()


def check(ctx: dict, win: dict) -> tuple[dict, dict]:
    """Numbers compared and their limits.  Runs once the window has closed
    and the peak has been read: the program's state goes first."""
    release(ctx)
    config, n = ctx["config"], ctx["checked"]
    t0 = time.perf_counter()
    reference = refnn.run_steps(ctx["reference"], config["optimizer"],
                                ctx["params0"], ctx["pool"][:n],
                                weights.seed_key(ctx["seed"]))
    numbers, leaves = compare.training_numbers(
        ctx["program"], reference, config.get("output_layers", ()))
    numbers["skipped_steps"] = float(win["failed"])
    numbers["final_loss_nonfinite"] = 0.0 if np.isfinite(
        ctx["final_loss"]) else 1.0
    print(f"[check] reference {time.perf_counter() - t0:.1f}s  "
          f"losses {json.dumps(reference['loss'])}  numbers "
          f"{json.dumps(numbers)}  worst leaves {json.dumps(leaves)}",
          flush=True)
    return numbers, dict(config["limits"])
