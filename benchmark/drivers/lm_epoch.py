"""Driver for language-model training cells: like ``train_epoch.py`` the
window is ONE call of ``Trainer.train_epoch`` on the harness's iterable, and
the first ``check_steps`` steps go through that same call, one batch each.

The trainer is built as ``deep_vision_tpu/cli/train.py`` builds it (config
from the zoo, its model, ``LanguageModelingTask``, ``Trainer``).  The zoo
holds the model at its published size; the keys the configuration file lists
under ``reduced`` are set from the file, every other key of the architecture
has to agree with it.  The weights are the benchmark's own
(``benchmark/weights_lm.py``), and the plain reference beside the
configuration file follows the same three steps from the same weights and
batches (the model draws nothing, so there is no key to share).

From ``train_epoch.py``, unchanged: the feed, the window, the record of the
checked steps and the release of the program's state.
"""

from __future__ import annotations

import json
import os
import shutil
import time

import jax
import numpy as np

from benchmark import compare, weights_lm
from benchmark.byname import load_module
from benchmark.drivers.train_epoch import (  # noqa: F401 — window is the driver's
    checked_steps,
    enable_cache,
    flat,
    release,
    unflat,
    window,
)


def build(config: dict, traffic: dict, env: dict) -> dict:
    """The seed-independent part: config, model, task, Trainer."""
    from deep_vision_tpu.core.config import get_config
    from deep_vision_tpu.core.trainer import Trainer
    from deep_vision_tpu.parallel import make_mesh
    from deep_vision_tpu.tasks.language_modeling import LanguageModelingTask

    cfg = get_config(config["zoo_config"])
    arch = cfg.extra["architecture"]
    for key, value in arch.items():
        if key in config["reduced"]:
            arch[key] = config[key]
        elif config[key] != value:
            raise ValueError(f"{key}: the file says {config[key]!r}, the zoo "
                             f"{value!r}, and it is not listed under reduced")
    cfg.extra["sequence_length"] = config["sequence_length"]
    cfg.batch_size = cfg.eval_batch_size = int(config["batch_size"])
    cfg.half_precision = {"bfloat16": True, "float32": False}[config["compute_dtype"]]
    cfg.num_classes = int(config["vocab_size"])
    ours, theirs = config["optimizer"], cfg.optimizer
    for key, value in ours.items():
        if getattr(theirs, key) != value:
            raise ValueError(f"optimizer {key}: the file says {value!r}, the "
                             f"zoo {getattr(theirs, key)!r}")
    mesh = make_mesh(devices=jax.devices()[: env["chips"]])
    workdir = env["workdir"]
    shutil.rmtree(workdir, ignore_errors=True)
    trainer = Trainer(cfg, cfg.model(), LanguageModelingTask(), mesh=mesh,
                      workdir=workdir)
    reference = load_module(os.path.join(env["config_dir"], config["name"] + ".py"),
                            "benchmark_reference").Reference(config)
    return {"trainer": trainer, "reference": reference, "config": config,
            "traffic": traffic}


def fresh_state(built: dict, seed: int, sample: dict):
    """The trainer's own state with the benchmark's weights, put in leaf by
    leaf as the trainer's own go: two whole sets do not fit beside the
    optimizer's moments."""
    state = built["trainer"].init_state(sample)
    old = flat(state.params)
    new = {}
    for index, leaf in enumerate(sorted(old)):
        new[leaf] = weights_lm.make_leaf(leaf, index, old[leaf].shape, seed,
                                         old[leaf].sharding)
        old[leaf].delete()
    return state.replace(params=unflat(new))


def setup(config: dict, traffic: dict, seed: int, env: dict) -> dict:
    generator = load_module(os.path.join(env["code"], "generators",
                                         traffic["generator"] + ".py"),
                            "benchmark_generator")
    enable_cache()
    t0 = time.perf_counter()
    built = build(config, traffic, env)  # first: a program without the model stops here
    pool = generator.make_pool(config, traffic, seed)
    t1 = time.perf_counter()
    state = fresh_state(built, seed, pool[0])
    t2 = time.perf_counter()
    n = int(traffic["check_steps"])
    state, params0, record = checked_steps(built, state, pool[:n])
    t3 = time.perf_counter()
    print(f"[setup] build+pool {t1 - t0:.1f}s  init {t2 - t1:.1f}s  "
          f"first {n} steps {t3 - t2:.1f}s  losses "
          + " ".join(f"{v:.4f}" for v in record["loss"]), flush=True)
    built.update(state=state, pool=pool, params0=params0, program=record,
                 seed=seed, checked=n)
    return built


def numbers_of(program: dict, reference: dict, config: dict) -> tuple[dict, dict]:
    """``compare.training_numbers`` and two numbers of the first gradient,
    each the norm of the difference against the reference's own norm.
    ``grad_diff_output``: the tied table, whose gradient is features^T x
    d loss / d logits plus the gather's, so it carries the whole forward pass
    and the loss.  ``grad_diff_scan``: every ``A_log`` (the decay rate a head)
    taken as one vector; their gradient comes through the scan's state alone,
    so a state carried where it should start anew shows there first, and
    pooled over the layers the 64-number leaves' noise evens out."""
    numbers, leaves = compare.training_numbers(program, reference)
    rates = [k for k in sorted(reference["grad"]) if k.endswith(config["scan_leaf"])]

    def pooled(record):
        return {"x": np.concatenate([np.ravel(record["grad"][k]) for k in rates])}

    table = config["output_leaf"]
    numbers["grad_diff_output"] = compare.leaf_numbers(
        program["grad"], reference["grad"], [table])[0][table][1]
    numbers["grad_diff_scan"] = compare.leaf_numbers(
        pooled(program), pooled(reference))[0]["x"][1]
    return numbers, leaves


def check(ctx: dict, win: dict) -> tuple[dict, dict]:
    """Numbers compared and their limits.  Runs once the window has closed
    and the peak has been read: the program's state goes first."""
    release(ctx)
    config, n = ctx["config"], ctx["checked"]
    t0 = time.perf_counter()
    reference = ctx["reference"].run_steps(ctx["params0"], ctx["pool"][:n])
    t1 = time.perf_counter()
    numbers, leaves = numbers_of(ctx["program"], reference, config)
    numbers["skipped_steps"] = float(win["failed"])
    numbers["final_loss_nonfinite"] = 0.0 if np.isfinite(
        ctx["final_loss"]) else 1.0
    print(f"[check] reference {t1 - t0:.1f}s {json.dumps(reference['seconds'])}  "
          f"numbers {time.perf_counter() - t1:.1f}s  losses {json.dumps(reference['loss'])}  numbers "
          f"{json.dumps(numbers)}  worst leaves {json.dumps(leaves)}",
          flush=True)
    return numbers, dict(config["limits"])
