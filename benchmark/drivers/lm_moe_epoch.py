"""Driver for training cells of a language model with routed experts: as
``lm_epoch.py``, the window is ONE call of ``Trainer.train_epoch`` on the
harness's iterable and the first ``check_steps`` steps go through that same
call, one batch each.

What differs from ``lm_epoch.py`` is what a share of the experts needs.
The configuration file's ``num_experts`` is the number of experts **held**
on this chip (listed under ``reduced``); the router keeps the published
width (``published.num_experts``), which is what the zoo's architecture
holds and what the model is built with, together with the share
(``expert_first``, ``num_experts`` experts from there).  The weights are
``benchmark/weights_moe.py``'s, the selection biases (state beside the
parameters, moved by the forward pass and by no gradient) are drawn and
recorded with them, and the numbers compared add the router's and the
experts' gradients, the count of dropped assignments and what the
balancing did to the biases.

From ``train_epoch.py``, unchanged: the feed, the window, the release of the
program's state, and the record of the checked steps (to which the biases
are added).
"""

from __future__ import annotations

import json
import os
import resource
import shutil
import time

import jax
import numpy as np

from benchmark import compare, weights_moe
from benchmark.byname import load_module
from benchmark.drivers import train_epoch
from benchmark.drivers.train_epoch import (  # noqa: F401 — window is the driver's
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
        # the file's num_experts is the share; the router's width is published
        ours = config["published"][key] if key == "num_experts" else config[key]
        if key in config["reduced"]:
            arch[key] = ours
        elif ours != value:
            raise ValueError(f"{key}: the file says {ours!r}, the zoo "
                             f"{value!r}, and it is not listed under reduced")
    cfg.extra["expert_first"] = int(config["expert_first"])
    cfg.extra["expert_count"] = int(config["num_experts"])
    if cfg.extra["expert_bias_update_rate"] != config["expert_bias_update_rate"]:
        raise ValueError(f"expert_bias_update_rate: the file says "
                         f"{config['expert_bias_update_rate']!r}, the zoo "
                         f"{cfg.extra['expert_bias_update_rate']!r}")
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
    optimizer's moments.  The selection biases are state beside the
    parameters (``batch_stats``) and are drawn with them, each leaf keyed by
    its place among all of them."""
    state = built["trainer"].init_state(sample)
    old, biases = flat(state.params), flat(state.batch_stats)
    new = {}
    for index, leaf in enumerate(sorted({**old, **biases})):
        was = old[leaf] if leaf in old else biases[leaf]
        new[leaf] = weights_moe.make_leaf(leaf, index, was.shape, seed,
                                          was.sharding)
        was.delete()
    return state.replace(
        params=unflat({k: v for k, v in new.items() if k in old}),
        batch_stats=unflat({k: v for k, v in new.items() if k in biases}))


def checked_steps(built: dict, state, batches: list):
    """``train_epoch.checked_steps`` with the selection biases among the
    leaves: they are state beside the parameters, their gradient is zero
    and their change is what the forward passes' balancing gave them."""
    biases0 = jax.device_get(flat(state.batch_stats))
    state, params0, record = train_epoch.checked_steps(built, state, batches)
    for leaf, after in jax.device_get(flat(state.batch_stats)).items():
        params0[leaf] = biases0[leaf]
        record["grad"][leaf] = np.zeros_like(after)
        record["delta"][leaf] = after - biases0[leaf]
    return state, params0, record


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
    record.update(logged_counters(built["trainer"].workdir))
    t3 = time.perf_counter()
    print(f"[setup] build+pool {t1 - t0:.1f}s  init {t2 - t1:.1f}s  "
          f"first {n} steps {t3 - t2:.1f}s  losses "
          + " ".join(f"{v:.4f}" for v in record["loss"]), flush=True)
    built.update(state=state, pool=pool, params0=params0, program=record,
                 seed=seed, checked=n)
    return built


def logged_rows(workdir: str) -> list:
    path = os.path.join(workdir, "metrics.jsonl")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f]


def logged_counters(workdir: str, since: int = 0) -> dict:
    """Of the program's own routing counters in ``metrics.jsonl`` from row
    ``since`` on: the largest ``moe_dropped`` and the first step's
    ``moe_bias_lift`` (the first checked step's, where both sides stand on
    the same weights)."""
    rows = logged_rows(workdir)[since:]

    def values(name):
        return [float(r["value"]) for r in rows if r.get("name") == name]

    return {"moe_dropped": max(values("train_moe_dropped"), default=None),
            "moe_bias_lift": next(iter(values("train_moe_bias_lift")), None)}


def numbers_of(program: dict, reference: dict, config: dict) -> tuple[dict, dict]:
    """``compare.training_numbers`` and three numbers of the first gradient,
    each the norm of the difference against the reference's own norm, and
    the count of dropped assignments.  ``grad_diff_output``: the tied table,
    whose gradient is features^T x d loss / d logits plus the gather's, so
    it carries the whole forward pass and the loss.  ``grad_diff_router``:
    every layer's router taken as one vector; the loss reaches it through
    the chosen experts' weights alone, so weights taken from the wrong
    scores or normalised over the wrong sum show there first.
    ``grad_diff_experts``: every held expert's kernels taken as one vector;
    a row sent to the wrong expert, left out or weighed wrongly shows
    there.  ``moe_dropped``: assignments on held experts that were not
    computed, as the side counts them itself (the program in its step's
    metrics: the largest it logged).  ``moe_bias_lift_gap``: the gap between
    the two sides' ``moe_bias_lift`` of the first step (the chosen experts'
    selection biases averaged with the weights their token gives them, over
    all tokens and expert layers) against the root mean square of the
    biases.  A token whose fourth and fifth expert change places under the
    program's rounding moves the gradients wholesale and this mean by next
    to nothing; weights that take the bias in move it on every token.
    ``moe_bias_gap``: the share of the selection biases, every expert of
    every layer, whose change over the checked steps lies further than half
    an ``expert_bias_update_rate`` from the reference's: a side that does not
    balance, or balances the other way, differs on every one, a flipped
    token only where a load stands within a few rows of the mean."""
    numbers, leaves = compare.training_numbers(program, reference)

    def pooled(ending):
        """Norm of the difference over the reference's norm with every leaf
        whose name has ``ending`` taken as one vector, summed leaf by leaf:
        joined, the experts' 604 M numbers in float64 are 4.8 GB a side and
        as much again for their difference."""
        names = [k for k in sorted(reference["grad"]) if ending in "/" + k]
        gap = norm = 0.0
        for k in names:
            ref = np.asarray(reference["grad"][k], np.float64)
            gap += float(np.sum(np.square(np.asarray(program["grad"][k],
                                                     np.float64) - ref)))
            norm += float(np.sum(np.square(ref)))
        return float(np.sqrt(gap / max(norm, 1e-60)))

    table = config["output_leaf"]
    numbers["grad_diff_output"] = compare.leaf_numbers(
        program["grad"], reference["grad"], [table])[0][table][1]
    numbers["grad_diff_router"] = pooled(config["router_leaf"])
    numbers["grad_diff_experts"] = pooled(config["expert_leaves"])
    dropped, lift = program.get("moe_dropped"), program.get("moe_bias_lift")
    numbers["moe_dropped"] = float("inf") if dropped is None else float(dropped)
    numbers["moe_bias_lift_gap"] = float("inf") if lift is None else abs(
        lift - reference["moe_bias_lift"]) / max(reference["moe_bias_rms"], 1e-30)
    moved = [np.abs(program["delta"][k] - reference["delta"][k])
             > 0.5 * config["expert_bias_update_rate"]
             for k in sorted(reference["delta"]) if k.endswith("expert_bias")]
    numbers["moe_bias_gap"] = float(np.mean(np.concatenate(moved)))
    return numbers, leaves


def check(ctx: dict, win: dict) -> tuple[dict, dict]:
    """Numbers compared and their limits.  Runs once the window has closed
    and the peak has been read: the program's state goes first.  The count
    of dropped assignments is the largest over the checked steps and the
    window's logged steps."""
    ctx["program"]["moe_dropped"] = logged_counters(
        ctx["trainer"].workdir)["moe_dropped"]
    release(ctx)
    config, n = ctx["config"], ctx["checked"]
    print(f"[check] memory as the reference starts "
          f"{json.dumps(jax.devices()[0].memory_stats())}", flush=True)
    t0 = time.perf_counter()
    reference = ctx["reference"].run_steps(ctx.pop("params0"), ctx["pool"][:n])
    t1 = time.perf_counter()
    numbers, leaves = numbers_of(ctx["program"], reference, config)
    numbers["skipped_steps"] = float(win["failed"])
    numbers["final_loss_nonfinite"] = 0.0 if np.isfinite(
        ctx["final_loss"]) else 1.0
    print(f"[check] host peak {resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20:.1f} GiB  "
          f"reference {t1 - t0:.1f}s {json.dumps(reference['seconds'])}  "
          f"numbers {time.perf_counter() - t1:.1f}s  losses {json.dumps(reference['loss'])}  "
          f"reference counters {json.dumps({k: reference[k] for k in reference if k.startswith('moe_')})}  "
          f"numbers {json.dumps(numbers)}  worst leaves {json.dumps(leaves)}",
          flush=True)
    return numbers, dict(config["limits"])
