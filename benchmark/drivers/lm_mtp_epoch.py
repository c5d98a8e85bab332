"""Driver for training cells of a latent-attention decoder with routed and
shared experts and a multi-token-prediction module: as ``lm_moe_epoch.py``
(from which the state, the checked steps, the window and the routing numbers
come unchanged), the window is ONE call of ``Trainer.train_epoch`` on the
harness's iterable and the first ``check_steps`` steps go through that same
call, one batch each.

What differs is the configuration's names and the second loss.  The file's
``n_routed_experts`` is the number of experts **held** on this chip (listed
under ``reduced``); the router keeps the published width
(``published.n_routed_experts``), which is what the zoo's architecture holds
and what the model is built with, together with the share
(``expert_first``, ``n_routed_experts`` experts from there).  The task is
built with the file's ``mtp_loss_weight``, which has to be the zoo's.  The
numbers compared add three to the routed driver's: the prediction module's
gradients, the latent projections' gradients, and the count of positions
that carry a second target.

After the checked steps, set-up drives ``settle_steps`` more steps of the pool
through the same call (a traffic parameter; a whole number of turns of the
pool, so the window starts on the batch it would have started on).  From a
random start the normed stream of this model is mostly what all tokens of a
document share (the first operator averages values over a prefix while the
embeddings are still 0.02 wide), every token ranks the experts alike, and
for some eighty steps an expert's load is all of a row's tokens or none
(PERF.md s6, PR 35, has the series); a window opened on the fourth step
would measure that start and not the regime a run spends its time in.
"""

from __future__ import annotations

import json
import os
import resource
import shutil
import time

import jax
import numpy as np

from benchmark.byname import load_module
from benchmark.drivers import lm_moe_epoch
from benchmark.drivers.lm_moe_epoch import (  # noqa: F401 — the driver's own
    checked_steps,
    enable_cache,
    fresh_state,
    logged_rows,
    release,
    window,
)

LATENT = ("/q_a/kernel", "/q_b/kernel", "/kv_a/kernel", "/kv_b/kernel")
MODULE = "mtp/"


def build(config: dict, traffic: dict, env: dict) -> dict:
    """The seed-independent part: config, model, task, Trainer."""
    from deep_vision_tpu.core.config import get_config
    from deep_vision_tpu.core.trainer import Trainer
    from deep_vision_tpu.parallel import make_mesh
    from deep_vision_tpu.tasks.language_modeling import LanguageModelingTask

    cfg = get_config(config["zoo_config"])
    arch = cfg.extra["architecture"]
    for key, value in arch.items():
        # the file's n_routed_experts is the share; the router's width is published
        ours = config["published"][key] if key == "n_routed_experts" else config[key]
        if key in config["reduced"]:
            arch[key] = ours
        elif ours != value:
            raise ValueError(f"{key}: the file says {ours!r}, the zoo "
                             f"{value!r}, and it is not listed under reduced")
    cfg.extra["expert_first"] = int(config["expert_first"])
    cfg.extra["expert_count"] = int(config["n_routed_experts"])
    for key in ("expert_bias_update_rate", "mtp_loss_weight"):
        if cfg.extra[key] != config[key]:
            raise ValueError(f"{key}: the file says {config[key]!r}, the zoo "
                             f"{cfg.extra[key]!r}")
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
    trainer = Trainer(cfg, cfg.model(),
                      LanguageModelingTask(config["mtp_loss_weight"]), mesh=mesh,
                      workdir=workdir)
    reference = load_module(os.path.join(env["config_dir"], config["name"] + ".py"),
                            "benchmark_reference").Reference(config)
    return {"trainer": trainer, "reference": reference, "config": config,
            "traffic": traffic}


def logged_counters(workdir: str, since: int = 0) -> dict:
    """``lm_moe_epoch.logged_counters`` and the first logged step's
    ``mtp_targets`` from row ``since`` on."""
    targets = [float(r["value"]) for r in logged_rows(workdir)[since:]
               if r.get("name") == "train_mtp_targets"]
    return {**lm_moe_epoch.logged_counters(workdir, since),
            "mtp_targets": next(iter(targets), None)}


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
    settle = int(traffic.get("settle_steps", 0))
    if settle:
        trainer = built["trainer"]
        state = trainer.train_epoch(
            state, (pool[(n + i) % len(pool)] for i in range(settle)),
            trainer.start_epoch)
    print(f"[setup] build+pool {t1 - t0:.1f}s  init {t2 - t1:.1f}s  "
          f"first {n} steps {t3 - t2:.1f}s  {settle} more "
          f"{time.perf_counter() - t3:.1f}s  losses "
          + " ".join(f"{v:.4f}" for v in record["loss"]), flush=True)
    built.update(state=state, pool=pool, params0=params0, program=record,
                 seed=seed, checked=n)
    return built


def pooled(program: dict, reference: dict, chosen) -> float:
    """Norm of the difference over the reference's norm with every leaf
    ``chosen`` says yes to taken as one vector, summed leaf by leaf."""
    gap = norm = 0.0
    for k in sorted(reference["grad"]):
        if not chosen(k):
            continue
        ref = np.asarray(reference["grad"][k], np.float64)
        gap += float(np.sum(np.square(np.asarray(program["grad"][k],
                                                 np.float64) - ref)))
        norm += float(np.sum(np.square(ref)))
    return float(np.sqrt(gap / max(norm, 1e-60)))


def numbers_of(program: dict, reference: dict, config: dict) -> tuple[dict, dict]:
    """``lm_moe_epoch.numbers_of`` (``grad_diff_output`` on the untied head's
    kernel, whose gradient is features^T x d loss / d logits of both heads)
    and three numbers more.  ``grad_diff_mtp``: every leaf of the prediction
    module taken as one vector; the second loss alone reaches them, so its
    weight, its targets and the module's input (which token is embedded, in
    which order the two halves are joined) show there.  ``grad_diff_latent``:
    every ``q_a``, ``q_b``, ``kv_a`` and ``kv_b`` kernel as one vector; what is
    rotated, which key a head gets and whether the latent is normed show
    there before anywhere else.  ``mtp_targets_gap``: |program - reference|
    of the positions that carry a second target at the first step, which the
    program derives from the first loss's weights and the reference from
    the segment ids."""
    numbers, leaves = lm_moe_epoch.numbers_of(program, reference, config)
    numbers["grad_diff_mtp"] = pooled(program, reference,
                                      lambda k: k.startswith(MODULE))
    numbers["grad_diff_latent"] = pooled(
        program, reference, lambda k: ("/" + k).endswith(LATENT))
    targets = program.get("mtp_targets")
    numbers["mtp_targets_gap"] = float("inf") if targets is None else abs(
        targets - reference["mtp_targets"])
    return numbers, leaves


def check(ctx: dict, win: dict) -> tuple[dict, dict]:
    """Numbers compared and their limits, as ``lm_moe_epoch.check`` makes
    them: the program's state goes first, the count of dropped assignments is
    the largest over the checked steps and the window's logged steps."""
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
    counted = {k: reference[k] for k in reference if k.startswith(("moe_", "mtp_"))}
    print(f"[check] host peak {resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20:.1f} GiB  "
          f"reference {t1 - t0:.1f}s {json.dumps(reference['seconds'])}  "
          f"numbers {time.perf_counter() - t1:.1f}s  losses {json.dumps(reference['loss'])}  "
          f"reference counters {json.dumps(counted)}  "
          f"numbers {json.dumps(numbers)}  worst leaves {json.dumps(leaves)}",
          flush=True)
    return numbers, dict(config["limits"])
