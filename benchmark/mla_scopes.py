"""Device time of a traced run of a latent-attention decoder with routed
and shared experts and a multi-token-prediction module, by the decoder's own
scopes.

The model marks ``embed``, ``mla_op`` (inside it ``mla_core``: the two
attention kernels and the layout passes around them), ``dense_block``,
``routed_ffn`` (inside it ``shared_expert``, and ``ops/moe.py``'s
``moe_route`` and ``moe_experts``), ``lm_head`` and ``mtp`` (around the whole
module, whose own operator, routed block, embedding and head carry their
scopes inside it) with ``jax.named_scope``; the trainer marks ``loss`` and
``optimizer``.  An operation belongs to a scope where the scope's name is a
whole component of its ``op_name``, in the forward pass, in its
recomputation and in the backward pass alike; an operation the compiler
adds itself goes by its reader, else by its writer
(``lm_scopes.inherited_scopes``).  Per executed step, in ms.  ``mtp`` is an
overlay (its operator also counts under ``mla_op``); ``mtp_rest`` is what of
it lies under none of the model's other scopes (the two norms and
``W_eh``).  Nothing is returned for a trace without an operation under
``mla_op``: a program that has no such model, the parent of the PR that
added it among them.

The run's ``[mla_scopes]`` line gives every part, the disjoint parts' sum
(``accounted``) beside ``all`` (which is ``step_device_ms`` where no
operation nests), the traced steps' ``moe_assignments`` and ``moe_max_load``,
the pairs a step's rows leave visible, the attention core's roofline for
them and the step's share of the peak with the core's needed products
counted.  The pairs are ``input_pairs_per_step``, the mean over the window's
batches: the window cycles a pool of 8 and the traced steps 10-25 are two
whole turns of it, so the traced steps' mean is the pool's.
"""

from __future__ import annotations

import json

from benchmark import flops_mla, lm_scopes, moe_scopes, series
from benchmark import trace as trace_lib

MODEL = ("mla_op", "dense_block", "routed_ffn", "lm_head", "embed")
INSIDE = ("mla_core", "shared_expert", "moe_route", "moe_experts", "mtp")
STEP = ("loss", "optimizer")
DISJOINT = MODEL + ("mtp_rest",) + STEP + ("unscoped",)


def scope_ms(ops: list, executions: float) -> dict | None:
    """``ops``: ``(scope, start_ns, duration_ns, ...)`` of every chip."""
    total = dict.fromkeys(DISJOINT + INSIDE + ("all",), 0.0)
    for scope, _, d, *_ in ops:
        parts = scope.split(";")[0].split("/")
        total["all"] += d
        for name in INSIDE:
            if name in parts:
                total[name] += d
        for name in MODEL:
            if name in parts:
                total[name] += d
                break
        else:
            if "mtp" in parts:
                total["mtp_rest"] += d
            elif "jvp(loss)" in parts or "transpose(jvp(loss))" in parts:
                total["loss"] += d
            elif "optimizer" in parts:
                total["optimizer"] += d
            else:
                total["unscoped"] += d
    if not executions or not total["mla_op"]:
        return None
    return {k: v / executions / 1e6 for k, v in total.items()}


def traced_counter(run: dict, name: str) -> float | None:
    """``moe_scopes.traced_counter`` with the traced steps counted from the
    window's first: the set-up's ``settle_steps`` come before it."""
    traffic = run["traffic"]
    before = int(traffic["check_steps"]) + int(traffic.get("settle_steps", 0))
    return moe_scopes.traced_counter(
        {**run, "traffic": {**traffic, "check_steps": before}}, name)


def pairs_per_step(run: dict) -> float | None:
    """Visible (query, key) pairs a step, where the configuration is of a
    latent-attention model and the program counts them."""
    if not flops_mla.applies(run["config"]):
        return None
    return series.last(run["window"]["workdir"], "input_pairs_per_step")


def analyze(run: dict) -> dict | None:
    """Computed by the first reader that asks, kept on the harness's
    ``run`` for the others and printed as the run's ``[mla_scopes]`` line."""
    if "mla_scopes" not in run:
        out = None
        trace_dir = run["window"].get("trace_dir")
        path = trace_lib.find_xplane(trace_dir) if trace_dir else None
        trace = run.get("trace") or {}
        executions = trace.get("step_executions")
        if path and executions and flops_mla.applies(run["config"]):
            ops, chips = lm_scopes.traced_ops(path)
            out = scope_ms(ops, executions * max(chips, 1))
        if out is not None:
            config, peaks = run["config"], run["peaks"]
            out["accounted"] = sum(out[k] for k in DISJOINT)
            out["moe_assignments"] = traced_counter(run, "train_moe_assignments")
            out["moe_max_load"] = traced_counter(run, "train_moe_max_load")
            pairs = out["attn_pairs"] = pairs_per_step(run)
            out["mla_core_roofline_pct"] = out["mla_core_bound_by"] = None
            out["step_mfu_with_core_pct"] = None
            if pairs and out["mla_core"]:
                least, bound = flops_mla.core_roofline_seconds(config, pairs, peaks)
                out["mla_core_roofline_pct"] = 100.0 * least * 1e3 / out["mla_core"]
                out["mla_core_bound_by"] = bound
                work = executions * (
                    config["batch_size"] * config["train_flops_per_image"]
                    + flops_mla.core_train_flops(config, pairs))
                out["step_mfu_with_core_pct"] = 100.0 * work / (
                    trace["window_s"] * peaks["bf16_flops_per_s"] * run["chips"])
            print(f"[mla_scopes] {json.dumps(out)}", flush=True)
        run["mla_scopes"] = out
    return run["mla_scopes"]


def number(run: dict, key: str):
    found = analyze(run)
    return None if found is None else found[key]
