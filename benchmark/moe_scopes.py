"""Device time of a traced run of a decoder with routed experts, by the
decoder's own scopes.

The model marks ``embed``, ``conv_op``, ``gqa_op``, ``dense_ffn``, ``moe``
(inside it ``moe_route``: router, top-k, sort, gathers, the weighted sum;
and ``moe_experts``: the weights' cast, the grouped products and the SwiGLU
between them) and ``lm_head`` with ``jax.named_scope``; the trainer marks
``loss`` and ``optimizer``.  An operation belongs to a scope where the
scope's name is a whole component of its ``op_name``, in the forward pass,
in its recomputation and in the backward pass alike; an operation the
compiler adds itself goes by its reader, else by its writer
(``lm_scopes.inherited_scopes``).  Per executed step, in ms.  Nothing is
returned for a trace without an operation under ``moe``: a program that has
no such model, the parent of the PR that added it among them.

The run's ``[moe_scopes]`` line gives every part, their sum with the
unscoped rest (``all``, which is ``step_device_ms`` where no operation
nests), the assignments counted in the traced steps and the expert
products' roofline for them.
"""

from __future__ import annotations

import json
import os

from benchmark import flops_moe, lm_scopes
from benchmark import trace as trace_lib

MODEL = ("embed", "conv_op", "gqa_op", "dense_ffn", "moe", "lm_head")
INSIDE = ("moe_route", "moe_experts")
STEP = ("loss", "optimizer")


def scope_ms(ops: list, executions: float) -> dict | None:
    """``ops``: ``(scope, start_ns, duration_ns, ...)`` of every chip."""
    total = dict.fromkeys(MODEL + INSIDE + STEP + ("unscoped", "all"), 0.0)
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
            if "jvp(loss)" in parts or "transpose(jvp(loss))" in parts:
                total["loss"] += d
            elif "optimizer" in parts:
                total["optimizer"] += d
            else:
                total["unscoped"] += d
    if not executions or not total["moe"]:
        return None
    return {k: v / executions / 1e6 for k, v in total.items()}


def traced_counter(run: dict, name: str) -> float | None:
    """The mean of the step metric ``name`` (``train_moe_assignments``,
    ``train_moe_max_load``) over the steps logged inside the traced ones, so
    that every counter of a result line, and the roofline that divides the
    traced steps' time by their work, is of the same steps.  The last logged
    value where no logged step falls among the traced ones; nothing where
    the program logs no such metric."""
    path = os.path.join(run["window"]["workdir"], "metrics.jsonl")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        rows = [r for r in map(json.loads, f) if r.get("name") == name]
    if not rows:
        return None
    first, last = run["traffic"]["trace_steps"]
    done = int(run["traffic"]["check_steps"])
    inside = [float(r["value"]) for r in rows
              if done + first <= r["step"] <= done + last]
    return sum(inside) / len(inside) if inside else float(rows[-1]["value"])


def analyze(run: dict) -> dict | None:
    """Computed by the first reader that asks, kept on the harness's
    ``run`` for the others and printed as the run's ``[moe_scopes]`` line."""
    if "moe_scopes" not in run:
        out = None
        trace_dir = run["window"].get("trace_dir")
        path = trace_lib.find_xplane(trace_dir) if trace_dir else None
        executions = (run.get("trace") or {}).get("step_executions")
        if path and executions:
            ops, chips = lm_scopes.traced_ops(path)
            out = scope_ms(ops, executions * max(chips, 1))
        if out is not None:
            out["accounted"] = sum(out[k] for k in MODEL + STEP)
            assignments = traced_counter(run, "train_moe_assignments")
            out["moe_assignments"] = assignments
            out["moe_experts_roofline_pct"] = out["moe_experts_bound_by"] = None
            if assignments and out["moe_experts"]:
                least, bound = flops_moe.experts_roofline_seconds(
                    run["config"], assignments, run["peaks"])
                out["moe_experts_roofline_pct"] = (
                    100.0 * least * 1e3 / out["moe_experts"])
                out["moe_experts_bound_by"] = bound
            print(f"[moe_scopes] {json.dumps(out)}", flush=True)
        run["moe_scopes"] = out
    return run["moe_scopes"]


def number(run: dict, key: str):
    found = analyze(run)
    return None if found is None else found[key]
