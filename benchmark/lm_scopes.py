"""Device time of a traced language-model run by the decoder's own scopes.

The model marks ``embed``, ``mamba`` (``ssd`` inside it, around the scan
only), ``attention``, ``mlp`` and ``lm_head`` with ``jax.named_scope``; the
trainer marks ``loss`` and ``optimizer``.  An operation belongs to a scope
where the scope's name is a whole component of its ``op_name`` (a fusion
goes by the first operation XLA names it for), in the forward pass, in its
recomputation and in the backward pass alike.  Per executed step, in ms,
summed over the traced steps and divided by the executions the harness
counts.  Nothing is returned for a trace without an operation under
``mamba``, ``attention`` or ``mlp``: a program that has no such model.

An operation the compiler adds itself carries no scope: the two halves of an
asynchronous copy between memory spaces (``copy-start`` / ``copy-done``,
``slice-start`` / ``slice-done``), a layout ``copy``, a ``reshape``.  Its
name in the trace is its HLO text, operands included, so it goes by the
first scoped operation that reads its result (a copy is made for its
reader), else by the one that wrote its operand, and stays unscoped where
neither has a scope (a parameter prefetched for nothing that is named).
"""

from __future__ import annotations

import json
import re

from benchmark import flops_lm, spans
from benchmark import trace as trace_lib

MODEL = ("embed", "mamba", "attention", "mlp", "lm_head")
STEP = ("loss", "optimizer")
OPERAND = re.compile(r"%[\w.\-]+")
HOPS = 4  # start -> done -> a second copy -> its done -> the reader


def inherited_scopes(scopes: dict) -> dict:
    """``scopes``: an operation's HLO text (``%name = shape op(operands)``)
    -> its scope, "" where it has none.  The same with every "" filled in
    from the operation's readers, else from its operands' writers, as far as
    ``HOPS`` operations away."""
    text_of, readers = {}, {}
    for text in scopes:
        name, _, rest = text.partition(" = ")
        text_of[name] = text
        for operand in OPERAND.findall(rest):
            readers.setdefault(operand, []).append(text)

    def look(text, towards, hops):
        near = towards(text)
        found = next((scopes[t] for t in near if scopes[t]), "")
        if found or not hops:
            return found
        return next(filter(None, (look(t, towards, hops - 1) for t in near)), "")

    def after(text):
        return readers.get(text.partition(" = ")[0], [])

    def before(text):
        operands = OPERAND.findall(text.partition(" = ")[2])
        return [text_of[o] for o in operands if o in text_of]

    return {text: scope or look(text, after, HOPS) or look(text, before, HOPS)
            for text, scope in scopes.items()}


def traced_ops(path: str) -> tuple[list, int]:
    """``(scope, start_ns, duration_ns)`` of every operation of every chip,
    scopes inherited as above, and the number of chips."""
    from jax.profiler import ProfileData

    metadata = spans.event_metadata(path)
    ops, chips = [], 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(trace_lib.DEVICE_PREFIX):
            continue
        scopes = inherited_scopes(
            {text: row["scope"] for text, row in metadata.get(plane.name, {}).items()})
        events = [ev for line in plane.lines if line.name == trace_lib.OPS_LINE
                  for ev in line.events]
        ops += [(scopes.get(ev.name, ""), ev.start_ns, ev.duration_ns) for ev in events]
        chips += bool(events)
    return ops, chips


def scope_ms(ops: list, executions: float) -> dict | None:
    """``ops``: ``(scope, start_ns, duration_ns, ...)`` of every chip."""
    total = dict.fromkeys(MODEL + STEP + ("ssd", "unscoped", "all"), 0.0)
    for scope, _, d, *_ in ops:
        parts = scope.split(";")[0].split("/")
        total["all"] += d
        if "ssd" in parts:
            total["ssd"] += d
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
    if not executions or not (total["mamba"] or total["attention"] or total["mlp"]):
        return None
    return {k: v / executions / 1e6 for k, v in total.items()}


def analyze(run: dict) -> dict | None:
    """Computed by the first reader that asks, kept on the harness's
    ``run`` for the others and printed as the run's ``[lm_scopes]`` line."""
    if "lm_scopes" not in run:
        out = None
        trace_dir = run["window"].get("trace_dir")
        path = trace_lib.find_xplane(trace_dir) if trace_dir else None
        executions = (run.get("trace") or {}).get("step_executions")
        if path and executions:
            ops, chips = traced_ops(path)
            out = scope_ms(ops, executions * max(chips, 1))
        if out is not None:
            tokens = run["config"]["batch_size"] * run["config"]["sequence_length"]
            least, bound = flops_lm.scan_roofline_seconds(
                run["config"], tokens, run["peaks"])
            out["ssd_roofline_pct"] = (100.0 * least * 1e3 / out["ssd"]
                                       if out["ssd"] else None)
            out["ssd_bound_by"] = bound
            out["accounted"] = sum(out[k] for k in MODEL + STEP)
            print(f"[lm_scopes] {json.dumps(out)}", flush=True)
        run["lm_scopes"] = out
    return run["lm_scopes"]


def number(run: dict, key: str):
    found = analyze(run)
    return None if found is None else found[key]
