"""The program's own spans on a device trace's clock, and the device's
operations put down to the phases of the train step.

Two files of a traced run are read.  ``<workdir>/spans.jsonl`` is the
trainer's: a header with two ``(time.monotonic_ns, time.time_ns)`` pairs,
then one line per interval of the prefetcher's producer thread
(``prep_wait / assemble / h2d / enqueue``) and of the train loop
(``stall / dispatch / fetch / log / step``, ``profile`` around the trace's
start and stop), in ``time_ns`` terms.  The ``.xplane.pb`` dates itself: its
plane ``Task Environment`` carries ``profile_start_time`` in ns since the
epoch and every ``start_ns`` in the file counts from there, so an interval's
place on the trace's clock is its ``t_ns - profile_start_time``.

The clock is checked, not assumed.  A logged step's ``fetch`` returns once
the execution of the step before it has ended, which numbers the executions
of the step's module in the trace; no execution may then start before the
``dispatch`` interval of its own number began, and none may be under way as
a ``log`` ends (the logger reads ``state.step``, which drains the device).
Where that fails, where the plane is absent or where the spans do not cover
the traced span, the span metrics read nothing: a missing number, not a
wrong one.

A device operation's scope is the ``op_name`` of its HLO metadata
(``jit(train_step)/transpose(jvp(forward))/ResNet/.../conv_general_dilated``),
which ``jax.named_scope`` and Flax's module names write and the trace keeps
as the ``tf_op`` stat of the event's metadata, beside the compiler's
``flops`` and ``bytes_accessed`` for the operation.
"""

from __future__ import annotations

import bisect
import json
import os
import re

from benchmark import trace as trace_lib

PHASES = ("prologue", "forward", "loss", "backward", "optimizer")
TASK_PLANE = "Task Environment"
INPUT_STAGES = ("prep_wait", "assemble", "h2d")  # not enqueue: a full queue
KIND = re.compile(r"_\d+$")  # BottleneckBlock_3 is a BottleneckBlock
TOP = 10


def phase_of(scope: str) -> str | None:
    """The step's phase a scope path belongs to, by its whole components
    (a module that happens to be called ``forward`` is none of them)."""
    parts = scope.split("/")
    if "prologue" in parts:
        return "prologue"
    if any(p.startswith("transpose(") for p in parts):
        return "backward"
    if "jvp(loss)" in parts:
        return "loss"
    if "jvp(forward)" in parts:
        return "forward"
    if "optimizer" in parts:
        return "optimizer"
    return None


def module_of(scope: str) -> str:
    """The Flax module path inside a scope: the components between the
    transforms and the primitive (``ResNet/BottleneckBlock_3/BatchNorm_0``)."""
    return "/".join(p for p in scope.split("/")[:-1] if "(" not in p)


# ------------------------------------------------------------- the two files

def read_spans(workdir: str) -> dict | None:
    """``spans.jsonl`` as ``{"header", "producer", "consumer"}``, each
    thread's intervals as ``(stage, batch, t0_ns, t1_ns)`` in file order."""
    path = os.path.join(workdir, "spans.jsonl")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    if not rows or "clock" not in rows[0]:
        return None
    out = {"header": rows[0], "producer": [], "consumer": []}
    for r in rows[1:]:
        out[r["thread"]].append((r["stage"], r["batch"], r["t0_ns"], r["t1_ns"]))
    return out


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, the bytes for the rest; nothing is decoded further."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, value


def event_metadata(path: str) -> dict:
    """``{plane: {event name: {"scope", "flops", "bytes"}}}`` for the device
    planes of an ``.xplane.pb``.  ``jax.profiler.ProfileData`` hands over an
    event's own stats; the scope (``tf_op``: the HLO ``op_name`` and a
    colon) and the compiler's counts sit on the event's metadata, which it
    does not, so the file's ``event_metadata`` maps are read off the wire
    (tsl/profiler/protobuf/xplane.proto: XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4, .stat_metadata = 5; XEventMetadata.name = 2,
    .stats = 5; XStat.metadata_id = 1, .uint64 = 3, .int64 = 4, .str = 5,
    .ref = 7; a map entry's value = 2).  Lines are skipped, not parsed."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for field, value in _fields(plane):
            if field == 2:
                name = bytes(value).decode()
            elif field == 4:
                events.append(value)
            elif field == 5:
                entry = dict(_fields(dict(_fields(value)).get(2, b"")))
                stat_names[entry.get(1, 0)] = bytes(entry.get(2, b"")).decode()
        if not name.startswith(trace_lib.DEVICE_PREFIX):
            continue
        wanted = {k: v for k, v in stat_names.items()
                  if v in ("tf_op", "flops", "bytes_accessed")}
        table = out[name] = {}
        for entry in events:
            row = {"scope": "", "flops": 0, "bytes": 0}
            event_name = ""
            for field, value in _fields(dict(_fields(entry)).get(2, b"")):
                if field == 2:
                    event_name = bytes(value).decode()
                elif field == 5:
                    stat = dict(_fields(value))
                    key = wanted.get(stat.get(1))
                    if key == "tf_op":
                        text = (stat_names.get(stat[7], "") if 7 in stat
                                else bytes(stat.get(5, b"")).decode())
                        row["scope"] = text.rsplit(":", 1)[0]
                    elif key:
                        row["flops" if key == "flops" else "bytes"] = int(
                            stat.get(3, stat.get(4, 0)))
            table[event_name] = row
    return out


def read_xplane(path: str) -> dict:
    """``{"start_ns": profile_start_time or None, "chips": {plane:
    {"ops": [(scope, start_ns, duration_ns, flops, bytes)], "modules":
    [(name, start_ns, duration_ns)]}}}``; a scope is "" where the
    operation's metadata names none."""
    from jax.profiler import ProfileData

    metadata = event_metadata(path)
    nothing = {"scope": "", "flops": 0, "bytes": 0}
    out = {"start_ns": None, "chips": {}}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == TASK_PLANE:
            stats = dict(plane.stats)
            if "profile_start_time" in stats:
                out["start_ns"] = int(stats["profile_start_time"])
        if not plane.name.startswith(trace_lib.DEVICE_PREFIX):
            continue
        table = metadata.get(plane.name, {})
        chip = {"ops": [], "modules": []}
        for line in plane.lines:
            if line.name == trace_lib.MODULES_LINE:
                chip["modules"] = [(ev.name, ev.start_ns, ev.duration_ns)
                                   for ev in line.events]
            elif line.name == trace_lib.OPS_LINE:
                for ev in line.events:
                    row = table.get(ev.name, nothing)
                    chip["ops"].append((row["scope"], ev.start_ns, ev.duration_ns,
                                        row["flops"], row["bytes"]))
        if chip["ops"]:
            out["chips"][plane.name] = chip
    return out


# ------------------------------------------------------ the device's phases

def device_phases(chips: dict, executions: float) -> dict | None:
    """Per executed step: device ms, and the compiler's GB and TFLOP, by
    phase, by module path and by phase and kind of module (a fusion goes by
    the operation XLA names it for, a BatchNorm fused into a conv by the
    conv); ms under ``best_iou``.  None where no operation lies under
    ``jvp(forward)``: a program without the trainer's scopes, whose backward
    pass autodiff names all the same (``transpose(jvp(ResNet))``)."""
    by_phase = {k: [0.0, 0, 0] for k in PHASES + ("unscoped",)}
    by_module: dict = {}
    by_kind: dict = {}
    best_iou = 0.0
    for chip in chips.values():
        for scope, _, d, flops, nbytes in chip["ops"]:
            # a fusion lists the scopes of what it fused: the first names it
            scope = scope.split(";")[0]
            phase = phase_of(scope)
            rows = [by_phase[phase or "unscoped"]]
            if phase:
                module = module_of(scope) or f"({phase})"
                kind = KIND.sub("", module.rsplit("/", 1)[-1])
                rows.append(by_module.setdefault(module, [0.0, 0, 0]))
                rows.append(by_kind.setdefault(f"{phase}/{kind}", [0.0, 0, 0]))
            for row in rows:
                row[0] += d
                row[1] += nbytes
                row[2] += flops
            if "best_iou" in scope.split("/"):
                best_iou += d
    if not executions or not by_phase["forward"][0]:
        return None
    total = sum(row[0] for row in by_phase.values())
    steps = len(chips) * executions  # sums over chips -> one chip's step

    def per_step(row):
        return [row[0] / steps / 1e6, row[1] / steps / 1e9, row[2] / steps / 1e12]

    def heaviest(table):
        return [[k] + per_step(row) for k, row in sorted(
            table.items(), key=lambda kv: -kv[1][0])[:TOP]]

    return {
        "phase_ms": {k: per_step(row)[0] for k, row in by_phase.items()},
        "phase_gb_tflop": {k: per_step(row)[1:] for k, row in by_phase.items()},
        "unscoped_pct": 100.0 * by_phase["unscoped"][0] / total,
        "best_iou_ms": best_iou / steps / 1e6 if best_iou else None,
        "modules_ms_gb_tflop": heaviest(by_module),
        "kinds_ms_gb_tflop": heaviest(by_kind),
    }


# ------------------------------------------------- the spans on the trace

def check_clock(executions: list, consumer: list) -> dict | None:
    """Number one chip's executions of the step (``(start, end)``, sorted)
    by the loop's batches and hold each to its dispatch.

    A ``fetch`` of batch i returns once the execution of batch i - 1 has
    ended, so the latest execution that ended before it is number i - 1;
    every ``fetch`` inside the trace has to agree.  None where none is
    there to number by, where two disagree, where an execution starts
    before the ``dispatch`` interval of its number began, or where one is
    under way as a ``log`` ends: the logger reads ``state.step``, which
    waits for all that was dispatched (without this a clock off by a whole
    step's period passes: the numbering moves with it)."""
    ends = [e for _, e in executions]
    starts = [s for s, _ in executions]
    first = None
    fetch_lag = []
    for stage, batch, _, t1 in consumer:
        if stage != "fetch" or not ends[0] <= t1 <= ends[-1]:
            continue
        j = bisect.bisect_right(ends, t1) - 1
        if first is None:
            first = batch - 1 - j
        elif first != batch - 1 - j:
            return None
        fetch_lag.append(t1 - ends[j])
    if first is None:
        return None
    for stage, _, _, t1 in consumer:
        if stage == "log":
            j = bisect.bisect_right(starts, t1) - 1
            if j >= 0 and t1 < ends[j]:
                return None
    dispatched = {batch: t0 for stage, batch, t0, _ in consumer
                  if stage == "dispatch"}
    lead = []
    for j, (start, _) in enumerate(executions):
        t0 = dispatched.get(first + j)
        if t0 is None or start < t0:
            return None
        lead.append(start - t0)
    # the first execution shown is cut by the trace's start: not a lead
    return {"first_batch": first, "dispatch_lead_min_us": min(lead[1:] or lead) / 1e3,
            "fetch_lag_min_us": min(fetch_lag) / 1e3,
            "fetch_lag_max_us": max(fetch_lag) / 1e3}


def idle_by_stage(chips: dict, consumer: list) -> tuple[dict, float] | None:
    """Seconds in which no operation ran, by the consumer stage they fall
    in, averaged over chips, and the traced span's seconds (first operation
    to last over all chips, as ``trace.reduce_planes`` takes it).  None
    where the consumer's intervals do not cover the span."""
    t0 = min(op[1] for c in chips.values() for op in c["ops"])
    t1 = max(op[1] + op[2] for c in chips.values() for op in c["ops"])
    if not consumer or consumer[0][2] > t0 or consumer[-1][3] < t1:
        return None
    starts = [iv[2] for iv in consumer]
    idle: dict = {}
    for chip in chips.values():
        busy = trace_lib.union([(op[1], op[1] + op[2]) for op in chip["ops"]])
        # the complement of busy inside the span: t0, s1, e1, ..., sn, en, t1
        bounds = [t0] + [t for interval in busy for t in interval] + [t1]
        for g0, g1 in zip(bounds[0::2], bounds[1::2]):
            i = max(bisect.bisect_right(starts, g0) - 1, 0)
            while i < len(consumer) and consumer[i][2] < g1:
                stage, _, a, b = consumer[i]
                over = min(g1, b) - max(g0, a)
                if over > 0:
                    idle[stage] = idle.get(stage, 0.0) + over
                i += 1
    n = len(chips)
    return {k: v / n * trace_lib.NS for k, v in idle.items()}, (t1 - t0) * trace_lib.NS


def on_trace_clock(intervals: list, start_ns: int) -> list:
    return [(stage, batch, a - start_ns, b - start_ns)
            for stage, batch, a, b in intervals]


def span_numbers(spans: dict, xplane: dict, step_module: str) -> dict | None:
    """Idle shares by what the loop was doing, and what a batch costs the
    producer; None where the clock cannot be placed or fails its check."""
    if xplane["start_ns"] is None:
        return None
    consumer = on_trace_clock(spans["consumer"], xplane["start_ns"])
    clocks = []
    for chip in xplane["chips"].values():
        executions = sorted((s, s + d) for name, s, d in chip["modules"]
                            if name.split("(")[0] == step_module)
        checked = check_clock(executions, consumer) if executions else None
        if checked is None:
            return None
        clocks.append(checked)
    found = idle_by_stage(xplane["chips"], consumer)
    if found is None:
        return None
    idle_s, span_s = found
    pairs = spans["header"]["clock"]
    batches = max(1, sum(1 for iv in spans["producer"] if iv[0] == "h2d"))
    producer_ns = sum(b - a for stage, _, a, b in spans["producer"]
                      if stage in INPUT_STAGES)
    return {
        "clock": {
            "profile_start_time_ns": xplane["start_ns"],
            # wall minus monotonic as the trace stopped, less as it started
            "drift_us": ((pairs[-1][1] - pairs[-1][0])
                         - (pairs[0][1] - pairs[0][0])) / 1e3,
            "dispatch_lead_min_us": min(c["dispatch_lead_min_us"] for c in clocks),
            "fetch_lag_min_us": min(c["fetch_lag_min_us"] for c in clocks),
            "fetch_lag_max_us": max(c["fetch_lag_max_us"] for c in clocks),
            "first_batch": clocks[0]["first_batch"],
        },
        "idle_s": idle_s,
        "span_s": span_s,
        "idle_input_pct": 100.0 * idle_s.get("stall", 0.0) / span_s,
        "idle_loop_pct": 100.0 * sum(
            v for k, v in idle_s.items() if k != "stall") / span_s,
        "input_batch_ms": producer_ns / batches / 1e6,
    }


# ------------------------------------------------------------ for a reader

def analyze(run: dict) -> dict:
    """Both halves for a traced run (either may be None).  Computed by the
    first reader that asks, kept on the harness's ``run`` for the other
    nine, and printed as the run's one ``[spans]`` line."""
    if "spans_analysis" not in run:
        out = {"device": None, "spans": None}
        trace_dir = run["window"].get("trace_dir")
        path = trace_lib.find_xplane(trace_dir) if trace_dir else None
        if path and run.get("trace"):
            xplane = read_xplane(path)
            spans = read_spans(run["window"]["workdir"])
            if xplane["chips"]:
                out["device"] = device_phases(
                    xplane["chips"], run["trace"].get("step_executions"))
                if spans is not None:
                    out["spans"] = span_numbers(
                        spans, xplane, run["window"].get("step_module"))
            print(f"[spans] {json.dumps(out)}", flush=True)
        run["spans_analysis"] = out
    return run["spans_analysis"]


def device_number(run: dict, key: str, phase: str | None = None):
    device = analyze(run)["device"]
    if device is None:
        return None
    return device[key][phase] if phase else device[key]


def span_number(run: dict, key: str):
    spans = analyze(run)["spans"]
    return None if spans is None else spans[key]
