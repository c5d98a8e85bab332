"""Set-up split by the program's own launch record.

``<workdir>/launch.jsonl`` is written by the trainer beside ``spans.jsonl``
after a profiled epoch (``deep_vision_tpu/obs/launch.py``): a header, one
line a stage of the process from its start to the file's writing (``name``,
``ordinal``, ``parent``, ``t0_ns``, ``t1_ns``; the stages whose parent is
``launch`` tile that span, ``caller`` names what ran between two of the
program's own), and one line a compile interval (``kind``: ``trace``,
``lower``, ``backend_compile`` or ``cache_retrieval``; ``fun``, ``cache``,
``parent``, ``batch``), all in ``time.time_ns`` terms.

The window is the profiled ``epoch`` call, the last one; set-up is all
before it.  A stage's time is its self time: its interval less the compile
intervals inside it.  Five times come out, and sum to the run's ``setup_s``
by construction:

    setup_outside_s   ``outside`` + ``import`` + ``backend``: process start
                      to the package's first call, and the runtime's start
                      where the program is the one that starts it
    setup_build_s     ``cache`` + ``build`` + ``init`` + ``restore``
    setup_compile_s   the union of every compile interval before the window
    setup_steps_s     the ``epoch`` calls before the window
    setup_caller_s    ``to_window_s`` less the four: the ``caller`` gaps

and one count, ``setup_cache_misses``: backend compiles before the window
that the persistent cache did not serve.  Without the file (a program that
writes none) every reader gives None.
"""

from __future__ import annotations

import json
import os

OUTSIDE = ("outside", "import", "backend")
BUILD = ("cache", "build", "init", "restore")
NS = 1e-9
TOP = 10


def read_launch(workdir: str) -> dict | None:
    """``launch.jsonl`` as ``{"header", "stages", "firsts", "compiles"}``."""
    path = os.path.join(workdir, "launch.jsonl")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    if not rows or "process_start_ns" not in rows[0]:
        return None
    out = {"header": rows[0], "stages": [], "firsts": [], "compiles": [],
           "bytes": os.path.getsize(path)}
    for r in rows[1:]:
        if "kind" in r:
            out["compiles"].append(r)
        elif r["parent"] == "launch":
            out["stages"].append(r)
        else:
            out["firsts"].append(r)
    return out


def union(intervals: list) -> list:
    """Sorted, disjoint ``(t0, t1)`` covering the same instants."""
    out: list = []
    for t0, t1 in sorted(intervals):
        if out and t0 <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t1)
        else:
            out.append([t0, t1])
    return out


def covered(disjoint: list, t0: int, t1: int) -> int:
    """Nanoseconds of ``[t0, t1]`` that the disjoint intervals cover."""
    return sum(max(0, min(b, t1) - max(a, t0)) for a, b in disjoint)


def split(record: dict, to_window_s: float) -> dict | None:
    """The six numbers and what the ``[launch]`` line shows beside them;
    None where the record holds no ``epoch`` call to take for the window."""
    stages = record["stages"]
    epochs = [s for s in stages if s["name"] == "epoch"]
    if not epochs:
        return None
    opens = epochs[-1]["t0_ns"]
    before = [s for s in stages if s["t1_ns"] <= opens]
    early = [c for c in record["compiles"] if c["t0_ns"] < opens]
    busy = union([(c["t0_ns"], min(c["t1_ns"], opens)) for c in early])

    total: dict = {}
    own: dict = {}
    for s in before:
        total[s["name"]] = total.get(s["name"], 0) + s["t1_ns"] - s["t0_ns"]
        own[s["name"]] = (own.get(s["name"], 0) + s["t1_ns"] - s["t0_ns"]
                          - covered(busy, s["t0_ns"], s["t1_ns"]))

    def self_s(names) -> float:
        return sum(own.get(n, 0) for n in names) * NS

    numbers = {
        "setup_outside_s": self_s(OUTSIDE),
        "setup_build_s": self_s(BUILD),
        "setup_compile_s": sum(b - a for a, b in busy) * NS,
        "setup_steps_s": self_s(("epoch",)),
    }
    numbers["setup_caller_s"] = to_window_s - sum(numbers.values())
    programs = [c for c in early if c["kind"] == "backend_compile"]
    numbers["setup_cache_misses"] = float(
        sum(1 for c in programs if c["cache"] != "hit"))

    by_parent: dict = {}
    for c in early:
        row = by_parent.setdefault(f"{c['parent']}/{c['kind']}", [0, 0.0])
        row[0] += 1
        row[1] += (c["t1_ns"] - c["t0_ns"]) * NS
    # a compile the program put under one stage and the clock under another
    edges = [s["t0_ns"] for s in stages] + [stages[-1]["t1_ns"]]
    over = [min(e - c["t0_ns"], c["t1_ns"] - e) for c in record["compiles"]
            for e in edges if c["t0_ns"] < e < c["t1_ns"]]
    firsts = {(f["name"], f["ordinal"]): (f["t1_ns"] - f["t0_ns"]) * NS
              for f in record["firsts"]}
    header = record["header"]
    return {
        "numbers": numbers,
        "to_window_s": to_window_s,
        # the record's own account of the same span: process start to the
        # window's epoch call, against the harness's perf_counter
        "process_start_to_window_s": (opens - header["process_start_ns"]) * NS,
        "stage_s": {k: v * NS for k, v in total.items()},
        "stage_self_s": {k: v * NS for k, v in own.items()},
        "epochs_before_window": len(epochs) - 1,
        "first_epochs_s": [
            [e["ordinal"], (e["t1_ns"] - e["t0_ns"]) * NS,
             firsts.get(("first_dispatch", e["ordinal"])),
             firsts.get(("first_fetch", e["ordinal"]))] for e in epochs[:4]],
        "compiles_n_s": {k: [n, s] for k, (n, s) in sorted(by_parent.items())},
        "longest_programs": [
            [c["fun"], (c["t1_ns"] - c["t0_ns"]) * NS, c["cache"], c["parent"]]
            for c in sorted(programs,
                            key=lambda c: c["t0_ns"] - c["t1_ns"])[:TOP]],
        "compiles_in_window": sum(1 for c in record["compiles"]
                                  if c["kind"] == "backend_compile"
                                  and c["t0_ns"] >= opens),
        "straddling": len(over),
        "straddling_worst_ms": max(over, default=0) / 1e6,
        "cache_hits": header["cache_hits"],
        "cache_misses": header["cache_misses"],
        "intervals": header["intervals"], "dropped": header["dropped"],
        "file_bytes": record["bytes"],
    }


def analyze(run: dict) -> dict | None:
    """Computed by the first reader that asks, kept on the harness's ``run``
    for the other five, and printed as the run's one ``[launch]`` line."""
    if "launch_analysis" not in run:
        record = read_launch(run["window"]["workdir"])
        out = split(record, run["to_window_s"]) if record else None
        if out is not None:
            print(f"[launch] {json.dumps(out)}", flush=True)
        run["launch_analysis"] = out
    return run["launch_analysis"]


def number(run: dict, key: str):
    out = analyze(run)
    return None if out is None else out["numbers"][key]
