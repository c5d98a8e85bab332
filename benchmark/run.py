"""The benchmark's command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, the only one that touches JAX.  A cell is resolved by name and
by nothing else: its entry in ``BENCHMARK.json`` names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<traffic>.json``),
the mix names its driver (``drivers/<driver>.py``: ``setup``, ``window``,
``check``), and every metric has a reader of its own
(``metrics/<name>.py``: ``read(run)`` gives the number, or None where it
finds nothing to read).  Adding a cell, a driver or a metric adds files and
entries; nothing here names one.

The last line of standard output is the result; the last lines of standard
error are the numbers compared, each beside its limit.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)


from benchmark.byname import load_json, load_module  # noqa: E402


def find_cell(bench: dict, workload: str) -> dict:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r}; have {sorted(cells)}")
    return cells[workload]


def resolve(bench: dict, workload: str) -> dict:
    """Everything a cell is made of, found by name.  A mix lies in the
    ``traffic`` directory beside its configuration's ``configs``."""
    cell = find_cell(bench, workload)
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config_path = os.path.join(CHECKOUT, entry["file"])
    config = load_json(config_path)
    data = os.path.dirname(os.path.dirname(config_path))
    traffic = load_json(os.path.join(data, "traffic", cell["traffic"] + ".json"))
    driver = load_module(
        os.path.join(HERE, "drivers", traffic["driver"] + ".py"),
        "benchmark_driver")
    return {"cell": cell, "config": config, "traffic": traffic,
            "driver": driver, "config_dir": os.path.dirname(config_path)}


def probe_device(chips: int) -> dict:
    """The chips this run stands on, or SystemExit: no fallback."""
    import jax

    from benchmark import peaks

    devices = jax.devices()
    first = devices[0]
    try:
        row = peaks.lookup(first.platform, first.device_kind)
    except LookupError as e:
        raise SystemExit(str(e)) from e
    if len(devices) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX finds "
                         f"{len(devices)}")
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(devices), "peaks": row, "devices": devices[:chips]}


def memory_peak(devices) -> int:
    """Peak on the fullest chip: the peak of the live buffers plus the peak
    of what the runtime reserved for its programs' scratch (two peaks that
    need not fall at the same moment: an upper bound).  On this runtime
    ``peak_bytes_in_use`` counts buffers only (0.65 GiB beside a step whose
    compiler-counted footprint is 9 GiB); the programs' arenas show under
    ``peak_bytes_reserved`` and come off the free memory just the same."""
    stats = [d.memory_stats() for d in devices]
    print(f"[memory] {json.dumps(stats[0])}", flush=True)
    return max(int(s["peak_bytes_in_use"]) + int(s.get("peak_bytes_reserved", 0))
               for s in stats)


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, device: dict, read_peak=memory_peak) -> tuple[dict, list]:
    """Set-up, window, peak, comparison, metrics.  Returns the result line's
    object and the rows (name, value, limit) of the numbers compared."""
    from benchmark import compare
    from benchmark import trace as trace_lib
    from benchmark.compilelog import CompileLog

    parts = resolve(bench, workload)
    driver, cell = parts["driver"], parts["cell"]
    log = CompileLog()
    env = {"code": HERE, "config_dir": parts["config_dir"],
           "chips": int(cell["chips"]),
           "workdir": os.path.join(CHECKOUT, ".bench_work", workload)}
    ctx = driver.setup(parts["config"], parts["traffic"], seed, env)
    print(f"[compile] set-up {json.dumps(log.since((0, 0, 0)))}", flush=True)
    mark = log.mark()
    win = driver.window(ctx, seconds, trace)
    inside = log.since(mark)
    print(f"[compile] window {json.dumps(inside)}", flush=True)
    peak = read_peak(device["devices"])
    numbers, limits = driver.check(ctx, win)
    numbers["compiles_in_window"] = float(inside["programs"])
    limits.setdefault("compiles_in_window", 0.0)
    correct, rows = compare.judge(numbers, limits)

    run = {"workload": workload, "chips": env["chips"], "seed": seed,
           "config": parts["config"], "traffic": parts["traffic"],
           "peaks": device["peaks"], "window": win,
           "to_window_s": win["opened"] - PROCESS_START,
           "memory_peak_bytes": peak, "trace": None}
    out_device = {"platform": device["platform"], "kind": device["kind"],
                  "count": device["count"], "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": win["attempted"],
              "failed": win["failed"], "metrics": {}, "device": out_device}
    if trace:
        summary = trace_lib.summarize(win["trace_dir"], win.get("step_module"))
        if summary is None:
            raise SystemExit("the traced run shows no operation on a device")
        run["trace"] = summary
        out_device["busy_s"] = summary["busy_s"]
        out_device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    for metric in bench["per_layer"] if trace else bench["end_to_end"]:
        reader = load_module(
            os.path.join(HERE, "metrics", metric["name"] + ".py"),
            "benchmark_metric_" + metric["name"].replace("-", "_"))
        value = reader.read(run)
        if value is not None:
            result["metrics"][metric["name"]] = {"value": float(value),
                                                 "unit": metric["unit"]}
    result["compared"] = {
        name: {"value": value if value is None or math.isfinite(value)
               else 1e30, "limit": limit} for name, value, limit in rows}
    return result, rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json(os.path.join(CHECKOUT, "BENCHMARK.json"))
    device = probe_device(int(find_cell(bench, args.workload)["chips"]))
    result, rows = run_cell(bench, args.workload, args.seed, args.seconds,
                            bool(args.trace), device)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    print(f"correct {result['correct']}", file=sys.stderr)
    for name, value, limit in rows:
        print(f"compared {name} value {value} limit {limit}", file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
