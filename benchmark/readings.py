"""Readings for the limits of a training cell, all in one process on the chip.

    python3 benchmark/readings.py --workload <name> --seeds 11,12,... \\
        --controls 3 --out chiprun_out/readings.json

For every seed: the program's first steps through ``Trainer.train_epoch``
(the run's own set-up, on the trainer built once) against the plain
reference — the lower readings.  For the first ``--controls`` seeds also the
control (the reference with its conv and dense operands rounded to fp8, put
in the program's place) and the half-batch fault (the reference with the
second half of every batch left out) — the upper readings.  Every row is
judged by ``compare.judge`` against the limits in the cell's configuration
file, as a run judges the program: the verdict is printed beside the row,
SUMMARY counts how many of each kind came out not correct, and the exit
code is 1 where a program row is not correct or a control or fault row is.
Not part of a benchmark run; the driver never calls it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None) -> int:
    from benchmark import compare, refnn, run, weights

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--modes", default="control_fp8,fault_half_batch")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench = run.load_json(os.path.join(run.CHECKOUT, "BENCHMARK.json"))
    parts = run.resolve(bench, args.workload)
    run.probe_device(int(parts["cell"]["chips"]))
    driver, config = parts["driver"], parts["config"]
    traffic = dict(parts["traffic"])
    n = int(traffic["check_steps"])
    traffic["pool_batches"] = n  # the pool's first batches, no more
    env = {"code": HERE, "config_dir": parts["config_dir"],
           "chips": int(parts["cell"]["chips"]),
           "workdir": os.path.join(run.CHECKOUT, ".bench_work",
                                   args.workload + ".readings")}
    driver.enable_cache()
    built = driver.build(config, traffic, env)
    generator = run.load_module(
        os.path.join(HERE, "generators", traffic["generator"] + ".py"), "g")
    def judged(numbers: dict) -> dict:
        """The verdict a run would give these numbers (the limits on what
        only a window produces, such as ``skipped_steps``, left aside)."""
        limits = {k: v for k, v in config["limits"].items() if k in numbers}
        ok, compared = compare.judge(numbers, limits)
        return {"correct": ok,
                "failed": [k for k, v, lim in compared if not v <= lim]}

    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        pool = generator.make_pool(config, traffic, seed)
        state = driver.fresh_state(built, seed, pool[0])
        state, params0, program = driver.checked_steps(built, state, pool)
        del state
        key = weights.seed_key(seed)
        hyper = config["optimizer"]
        ref = refnn.run_steps(built["reference"], hyper, params0, pool, key)
        row = {"seed": seed, "program_loss": program["loss"],
               "reference_loss": ref["loss"]}
        heads = config.get("output_layers", ())
        row["program"], row["program_leaves"] = compare.training_numbers(
            program, ref, heads)
        row["program_verdict"] = judged(row["program"])
        # the look: every leaf's (gap of norms, norm of difference)
        row["leaf_gaps"] = {
            "grad": compare.leaf_numbers(program["grad"], ref["grad"])[0],
            "delta": compare.leaf_numbers(program["delta"], ref["delta"])[0]}
        if i < args.controls:
            modes = {"reference_bf16": {"operands": "bfloat16"},
                     "control_fp8": {"operands": "fp8"},
                     "fault_half_batch": {"rows": "half"}}
            for name in args.modes.split(","):
                kw = modes[name]
                got = refnn.run_steps(built["reference"], hyper, params0,
                                      pool, key, **kw)
                row[name], _ = compare.training_numbers(got, ref, heads)
                row[name + "_verdict"] = judged(row[name])
                row[name + "_loss"] = got["loss"]
                row["leaf_gaps"][name + "_grad"] = compare.leaf_numbers(
                    got["grad"], ref["grad"])[0]
        row["seconds"] = round(time.perf_counter() - t0, 1)
        print(json.dumps({k: v for k, v in row.items() if k != "leaf_gaps"}),
              flush=True)
        rows.append(row)
    names = sorted(rows[0]["program"])
    summary = {"limits": config["limits"],
               "program_max": {k: max(r["program"][k] for r in rows)
                               for k in names},
               "program_not_correct": [r["seed"] for r in rows
                                       if not r["program_verdict"]["correct"]]}
    sound = not summary["program_not_correct"]
    for kind in ("reference_bf16", "control_fp8", "fault_half_batch"):
        have = [r for r in rows if kind in r]
        if have:
            summary[kind + "_min"] = {k: min(r[kind][k] for r in have)
                                      for k in names}
            passed = [r["seed"] for r in have if r[kind + "_verdict"]["correct"]]
            summary[kind + "_not_correct"] = (
                f"{len(have) - len(passed)} of {len(have)}")
            summary[kind + "_came_out_correct"] = passed
            if kind != "reference_bf16" and passed:
                sound = False
    print("SUMMARY " + json.dumps(summary), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f, indent=1)
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
