"""Readings for the limits of a training cell with routed experts, all in
one process on the chip.

    python3 benchmark/readings_moe.py --workload <name> --seeds 11,12,... \\
        --controls 2 --faults 1 --out chiprun_out/readings_moe.json

For every seed: the program's first steps through ``Trainer.train_epoch``
(the trainer built once) against the plain reference -- the lower readings.
For the first ``--controls`` seeds also the control (the reference with its
dense and expert operands rounded to fp8, in the program's place), and for
the first ``--faults`` seeds each planted fault in the program's place (the
reference with one thing wrong, ``FAULTS`` below) -- the upper readings.
Every row is judged by ``compare.judge`` against the limits in the cell's
configuration file, as a run judges the program; the exit code is 1 where a
program row is not correct or a control or fault row is.  Not part of a
benchmark run; the driver never calls it.
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

FAULTS = {
    "fault_biased_weights": {"weigh": "biased"},
    "fault_norm_over_held": {"normalise": "held"},
    "fault_capacity_1_25": {"capacity": 1.25},
    "fault_no_reset": {"reset": False},
    "fault_half_row": {"rows": "half"},
    "fault_no_balance": {"balance": False},
}


def main(argv=None) -> int:
    from benchmark import compare, run

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--faults", type=int, default=1)
    ap.add_argument("--only", default=None,
                    help="comma-separated names of FAULTS to plant (default all)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--bench", default=os.path.join(run.CHECKOUT, "BENCHMARK.json"))
    args = ap.parse_args(argv)
    bench = run.load_json(args.bench)
    parts = run.resolve(bench, args.workload)
    run.probe_device(int(parts["cell"]["chips"]))
    driver, config = parts["driver"], parts["config"]
    n = int(parts["traffic"]["check_steps"])
    traffic = dict(parts["traffic"], pool_batches=n)  # the first batches only
    env = {"code": HERE, "config_dir": parts["config_dir"],
           "chips": int(parts["cell"]["chips"]),
           "workdir": os.path.join(run.CHECKOUT, ".bench_work",
                                   args.workload + ".readings")}
    driver.enable_cache()
    built = driver.build(config, traffic, env)
    generator = run.load_module(
        os.path.join(HERE, "generators", traffic["generator"] + ".py"), "g")
    reference = built["reference"]
    planted = {k: v for k, v in FAULTS.items()
               if args.only is None or k in args.only.split(",")}

    def judged(numbers: dict) -> dict:
        limits = {k: v for k, v in config["limits"].items() if k in numbers}
        ok, compared = compare.judge(numbers, limits)
        return {"correct": ok,
                "failed": [k for k, v, lim in compared if not v <= lim]}

    rows, sound = [], True
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        pool = generator.make_pool(config, traffic, seed)
        state = driver.fresh_state(built, seed, pool[0])
        logged = len(driver.logged_rows(built["trainer"].workdir))
        state, params0, program = driver.checked_steps(built, state, pool)
        program.update(driver.logged_counters(built["trainer"].workdir, logged))
        del state
        ref = reference.run_steps(params0, pool)
        row = {"seed": seed, "program_loss": program["loss"],
               "reference_loss": ref["loss"],
               "reference_counters": {k: ref[k] for k in ref if k.startswith("moe_")}}
        row["program"], row["program_leaves"] = driver.numbers_of(
            program, ref, config)
        row["program_verdict"] = judged(row["program"])
        sound = sound and row["program_verdict"]["correct"]
        del program  # 6 GB of host memory a record: as few at a time as can be
        modes = {}
        if i < args.controls:
            modes["control_fp8"] = {"operands": "fp8"}
        if i < args.faults:
            modes.update(planted)
        for name, kw in modes.items():
            got = reference.run_steps(params0, pool, **kw)
            row[name], row[name + "_leaves"] = driver.numbers_of(got, ref, config)
            row[name + "_loss"] = got["loss"]
            row[name + "_verdict"] = judged(row[name])
            sound = sound and not row[name + "_verdict"]["correct"]
            del got
        row["seconds"] = round(time.perf_counter() - t0, 1)
        row["host_peak_gib"] = round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20, 1)
        print(json.dumps(row), flush=True)
        rows.append(row)
        del ref, params0
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump({"limits": config["limits"], "rows": rows}, f, indent=1)
    print(f"SOUND {sound}", flush=True)
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
