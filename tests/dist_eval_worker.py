"""Worker for test_distributed_eval_rank0_broadcast: one rank of a
2-process CPU 'pod' running Trainer.evaluate on YOLO-toy at random init.
The detection extras are still allgathered collectively (every rank's
shard reaches the global val set), but the host-side mAP accumulator
feeds on process 0 ONLY — the scalar metrics are broadcast so every
rank reports identical numbers without redoing the sweep per rank.

Run: python dist_eval_worker.py <coordinator> <process_id> <n> <workdir>.
"""

import os
import sys

# 2 virtual CPU devices per process, BEFORE any jax import
flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
         if not f.startswith("--xla_force_host_platform_device_count")]
flags.append("--xla_force_host_platform_device_count=2")
os.environ["XLA_FLAGS"] = " ".join(flags)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")  # tests run on the CPU

import numpy as np  # noqa: E402

from deep_vision_tpu.parallel.distributed import (  # noqa: E402
    initialize,
    make_pod_mesh,
)


def main():
    coordinator, pid, nprocs, workdir = (
        sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4])
    initialize(coordinator_address=coordinator, num_processes=nprocs,
               process_id=pid)
    mesh = make_pod_mesh({"data": -1})

    from deep_vision_tpu.core.config import get_config
    from deep_vision_tpu.core.trainer import Trainer
    from deep_vision_tpu.data.detection import (
        DetectionLoader,
        synthetic_detection_dataset,
    )
    from deep_vision_tpu.tasks.detection import YoloTask

    cfg = get_config("yolov3_toy")
    samples = synthetic_detection_dataset(16, 64, 3, seed=5)
    shard = [samples[i] for i in range(pid, len(samples), nprocs)]
    val = DetectionLoader(shard, 4, 3, 64, train=False)

    task = YoloTask(3)
    # count host-evaluator feeds on THIS rank: the whole point of the
    # rank-0 gate is that only process 0's accumulator sees batches
    real_make = task.make_host_evaluator
    feeds = {"n": 0}

    def counting_make():
        ev = real_make()
        orig = ev.add_batch

        def add_batch(batch):
            feeds["n"] += 1
            return orig(batch)

        ev.add_batch = add_batch
        return ev

    task.make_host_evaluator = counting_make

    trainer = Trainer(cfg, cfg.model(), task, mesh=mesh, workdir=workdir)
    state = trainer.init_state(next(iter(val)))
    m = trainer.evaluate(state, val)
    assert np.isfinite(m["loss"]), m
    assert "mAP" in m and "mAP50_95" in m, m
    if pid == 0:
        assert feeds["n"] > 0, "rank 0 must feed the accumulator"
    else:
        assert feeds["n"] == 0, \
            f"rank {pid} fed the accumulator {feeds['n']}x — the mAP " \
            f"sweep should run on process 0 only"
    # RESULT lines must be identical across ranks (broadcast metrics)
    print(f"RESULT pid={pid} loss={m['loss']:.6f} mAP={m['mAP']:.4f} "
          f"mAP50_95={m['mAP50_95']:.4f}", flush=True)
    print(f"EVALFEEDS pid={pid} n={feeds['n']}", flush=True)


if __name__ == "__main__":
    main()
