"""Worker for test_distributed_trainer_fit: one rank of an N-process CPU
'pod' running a REAL Trainer.fit — per-process data shards feeding a
process-spanning mesh, Orbax checkpointing coordinated across ranks
(process 0 writes), then a resume from the shared checkpoint directory.

Run: python dist_fit_worker.py <coordinator> <process_id> <n> <workdir>.
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
    from deep_vision_tpu.data.loader import ArrayLoader
    from deep_vision_tpu.data.mnist import synthetic_mnist
    from deep_vision_tpu.tasks.classification import ClassificationTask

    cfg = get_config("lenet5")
    cfg.total_epochs = 2
    cfg.log_every_steps = 2

    # identical seeded dataset on every rank; each rank FEEDS its own
    # interleaved shard (the per-host file sharding semantics) — global
    # batch 32 = 16 local × 2 processes
    data = synthetic_mnist(128)
    shard = {k: v[pid::nprocs] for k, v in data.items()}

    def loaders():
        return (ArrayLoader(shard, 16, seed=1),
                ArrayLoader(shard, 16, shuffle=False))

    train_loader, val_loader = loaders()
    trainer = Trainer(cfg, cfg.model(), ClassificationTask(10), mesh=mesh,
                      workdir=workdir)
    state = trainer.fit(train_loader, val_loader)
    step1 = int(jax.device_get(state.step))
    m1 = trainer.evaluate(state, val_loader)
    assert np.isfinite(m1["loss"]), m1
    assert trainer.checkpointer.latest_step() == step1
    # process 0 wrote the checkpoint files; every rank sees them (shared FS)
    print(f"FIT pid={pid} step={step1} loss={m1['loss']:.6f}", flush=True)

    # resume on a FRESH trainer from the shared checkpoint dir, train one
    # more epoch — the v4-32 recovery path
    cfg2 = get_config("lenet5")
    cfg2.total_epochs = 3
    cfg2.log_every_steps = 2
    train2, val2 = loaders()
    trainer2 = Trainer(cfg2, cfg2.model(), ClassificationTask(10), mesh=mesh,
                       workdir=workdir)
    state2 = trainer2.fit(train2, val2, resume=True)
    step2 = int(jax.device_get(state2.step))
    assert trainer2.start_epoch == 3, trainer2.start_epoch
    assert step2 > step1, (step1, step2)
    m2 = trainer2.evaluate(state2, val2)
    print(f"RESULT pid={pid} step={step2} loss={m2['loss']:.6f}", flush=True)


if __name__ == "__main__":
    main()
