"""Worker for test_distributed_two_processes: one of N processes in a
CPU 'pod'.  Run: python dist_worker.py <coordinator> <process_id> <n>.

Must be a real script (not -c/stdin): jax.distributed spawns service
threads, and the parent must be able to reap us cleanly on failure.
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
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from deep_vision_tpu.parallel.distributed import (  # noqa: E402
    initialize,
    make_pod_mesh,
)


def main():
    coordinator, pid, nprocs = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    initialize(coordinator_address=coordinator, num_processes=nprocs,
               process_id=pid)
    assert jax.process_count() == nprocs, jax.process_count()
    assert jax.process_index() == pid
    n_global = len(jax.devices())
    n_local = len(jax.local_devices())
    assert n_global == 2 * nprocs and n_local == 2, (n_global, n_local)

    mesh = make_pod_mesh({"data": -1})
    assert dict(mesh.shape) == {"data": n_global}, mesh.shape

    # a real cross-process collective: every process contributes its
    # local shard, the jitted global sum must see all of them
    local = np.full((n_local,), float(pid + 1), np.float32)
    arr = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("data")), local, (n_global,))
    total = jax.jit(lambda x: x.sum(),
                    out_shardings=NamedSharding(mesh, P()))(arr)
    val = float(np.asarray(total.addressable_shards[0].data))
    expect = sum(2.0 * (i + 1) for i in range(nprocs))
    assert val == expect, (val, expect)
    print(f"RESULT pid={pid} sum={val}", flush=True)


if __name__ == "__main__":
    main()
