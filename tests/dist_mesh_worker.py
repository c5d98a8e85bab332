"""Worker for test_mesh_serving_two_processes: one rank of a 2-process
CPU 'pod' (2 virtual devices per rank) serving LeNet over a 2×2
``data × model`` pod mesh.  Each rank shards the (deterministic,
identical) restore across all 4 global devices via the partition
fallback, compiles the bucket program, runs one global batch, and
checks every ADDRESSABLE output shard against a locally-computed
single-device reference — the GSPMD collectives cross process
boundaries, the numerics must not.  RESULT payloads are identical
across ranks by construction (same weights, same batch).

Run: python dist_mesh_worker.py <coordinator> <process_id> <n> <workdir>.
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
    # 2 procs × 2 local devices → data=2 (across processes, DCN-ish),
    # model=2 (inside each process)
    mesh = make_pod_mesh({"data": 2, "model": -1})

    from deep_vision_tpu.serve.registry import ModelRegistry

    reg = ModelRegistry()
    # empty shared workdir → deterministic PRNGKey(0) init on BOTH
    # ranks (the multi-process analogue of the smoke fixture)
    sm = reg.load_checkpoint("lenet5", workdir)
    view = sm.for_mesh(mesh, min_shard_dim=64)
    shard_bytes = view.param_bytes()
    global_bytes = view.param_global_bytes()
    assert shard_bytes < global_bytes, (shard_bytes, global_bytes)

    batch = 2
    try:
        prog = view.compile_bucket(batch)
    except Exception as e:  # noqa: BLE001 — backend capability probe
        if "Multiprocess computations aren't implemented" in str(e):
            # this jaxlib's CPU backend can't execute cross-process
            # SPMD programs at all (same limitation test_distributed
            # hits); the launcher turns this sentinel into a skip
            print(f"SKIPBACKEND pid={pid} cpu-multiprocess-unsupported",
                  flush=True)
            return
        raise
    x = np.random.RandomState(0).randn(
        batch, *sm.input_shape).astype(np.float32)
    # every rank holds the full batch; the global array slices each
    # addressable shard locally (no cross-host transfer)
    xg = jax.make_array_from_callback(
        x.shape, view.placement, lambda idx: x[idx])
    out = prog(xg)

    # local single-device reference: eager apply on this rank's own
    # host restore (float32 wire passes through the serve preprocess)
    ref = np.asarray(sm._model.apply(
        sm._variables, x, train=False)).astype(np.float32)
    for shard in out.addressable_shards:
        np.testing.assert_allclose(np.asarray(shard.data),
                                   ref[shard.index],
                                   rtol=1e-5, atol=1e-5)
    top1 = [int(c) for c in np.argmax(ref, axis=-1)]
    print(f"RESULT pid={pid} top1={top1} "
          f"logit_sum={float(np.sum(ref)):.6f} "
          f"shard_bytes={shard_bytes} global_bytes={global_bytes}",
          flush=True)


if __name__ == "__main__":
    main()
