"""`make serve-smoke`: boot the real HTTP server wiring on a random port
against a LeNet/MNIST workdir fixture, issue one /v1/classify request,
assert a 200 — once on the synchronous path (pipeline_depth=1), once on
the pipelined executor (depth=2, the production default; asserting the
scatter did exactly one bulk D2H per batch), once with an injected
transient compute failure (the request must still answer 200 through
bisect-retry and deep health must settle back to OK), once with the
full production wire (uint8 images + bfloat16 compute) through the same
fault, and finally the multi-device pass in a fresh subprocess with 2
forced host devices (`make serve-multi` runs just that pass): a
2-replica engine at depth 2, uint8 wire + bf16 compute, with the same
injected fault — requests spread over both replicas, routing/health
surface per-replica state, still 200s throughout.
Exercises exactly the `python -m deep_vision_tpu.cli.serve` path
(cli.serve.build_server), just without serve_forever in the foreground —
run directly, not under pytest."""

import argparse
import json
import os
import sys
import tempfile
import urllib.request

import numpy as np

# plain script (not pytest): make the repo root importable when invoked
# as `python tests/serve_smoke.py` from the checkout
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def smoke_one(pipeline_depth: int, faults: str = "",
              serve_devices: int = 1, requests: int = 1,
              wire_dtype: str = "uint8",
              infer_dtype: str = "float32") -> None:
    from deep_vision_tpu.cli.serve import build_server

    with tempfile.TemporaryDirectory() as workdir:
        # empty LeNet workdir fixture: restore falls back to random init,
        # which is the documented no-checkpoint smoke path
        args = argparse.Namespace(
            model="lenet5", workdir=workdir, stablehlo=None,
            host="127.0.0.1", port=0, max_batch=4, max_wait_ms=2.0,
            buckets=None, max_queue=64, warmup=False, verbose=False,
            pipeline_depth=pipeline_depth, faults=faults, fault_seed=0,
            serve_devices=serve_devices, shard_batches=False,
            wire_dtype=wire_dtype, infer_dtype=infer_dtype)
        engine, server = build_server(args)
        server.start_background()
        base = f"http://{server.host}:{server.port}"
        try:
            with urllib.request.urlopen(base + "/v1/healthz",
                                        timeout=60) as r:
                health = json.loads(r.read())
                assert r.status == 200 and health["status"] == "ok", health
                rep = health["engines"]["lenet5"]
                assert rep["batcher_alive"] and rep["accepting"], rep
                if serve_devices > 1:
                    assert len(rep["replicas"]) == serve_devices, rep
                    assert rep["can_serve"], rep
            # raw [0, 255] pixels on the uint8 wire (ints on the wire);
            # host-normalized floats on the legacy float32 wire
            if wire_dtype == "uint8":
                pixels = np.random.default_rng(0).integers(
                    0, 256, (32, 32, 1)).tolist()
            else:
                pixels = np.zeros((32, 32, 1)).tolist()
            body = json.dumps({"pixels": pixels}).encode()
            for _ in range(requests):
                req = urllib.request.Request(
                    base + "/v1/classify", data=body,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=60) as r:
                    assert r.status == 200, f"expected 200, got {r.status}"
                    top = json.loads(r.read())["top"]
                    assert len(top) == 5, top
            with urllib.request.urlopen(base + "/v1/stats",
                                        timeout=60) as r:
                stats = json.loads(r.read())["lenet5"]
            pipe = stats["pipeline"]
            assert pipe["depth"] == pipeline_depth, pipe
            # the scatter contract: ONE bulk D2H per executed batch
            assert pipe["bulk_transfers"] == stats["batches"] >= 1, pipe
            # the wire contract: images staged/transferred in the wire
            # dtype, computed in the infer dtype, H2D bytes accounted
            assert stats["wire_dtype"] == wire_dtype, stats["wire_dtype"]
            assert stats["infer_dtype"] == infer_dtype, stats["infer_dtype"]
            assert pipe["staging"]["dtype"] == wire_dtype, pipe["staging"]
            assert pipe["h2d_transfers"] >= stats["batches"], pipe
            px_bytes = 32 * 32 * (1 if wire_dtype == "uint8" else 4)
            assert pipe["h2d_bytes"] >= pipe["h2d_transfers"] * px_bytes, pipe
            health = stats["health"]
            assert health["state"] == "ok", health
            if faults:
                # the injected failure actually fired AND was recovered
                # from (bisect-retry re-executed the cohort)
                assert health["batch_failures"] >= 1, health
                assert health["retry_executions"] >= 1, health
                assert health["faults"]["injected"], health
            extra = ""
            if serve_devices > 1:
                routed = [r["routed_batches"] for r in stats["replicas"]]
                # round-robin tie-break: sequential singles must spread
                assert all(n >= 1 for n in routed), stats["replicas"]
                assert stats["routing"]["replicas"] == serve_devices
                assert stats["admission"]["free_replicas"] \
                    == serve_devices, stats["admission"]
                extra = f", {serve_devices} replicas routed {routed}"
            print(f"serve-smoke PASS (pipeline_depth={pipeline_depth}, "
                  f"wire={wire_dtype}, infer={infer_dtype}"
                  + (f", faults='{faults}'" if faults else "") + "): "
                  f"200 from port {server.port}, top-1 class "
                  f"{top[0]['class']}, {pipe['bulk_transfers']} bulk "
                  f"transfer(s) for {stats['batches']} batch(es), "
                  f"{pipe['h2d_bytes']} H2D byte(s), "
                  f"health {health['state']}{extra}")
        finally:
            server.shutdown()
            engine.stop(drain_deadline=5.0)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--multi", action="store_true",
                   help="run only the multi-device pass (needs "
                        "XLA_FLAGS=--xla_force_host_platform_device_"
                        "count=2 before jax initializes; make "
                        "serve-multi sets it)")
    opts = p.parse_args()
    if opts.multi:
        # 2 fake host devices, depth 2, fault-injected: the replica
        # wiring end to end.  Smokes run on the CPU; the platform pin
        # must land before the jax backend initializes.
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=2")
        import jax

        jax.config.update("jax_platforms", "cpu")
        # the production wire: uint8 images, bf16 matmuls, f32 outputs —
        # replicated over both devices with an injected fault
        smoke_one(2, faults="compute:exception:times=1",
                  serve_devices=2, requests=6,
                  wire_dtype="uint8", infer_dtype="bfloat16")
        return 0
    # legacy float32 wire still serves (back-compat path)
    smoke_one(1, wire_dtype="float32")
    # production default: uint8 wire, device-side preprocessing
    smoke_one(2)
    # fault-injected pass: one transient compute failure — the request
    # must still answer 200 (bisect-retry), health must settle back OK
    smoke_one(2, faults="compute:exception:times=1")
    # uint8 wire + bfloat16 compute together, through the same fault —
    # the retry path must re-stage the uint8 cohort and still answer 200
    smoke_one(2, faults="compute:exception:times=1",
              wire_dtype="uint8", infer_dtype="bfloat16")
    # multi-device pass: a fresh subprocess, because the forced host
    # device count must be set before this process's jax backend exists
    import subprocess

    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--multi"], env=env)
    return proc.returncode


if __name__ == "__main__":
    sys.exit(main())
