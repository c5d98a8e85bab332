"""`make mesh-smoke` (runs inside `make serve-smoke`): boot the real
cli.serve wiring with a FORCED 2×2 ``data × model`` mesh over 4 virtual
host devices, fault-injected, and assert the whole mesh surface end to
end: every request answers 200 through bisect-retry, /v1/healthz
advertises the mesh shape + per-chip shard bytes + HBM headroom,
/v1/stats prices the per-chip footprint strictly below the replicated
one, and every /metrics line parses — including the new
``dvt_serve_mesh_shape`` (one sample per axis) and
``dvt_serve_param_shard_bytes`` gauges, which must agree with the
stats document.  Run directly, not under pytest."""

import argparse
import json
import os
import re
import sys
import tempfile
import urllib.request

# 4 virtual host devices for the 2×2 mesh, BEFORE any jax import
_flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
          if not f.startswith("--xla_force_host_platform_device_count")]
_flags.append("--xla_force_host_platform_device_count=4")
os.environ["XLA_FLAGS"] = " ".join(_flags)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")  # tests run on the CPU

import numpy as np  # noqa: E402

# plain script (not pytest): make the repo root importable when invoked
# as `python tests/mesh_smoke.py` from the checkout
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_SAMPLE_RE = re.compile(r"([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (\S+)")


def parse_metrics(text: str) -> dict:
    """Validate every exposition line; return {name: {labels_str: value}}."""
    samples: dict = {}
    for line in text.splitlines():
        assert line.strip() == line and line, f"bad line {line!r}"
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            continue
        assert not line.startswith("#"), f"unknown comment {line!r}"
        m = _SAMPLE_RE.fullmatch(line)
        assert m, f"unparseable sample {line!r}"
        name, labels, value = m.groups()
        v = float("inf") if value == "+Inf" else float(value)
        samples.setdefault(name, {})[labels or ""] = v
    return samples


def main():
    from deep_vision_tpu.cli.serve import build_server

    with tempfile.TemporaryDirectory() as workdir:
        args = argparse.Namespace(
            model="lenet5", workdir=workdir, stablehlo=None,
            host="127.0.0.1", port=0, max_batch=4, max_wait_ms=2.0,
            buckets=None, max_queue=64, warmup=False, verbose=False,
            pipeline_depth=2, faults="compute:exception:times=1",
            fault_seed=0, serve_devices=1, shard_batches=False,
            mesh="2,2", partition_rules=None, partition_strict=False,
            partition_min_dim=64,
            wire_dtype="float32", infer_dtype="float32")
        engine, server = build_server(args)
        server.start_background()
        base = f"http://{server.host}:{server.port}"
        try:
            body = json.dumps(
                {"pixels": np.zeros((32, 32, 1)).tolist()}).encode()
            for _ in range(4):
                req = urllib.request.Request(
                    base + "/v1/classify", data=body,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=60) as r:
                    assert r.status == 200, r.status
                    assert len(json.loads(r.read())["top"]) == 5

            with urllib.request.urlopen(base + "/v1/healthz",
                                        timeout=60) as r:
                health = json.loads(r.read())
            rep = health["engines"]["lenet5"]
            assert rep["mesh_shape"] == {"data": 2, "model": 2}, rep
            assert rep["param_shard_bytes"] > 0, rep
            assert "hbm_headroom_bytes" in rep, rep

            with urllib.request.urlopen(base + "/v1/stats",
                                        timeout=60) as r:
                stats = json.loads(r.read())["lenet5"]
            assert stats["mesh_shape"] == {"data": 2, "model": 2}, stats
            shard, glob = (stats["param_shard_bytes"],
                           stats["param_global_bytes"])
            assert 0 < shard < glob, (shard, glob)
            h = stats["health"]
            # the injected failure fired AND was recovered from
            assert h["batch_failures"] >= 1, h
            assert h["retry_executions"] >= 1, h
            assert h["state"] == "ok", h

            with urllib.request.urlopen(base + "/metrics",
                                        timeout=60) as r:
                samples = parse_metrics(r.read().decode())
            mesh_g = samples["dvt_serve_mesh_shape"]
            assert mesh_g['{axis="data",model="lenet5"}'] == 2, mesh_g
            assert mesh_g['{axis="model",model="lenet5"}'] == 2, mesh_g
            shard_g = samples["dvt_serve_param_shard_bytes"]
            assert shard_g['{model="lenet5"}'] == shard, shard_g
            assert samples["dvt_serve_weight_hbm_bytes"][
                '{model="lenet5"}'] == shard, "cache unit must be per-chip"
            print(f"mesh smoke OK (2x2, faults recovered): per-chip "
                  f"{shard} B of {glob} B logical, "
                  f"{len(samples)} metric families parsed")
        finally:
            server.shutdown()
            engine.stop(drain_deadline=5.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
