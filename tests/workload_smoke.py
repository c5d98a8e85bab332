"""`make workload-smoke`: boot the multi-model plane the way
`python -m deep_vision_tpu.cli.serve --models hourglass_toy,dcgan`
does (cli.serve.build_server's plane path) with an injected transient
compute fault, then prove the workload-generic serving surface end to
end over real HTTP:

  * POST /v1/pose answers decoded keypoints (the heatmap→argmax
    epilogue compiled INTO the bucket program — no heatmap ever
    crosses D2H) and /v1/generate answers a base64 uint8 image at
    1 byte/pixel (the output-side uint8 wire), both also via the
    per-model /v1/models/{name}/<verb> routes — zero client errors
    through the fault (bisect-retry absorbs it);
  * unknown verbs 404 with the registry-derived supported list, and
    the wrong verb for a model's workload 400s naming the right one;
  * hot-reload hourglass_toy under live pose traffic (reload →
    canary → explicit operator POST /promote, min_requests pinned
    high so auto-promote can't race the operator path) — v2 active,
    ZERO hammer errors;
  * /v1/stats is plane-shaped with per-workload engine stats
    (d2h_bytes > 0 on both engines, fault counters prove the
    injection fired AND was retried), and every /metrics line parses
    as Prometheus text — including dvt_serve_d2h_bytes_total carrying
    workload="pose" and workload="generate" labels.

Run directly, not under pytest."""

import argparse
import base64
import json
import os
import re
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

import numpy as np

# plain script (not pytest): make the repo root importable when invoked
# as `python tests/workload_smoke.py` from the checkout
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# a metric line: name{labels} value  (labels optional; the value is
# validated separately with float(), which accepts nan/inf spellings)
_PROM_LINE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? (\S+)$")


def _post(base, path, payload, timeout=120):
    req = urllib.request.Request(
        base + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _get(base, path, timeout=60):
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        return json.loads(r.read())


def smoke():
    from deep_vision_tpu.cli.serve import build_server

    with tempfile.TemporaryDirectory() as workdir:
        for name in ("hourglass_toy", "dcgan"):
            os.makedirs(os.path.join(workdir, name), exist_ok=True)
        args = argparse.Namespace(
            model=None, models="hourglass_toy,dcgan", workdir=workdir,
            stablehlo=None, host="127.0.0.1", port=0, max_batch=2,
            max_wait_ms=2.0, buckets=None, max_queue=64, warmup=False,
            verbose=False, pipeline_depth=2,
            # one transient compute failure somewhere in the mix: every
            # request below must still answer 200 through bisect-retry
            faults="compute:exception:times=1", fault_seed=0,
            serve_devices=1, shard_batches=False,
            # uint8 requested for BOTH: pose keeps it (unit prologue on
            # device), the generate workload overrides dcgan's latent
            # input to float32 — the codec contract under one flag
            wire_dtype="uint8", infer_dtype="float32",
            hbm_budget_mb=0.0, canary_frac=0.5,
            # pinned far above any traffic this test sends, so the
            # explicit operator /promote below is the ONLY way v2 goes
            # active (exercises the override path, not the auto-gate)
            canary_min_requests=10**6, canary_max_error_rate=0.0,
            canary_max_p99_ratio=50.0, shadow_frac=0.0,
            phase_timeout_s=120.0)
        plane, server = build_server(args)
        server.start_background()
        base = f"http://{server.host}:{server.port}"
        try:
            health = _get(base, "/v1/healthz")
            assert health["status"] == "ok", health
            assert sorted(health["engines"]) == \
                ["dcgan", "hourglass_toy"], health

            # pose: raw uint8 pixels in, decoded keypoints out — both
            # the flat verb route and the per-model path route
            pose_px = np.random.default_rng(0).integers(
                0, 256, (64, 64, 3)).tolist()
            for path, body in (
                    ("/v1/pose", {"model": "hourglass_toy",
                                  "pixels": pose_px}),
                    ("/v1/models/hourglass_toy/pose",
                     {"pixels": pose_px})):
                status, out = _post(base, path, body)
                assert status == 200, (path, out)
                assert out["space"] == "heatmap", out
                kps = out["keypoints"]
                assert len(kps) == 8, out
                assert all({"x", "y", "score"} <= set(k) for k in kps)

            # generate: latent-in (seeded server-side), wire-ready
            # uint8 image out at 1 byte/pixel
            for path, body in (
                    ("/v1/generate", {"model": "dcgan", "seed": 7}),
                    ("/v1/models/dcgan/generate", {"seed": 7})):
                status, out = _post(base, path, body)
                assert status == 200, (path, out)
                img = out["image"]
                assert img["dtype"] == "uint8", img
                assert img["shape"] == [28, 28, 1], img
                raw = base64.b64decode(img["b64"])
                assert len(raw) == 28 * 28 * 1, len(raw)
            # deterministic codec: same seed → byte-identical image
            _, again = _post(base, "/v1/generate",
                             {"model": "dcgan", "seed": 7})
            assert again["image"]["b64"] == img["b64"]

            # registry-driven routing: unknown verbs 404 with the
            # supported list; the wrong verb for a workload 400s
            for path in ("/v1/frobnicate",
                         "/v1/models/dcgan/frobnicate"):
                try:
                    _post(base, path, {"seed": 0})
                    raise AssertionError(f"{path} should 404")
                except urllib.error.HTTPError as e:
                    assert e.code == 404, (path, e.code)
                    body = json.loads(e.read())
                    verbs = body["supported_verbs"]
                    assert {"classify", "detect", "pose", "generate",
                            "reload", "promote",
                            "rollback"} <= set(verbs), verbs
            try:
                _post(base, "/v1/classify",
                      {"model": "dcgan", "seed": 0})
                raise AssertionError("wrong verb should 400")
            except urllib.error.HTTPError as e:
                assert e.code == 400, e.code
                assert "/v1/generate" in json.loads(e.read())["error"]

            # the injected fault fired on the FIRST executed batch
            # and bisect-retry absorbed it (every request above was a
            # 200) — asserted BEFORE the rollout, because promote
            # retires the v1 engine that took the hit
            pre = _get(base, "/v1/stats")
            pre_health = {n: m["engine"]["health"]
                          for n, m in pre["models"].items()}
            assert sum(h["batch_failures"]
                       for h in pre_health.values()) >= 1, pre_health
            assert sum(h["retry_executions"]
                       for h in pre_health.values()) >= 1, pre_health
            failures = sum(h["batch_failures"]
                           for h in pre_health.values())
            retries = sum(h["retry_executions"]
                          for h in pre_health.values())

            # hot-reload hourglass_toy under live pose traffic:
            # reload → canary → explicit operator promote, zero errors
            errors, served = [], [0]
            stop = threading.Event()

            def hammer():
                while not stop.is_set():
                    try:
                        status, out = _post(
                            base, "/v1/pose",
                            {"model": "hourglass_toy",
                             "pixels": pose_px}, timeout=60)
                        assert status == 200 and out["keypoints"], out
                        served[0] += 1
                    except Exception as e:  # noqa: BLE001 — any failure is a lost request
                        errors.append(repr(e))

            t = threading.Thread(target=hammer, daemon=True)
            t.start()
            status, out = _post(base, "/v1/models/hourglass_toy/reload",
                                {"force": True})
            assert status == 200 and out["status"] == "reloading", out
            deadline = time.monotonic() + 120
            canary_seen = None
            while time.monotonic() < deadline:
                table = _get(base, "/v1/models")["models"]
                versions = table["hourglass_toy"]["versions"]
                canary_seen = [v for v in versions
                               if v["state"] == "canary"]
                if canary_seen and canary_seen[0].get(
                        "canary", {}).get("requests", 0) >= 2:
                    break
                time.sleep(0.05)
            assert canary_seen, versions
            status, out = _post(base,
                                "/v1/models/hourglass_toy/promote", {})
            assert status == 200 and out["status"] == "promoted", out
            assert out["version"] == 2, out
            while time.monotonic() < deadline:
                if _get(base, "/v1/models")["models"]["hourglass_toy"][
                        "active_version"] == 2:
                    break
                time.sleep(0.05)
            # v2 serves through the same fused epilogue
            status, out = _post(base, "/v1/pose",
                                {"model": "hourglass_toy",
                                 "pixels": pose_px})
            assert status == 200 and len(out["keypoints"]) == 8, out
            stop.set()
            t.join(60)
            assert not errors, \
                f"rollout lost {len(errors)}: {errors[:3]}"

            # plane-shaped stats: per-workload engines, D2H accounted
            stats = _get(base, "/v1/stats")
            assert set(stats) >= {"models", "plane"}, set(stats)
            assert stats["plane"]["promotions"] == 1, stats["plane"]
            engines = {n: m["engine"]
                       for n, m in stats["models"].items()}
            assert engines["hourglass_toy"]["workload"] == "pose"
            assert engines["dcgan"]["workload"] == "generate"
            for n, e in engines.items():
                assert e["pipeline"]["d2h_bytes"] > 0, (n, e["pipeline"])
                assert e["pipeline"]["d2h_bytes_by_bucket"], n
            # pose D2H is keypoints, not heatmaps: strictly under the
            # 16*16*8*4-byte-per-image stack it replaced
            pose_pipe = engines["hourglass_toy"]["pipeline"]
            assert pose_pipe["d2h_bytes"] < \
                engines["hourglass_toy"]["served"] * 16 * 16 * 8 * 4

            # /metrics: every line parses; the per-workload D2H series
            # exists for both workloads
            with urllib.request.urlopen(base + "/metrics",
                                        timeout=60) as r:
                text = r.read().decode()
            for line in text.splitlines():
                if not line or line.startswith("#"):
                    continue
                m = _PROM_LINE.match(line)
                assert m, f"bad metric line: {line}"
                float(m.group(2))  # ValueError = unparseable sample
            d2h_lines = [ln for ln in text.splitlines()
                         if ln.startswith("dvt_serve_d2h_bytes_total")]
            assert any('workload="pose"' in ln for ln in d2h_lines), \
                d2h_lines
            assert any('workload="generate"' in ln
                       for ln in d2h_lines), d2h_lines
            print(f"workload-smoke PASS: pose+generate from port "
                  f"{server.port}; reload under load promoted "
                  f"hourglass_toy v2 with {served[0]} client requests "
                  f"and 0 errors; fault fired ({failures} batch "
                  f"failure(s), {retries} retried); pose D2H "
                  f"{pose_pipe['d2h_bytes']}B for "
                  f"{engines['hourglass_toy']['served']} served, "
                  f"generate D2H "
                  f"{engines['dcgan']['pipeline']['d2h_bytes']}B; "
                  f"{len(text.splitlines())} metric lines parsed")
        finally:
            server.shutdown()
            plane.stop(drain_deadline=5.0)
    return 0


def main():
    # smokes run on the CPU: pin the platform before jax initializes
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")
    return smoke()


if __name__ == "__main__":
    sys.exit(main())
