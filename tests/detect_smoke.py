"""`make detect-smoke`: boot the plane the way
`python -m deep_vision_tpu.cli.serve --models yolov3_toy` does
(cli.serve.build_server's plane path) with an injected transient
compute fault, then prove device-side detect decode end to end over
real HTTP:

  * POST /v1/detect answers trimmed detections (decode → score floor →
    top-k → class-wise NMS compiled INTO the bucket program — the
    dense anchor pyramid never crosses D2H): ``num_detections`` always
    equals the row count, no padded/invalid rows ever reach a client,
    and per-request ``score_threshold`` trims server-side — zero
    client errors through the fault (bisect-retry absorbs it);
  * the engine's own counters prove the wire: bulk D2H is EXACTLY
    (served + padded) × K·28 B — boxes, not pyramids;
  * the wrong verb for a detect model 400s naming /v1/detect;
  * hot-reload yolov3_toy under live detect traffic through the FULL
    ladder — reload → SHADOW (the new greedy-IoU agreement metric
    gates the candidate: ≥10 live comparisons, perfect agreement for
    identical weights) → canary → explicit operator /promote
    (min_requests pinned high so auto-promote can't race) — v2
    active, ZERO hammer errors;
  * /v1/stats is plane-shaped with the shadow verdict banked on the
    v2 row, and every /metrics line parses as Prometheus text —
    including dvt_serve_d2h_bytes_total carrying workload="detect".

Run directly, not under pytest."""

import argparse
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
# as `python tests/detect_smoke.py` from the checkout
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# a metric line: name{labels} value  (labels optional; the value is
# validated separately with float(), which accepts nan/inf spellings)
_PROM_LINE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? (\S+)$")

#: fixed-size device row: K × (boxes f32×4 + score + class + valid)
_ROW_BYTES = 16 + 4 + 4 + 4


def _post(base, path, payload, timeout=120):
    req = urllib.request.Request(
        base + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _get(base, path, timeout=60):
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        return json.loads(r.read())


def _check_detect_body(out, min_score):
    assert out["model"] == "yolov3_toy", out
    dets = out["detections"]
    assert out["num_detections"] == len(dets), out
    for d in dets:
        assert {"box", "score", "class"} <= set(d), d
        assert len(d["box"]) == 4, d
        assert d["score"] >= min_score, (d, min_score)
        assert 0 <= d["class"] < 3, d
    return dets


def smoke():
    from deep_vision_tpu.cli.serve import build_server

    with tempfile.TemporaryDirectory() as workdir:
        os.makedirs(os.path.join(workdir, "yolov3_toy"), exist_ok=True)
        args = argparse.Namespace(
            model=None, models="yolov3_toy", workdir=workdir,
            stablehlo=None, host="127.0.0.1", port=0, max_batch=2,
            max_wait_ms=2.0, buckets=None, max_queue=64, warmup=False,
            verbose=False, pipeline_depth=2,
            # one transient compute failure somewhere in the mix: every
            # request below must still answer 200 through bisect-retry
            faults="compute:exception:times=1", fault_seed=0,
            serve_devices=1, shard_batches=False,
            wire_dtype="uint8", infer_dtype="float32",
            hbm_budget_mb=0.0, canary_frac=0.5,
            # pinned far above any traffic this test sends, so the
            # explicit operator /promote below is the ONLY way v2 goes
            # active (exercises the override path, not the auto-gate)
            canary_min_requests=10**6, canary_max_error_rate=0.0,
            canary_max_p99_ratio=50.0,
            # every 2nd live request duplicated onto the candidate:
            # the reload below must clear the detect agreement gate
            # (greedy IoU≥0.5 class-matched pairing) on REAL traffic
            shadow_frac=0.5,
            phase_timeout_s=120.0)
        plane, server = build_server(args)
        server.start_background()
        base = f"http://{server.host}:{server.port}"
        try:
            health = _get(base, "/v1/healthz")
            assert health["status"] == "ok", health
            assert sorted(health["engines"]) == ["yolov3_toy"], health

            # detect: raw uint8 pixels in, trimmed box list out — both
            # the flat verb route and the per-model path route
            px = np.random.default_rng(0).integers(
                0, 256, (64, 64, 3)).tolist()
            for path, body in (
                    ("/v1/detect", {"model": "yolov3_toy",
                                    "pixels": px}),
                    ("/v1/models/yolov3_toy/detect", {"pixels": px})):
                status, out = _post(base, path, body)
                assert status == 200, (path, out)
                # default request threshold is 0.3 — every surfaced
                # row clears it; padded device rows never appear
                _check_detect_body(out, 0.3)

            # per-request score_threshold trims server-side: a looser
            # floor returns a superset, a hopeless one returns empty
            _, loose = _post(base, "/v1/detect",
                             {"model": "yolov3_toy", "pixels": px,
                              "score_threshold": 0.05})
            _, tight = _post(base, "/v1/detect",
                             {"model": "yolov3_toy", "pixels": px,
                              "score_threshold": 0.999999})
            assert loose["num_detections"] >= out["num_detections"]
            assert tight["num_detections"] == 0, tight
            assert tight["detections"] == [], tight

            # the wrong verb for a detect model 400s naming the route
            try:
                _post(base, "/v1/classify",
                      {"model": "yolov3_toy", "pixels": px})
                raise AssertionError("wrong verb should 400")
            except urllib.error.HTTPError as e:
                assert e.code == 400, e.code
                assert "/v1/detect" in json.loads(e.read())["error"]

            # the injected fault fired and bisect-retry absorbed it
            # (every request above was a 200) — asserted BEFORE the
            # rollout, because promote retires the engine that took it
            pre = _get(base, "/v1/stats")
            pre_health = pre["models"]["yolov3_toy"]["engine"]["health"]
            assert pre_health["batch_failures"] >= 1, pre_health
            assert pre_health["retry_executions"] >= 1, pre_health
            failures = pre_health["batch_failures"]
            retries = pre_health["retry_executions"]

            # hot-reload under live detect traffic: reload → shadow
            # (agreement-gated) → canary → explicit operator promote,
            # zero client errors end to end
            errors, served = [], [0]
            stop = threading.Event()

            def hammer():
                while not stop.is_set():
                    try:
                        status, out = _post(
                            base, "/v1/detect",
                            {"model": "yolov3_toy", "pixels": px},
                            timeout=60)
                        assert status == 200, out
                        _check_detect_body(out, 0.3)
                        served[0] += 1
                    except Exception as e:  # noqa: BLE001 — any failure is a lost request
                        errors.append(repr(e))

            t = threading.Thread(target=hammer, daemon=True)
            t.start()
            status, out = _post(base, "/v1/models/yolov3_toy/reload",
                                {"force": True})
            assert status == 200 and out["status"] == "reloading", out
            deadline = time.monotonic() + 180
            canary_seen = None
            while time.monotonic() < deadline:
                table = _get(base, "/v1/models")["models"]
                versions = table["yolov3_toy"]["versions"]
                canary_seen = [v for v in versions
                               if v["state"] == "canary"]
                if canary_seen and canary_seen[0].get(
                        "canary", {}).get("requests", 0) >= 2:
                    break
                time.sleep(0.05)
            assert canary_seen, versions
            # reaching canary means the shadow gate PASSED on live
            # traffic: ≥ min_compared comparisons, and identical
            # weights give perfect greedy-IoU agreement
            shadow = canary_seen[0].get("shadow")
            assert shadow, canary_seen[0]
            assert shadow["compared"] >= 10, shadow
            assert shadow["agreed"] == shadow["compared"], shadow
            status, out = _post(base,
                                "/v1/models/yolov3_toy/promote", {})
            assert status == 200 and out["status"] == "promoted", out
            assert out["version"] == 2, out
            while time.monotonic() < deadline:
                if _get(base, "/v1/models")["models"]["yolov3_toy"][
                        "active_version"] == 2:
                    break
                time.sleep(0.05)
            # v2 serves through the same fused epilogue
            status, out = _post(base, "/v1/detect",
                                {"model": "yolov3_toy", "pixels": px})
            assert status == 200, out
            _check_detect_body(out, 0.3)
            stop.set()
            t.join(60)
            assert not errors, \
                f"rollout lost {len(errors)}: {errors[:3]}"

            # boxes, not pyramids: the drainer's bulk D2H is EXACTLY
            # (served + padded) × K·28 B fixed rows — the dense 64²
            # pyramid would be 8,064 B/image, the 416² one 340,704
            stats = _get(base, "/v1/stats")
            assert set(stats) >= {"models", "plane"}, set(stats)
            assert stats["plane"]["promotions"] == 1, stats["plane"]
            eng = stats["models"]["yolov3_toy"]["engine"]
            assert eng["workload"] == "detect", eng
            pipe = eng["pipeline"]
            detect = stats["models"]["yolov3_toy"].get(
                "describe", {}).get("detect") or _get(
                base, "/v1/models")["models"]["yolov3_toy"].get(
                "detect", {"top_k": 100})
            top_k = detect.get("top_k", 100)
            rows = eng["served"] + eng["padded_images"]
            assert pipe["d2h_bytes"] == rows * top_k * _ROW_BYTES, \
                (pipe["d2h_bytes"], rows, top_k)
            assert pipe["d2h_bytes_by_bucket"], pipe

            # /metrics: every line parses; the per-workload D2H series
            # carries the detect label
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
            assert any('workload="detect"' in ln for ln in d2h_lines), \
                d2h_lines
            print(f"detect-smoke PASS: device decode from port "
                  f"{server.port}; reload under load cleared the "
                  f"shadow agreement gate ({shadow['agreed']}/"
                  f"{shadow['compared']} matched) and promoted "
                  f"yolov3_toy v2 with {served[0]} client requests "
                  f"and 0 errors; fault fired ({failures} batch "
                  f"failure(s), {retries} retried); detect D2H "
                  f"{pipe['d2h_bytes']}B for {rows} bucket rows — "
                  f"{top_k * _ROW_BYTES}B/image, not 8,064; "
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
