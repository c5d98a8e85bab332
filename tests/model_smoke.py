"""`make model-smoke`: boot the multi-model control plane exactly the
way `python -m deep_vision_tpu.cli.serve --models lenet5,yolov3_toy`
does (cli.serve.build_server's plane path), on the CPU host platform
with a weight-cache budget too small to hold both models — then:

  * classify/detect through the per-model path routes
    (/v1/models/{name}/classify|detect) — both models answer 200 even
    though only one fits the HBM budget at a time (evict → spill →
    re-admit under the hood, visible in the cache counters);
  * hot-reload lenet5 MID-LOAD over HTTP (POST
    /v1/models/lenet5/reload {"force": true, "wait": true}) while a
    client thread hammers it — the reload must promote v2 and the
    client must see ZERO errors (the zero-downtime contract, end to
    end through the real HTTP stack);
  * assert /v1/models lists both names with their version tables,
    /v1/stats is plane-shaped (models/cache/plane), and every /metrics
    line parses as Prometheus text exposition — including the
    dvt_serve_model_up and dvt_serve_weight_cache_* series.

Run directly, not under pytest."""

import argparse
import json
import os
import re
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np

# plain script (not pytest): make the repo root importable when invoked
# as `python tests/model_smoke.py` from the checkout
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


def smoke():
    from deep_vision_tpu.cli.serve import build_server

    with tempfile.TemporaryDirectory() as workdir:
        for name in ("lenet5", "yolov3_toy"):
            os.makedirs(os.path.join(workdir, name), exist_ok=True)
        args = argparse.Namespace(
            model=None, models="lenet5,yolov3_toy", workdir=workdir,
            stablehlo=None, host="127.0.0.1", port=0, max_batch=4,
            max_wait_ms=2.0, buckets=None, max_queue=64, warmup=True,
            verbose=False, pipeline_depth=2, faults="", fault_seed=0,
            serve_devices=1, shard_batches=False, wire_dtype="float32",
            infer_dtype="float32",
            # ~1 MiB holds LeNet (~0.24 MiB) but not the toy YOLO
            # (~2.1 MiB): the cache must evict/spill to serve both
            hbm_budget_mb=1.0,
            canary_frac=0.5, canary_min_requests=3,
            canary_max_error_rate=0.0, canary_max_p99_ratio=50.0,
            shadow_frac=0.0, phase_timeout_s=60.0)
        plane, server = build_server(args)
        server.start_background()
        base = f"http://{server.host}:{server.port}"
        try:
            with urllib.request.urlopen(base + "/v1/healthz",
                                        timeout=60) as r:
                health = json.loads(r.read())
            assert health["status"] == "ok", health
            assert sorted(health["engines"]) == \
                ["lenet5", "yolov3_toy"], health
            # both models answer through the path route, repeatedly —
            # the second round forces the evict→re-admit cycle
            lenet_px = np.zeros((32, 32, 1)).tolist()
            yolo_px = np.zeros((64, 64, 3)).tolist()
            for _ in range(2):
                status, out = _post(base, "/v1/models/lenet5/classify",
                                    {"pixels": lenet_px})
                assert status == 200 and len(out["top"]) == 5, out
                status, out = _post(base, "/v1/models/yolov3_toy/detect",
                                    {"pixels": yolo_px})
                assert status == 200 and "detections" in out, out
            # the model table before the reload
            with urllib.request.urlopen(base + "/v1/models",
                                        timeout=60) as r:
                table = json.loads(r.read())["models"]
            assert table["lenet5"]["active_version"] == 1, table
            assert table["yolov3_toy"]["active_version"] == 1, table

            # hot-reload lenet5 while a client hammers it: zero errors
            errors, served = [], [0]
            stop = threading.Event()

            def hammer():
                while not stop.is_set():
                    try:
                        status, out = _post(
                            base, "/v1/models/lenet5/classify",
                            {"pixels": lenet_px}, timeout=60)
                        assert status == 200 and out["top"], out
                        served[0] += 1
                    except Exception as e:  # noqa: BLE001 — any failure is a lost request
                        errors.append(repr(e))

            t = threading.Thread(target=hammer, daemon=True)
            t.start()
            while served[0] < 5:  # canary traffic needs a live stream
                time.sleep(0.01)
            status, out = _post(base, "/v1/models/lenet5/reload",
                                {"force": True, "wait": True})
            stop.set()
            t.join(60)
            assert status == 200, out
            assert out["status"] == "done", out
            assert out["version"]["version"] == 2, out
            assert out["version"]["state"] == "active", out
            assert not errors, f"reload lost {len(errors)}: {errors[:3]}"

            # plane-shaped stats with live cache counters
            with urllib.request.urlopen(base + "/v1/stats",
                                        timeout=60) as r:
                stats = json.loads(r.read())
            assert set(stats) >= {"models", "cache", "plane"}, set(stats)
            assert stats["models"]["lenet5"]["active_version"] == 2
            assert stats["plane"]["promotions"] == 1, stats["plane"]
            cache = stats["cache"]
            assert cache["evictions"] >= 1, cache
            assert cache["spilled_bytes_total"] > 0, cache

            # /metrics: every line parses; the model/cache series exist
            with urllib.request.urlopen(base + "/metrics",
                                        timeout=60) as r:
                text = r.read().decode()
            for line in text.splitlines():
                if not line or line.startswith("#"):
                    continue
                m = _PROM_LINE.match(line)
                assert m, f"bad metric line: {line}"
                float(m.group(2))  # ValueError = unparseable sample
            assert ('dvt_serve_model_up{model="lenet5",state="active",'
                    'version="2"} 1') in text, \
                "missing model_up for the promoted version"
            assert 'dvt_serve_model_up{model="yolov3_toy"' in text
            for series in ("dvt_serve_weight_cache_budget_bytes",
                           "dvt_serve_weight_cache_hits_total",
                           "dvt_serve_weight_cache_evictions_total",
                           "dvt_serve_reloads_total",
                           "dvt_serve_promotions_total"):
                assert series in text, f"missing {series}"
            print(f"model-smoke PASS: 2 models on a "
                  f"{args.hbm_budget_mb} MiB budget from port "
                  f"{server.port}; reload under load promoted v2 with "
                  f"{served[0]} client requests and 0 errors; cache "
                  f"hits={cache['hits']} misses={cache['misses']} "
                  f"evictions={cache['evictions']} "
                  f"spilled={cache['spilled_bytes_total']}B; "
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
