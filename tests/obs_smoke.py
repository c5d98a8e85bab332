"""`make obs-smoke` (runs inside `make serve-smoke`): boot the real
cli.serve wiring on a random port, then assert the observability
surface end to end — /metrics parses as Prometheus text and its
counters advance between scrapes, a ?debug=1 request echoes a
client-chosen X-DVT-Request-Id and returns a span whose stage
breakdown accounts for its whole measured total, /v1/traces serves the
ring — and finally the same through a real gateway hop
(cli.gateway.build_gateway): the id must cross the wire into the
BACKEND's trace ring and the gateway's own /metrics must parse.
Run directly, not under pytest."""

import argparse
import json
import os
import re
import sys
import tempfile
import time
import urllib.request

import numpy as np

# plain script (not pytest): make the repo root importable when invoked
# as `python tests/obs_smoke.py` from the checkout
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


def _get(base, path):
    with urllib.request.urlopen(base + path, timeout=60) as r:
        blob = r.read()
        return r.status, dict(r.headers), blob


def _classify(base, rid=None, debug=False):
    body = json.dumps({"pixels": np.zeros((32, 32, 1)).tolist()}).encode()
    headers = {"Content-Type": "application/json"}
    if rid:
        headers["X-DVT-Request-Id"] = rid
    url = base + "/v1/classify" + ("?debug=1" if debug else "")
    req = urllib.request.Request(url, data=body, headers=headers)
    with urllib.request.urlopen(req, timeout=60) as r:
        return r.status, dict(r.headers), json.loads(r.read())


def main():
    from deep_vision_tpu.cli.gateway import build_gateway
    from deep_vision_tpu.cli.serve import build_server

    with tempfile.TemporaryDirectory() as workdir:
        args = argparse.Namespace(
            model="lenet5", workdir=workdir, stablehlo=None,
            host="127.0.0.1", port=0, max_batch=4, max_wait_ms=2.0,
            buckets=None, max_queue=64, warmup=False, verbose=False,
            pipeline_depth=2, faults="", fault_seed=0,
            serve_devices=1, shard_batches=False,
            wire_dtype="float32", infer_dtype="float32")
        engine, server = build_server(args)
        server.start_background()
        base = f"http://{server.host}:{server.port}"
        gw = gsrv = None
        try:
            # -- span + request id on the backend itself --
            rid = "0bs5m0ke00000001"
            status, headers, payload = _classify(base, rid=rid, debug=True)
            assert status == 200, status
            assert headers["X-DVT-Request-Id"] == rid, headers
            trace = payload["trace"]
            assert trace["request_id"] == rid, trace
            covered = sum(trace["stages"].values())
            assert covered >= 0.95 * trace["total_ms"], trace
            # -- /metrics parses and advances between scrapes --
            status, headers, blob = _get(base, "/metrics")
            assert status == 200, status
            assert headers["Content-Type"].startswith("text/plain"), headers
            first = parse_metrics(blob.decode())
            lab = '{model="lenet5"}'
            assert first["dvt_serve_up"][lab] == 1, first["dvt_serve_up"]
            _classify(base)
            # the handler seals its span AFTER replying, so give the
            # trace counter a moment to land before comparing scrapes
            monotone = ("dvt_serve_requests_served_total",
                        "dvt_serve_traces_finished_total",
                        "dvt_serve_compute_seconds_total")
            deadline = time.monotonic() + 5.0
            while True:
                second = parse_metrics(_get(base, "/metrics")[2].decode())
                if all(second[n][lab] > first[n][lab] for n in monotone) \
                        or time.monotonic() > deadline:
                    break
                time.sleep(0.01)
            for name in monotone:
                assert second[name][lab] > first[name][lab], name
            # this smoke runs on the CPU, which has no peak on record:
            # FLOPs are counted, the MFU gauge stays absent
            assert second["dvt_serve_flops_total"][lab] > 0
            assert "dvt_serve_mfu" not in second, second["dvt_serve_mfu"]
            # -- the trace ring is served --
            traces = json.loads(_get(base, "/v1/traces?n=8")[2])
            assert any(t["request_id"] == rid for t in traces["traces"]), \
                [t["request_id"] for t in traces["traces"]]
            # -- and the same through a real gateway hop --
            gw, gsrv = build_gateway(argparse.Namespace(
                backend=[f"{server.host}:{server.port}"],
                host="127.0.0.1", port=0, probe_interval_ms=50.0))
            gsrv.start_background()
            gbase = f"http://{gsrv.host}:{gsrv.port}"
            grid = "0bs5m0ke00000002"
            status, headers, payload = _classify(gbase, rid=grid,
                                                 debug=True)
            assert status == 200, status
            assert headers["X-DVT-Request-Id"] == grid, headers
            assert payload["trace"]["request_id"] == grid, payload
            assert payload["gateway_trace"]["request_id"] == grid, payload
            assert "backend_hop" in payload["gateway_trace"]["stages"]
            # the id crossed the wire: the BACKEND's ring holds it
            assert any(t["request_id"] == grid
                       for t in engine.tracer.recent(32))
            gsamples = parse_metrics(_get(gbase, "/metrics")[2].decode())
            assert gsamples["dvt_gateway_proxied_total"][""] >= 1
            assert gsamples["dvt_gateway_routable_backends"][""] == 1
            print(f"obs-smoke PASS: request id {rid} echoed with "
                  f"{covered:.3f}/{trace['total_ms']:.3f} ms accounted "
                  f"({covered / max(trace['total_ms'], 1e-9):.1%}), "
                  f"serve+gateway /metrics parsed "
                  f"({len(second)}+{len(gsamples)} series), "
                  f"no MFU gauge off-chip, id {grid} propagated "
                  f"gateway -> backend ring")
        finally:
            if gsrv is not None:
                gsrv.shutdown()
            if gw is not None:
                gw.stop()
            server.shutdown()
            engine.stop(drain_deadline=5.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
