"""Observability contract (CPU, tier-1 fast): per-request spans whose
breakdown sums exactly to the measured total, request-id propagation
across a REAL gateway→backend hop, Prometheus text that parses line by
line, fleet histogram merging that matches a recomputation, serving-MFU
sanity under load, and structured JSON-line logging.

Uses LeNet at random init like test_serve.py: observability is about
plumbing, not learned weights."""

import json
import logging
import re
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from deep_vision_tpu.core.metrics import LatencyHistogram, PromText
from deep_vision_tpu.obs.log import configure_logging, event, get_logger
from deep_vision_tpu.obs.mfu import MfuMeter
from deep_vision_tpu.obs.trace import REQUEST_ID_HEADER, Span, Tracer
from deep_vision_tpu.serve.engine import BatchingEngine
from deep_vision_tpu.serve.registry import ModelRegistry

pytestmark = pytest.mark.obs


@pytest.fixture(scope="module")
def lenet_serving(tmp_path_factory):
    reg = ModelRegistry()
    # empty workdir fixture → deterministic PRNGKey(0) random init
    sm = reg.load_checkpoint(
        "lenet5", str(tmp_path_factory.mktemp("lenet_workdir")))
    return reg, sm


def _images(n, shape=(32, 32, 1)):
    return [np.random.RandomState(i).randn(*shape).astype(np.float32)
            for i in range(n)]


# -- Prometheus text format -------------------------------------------------

_SAMPLE_RE = re.compile(
    r"([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (\S+)")
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _parse_prom(text: str) -> dict:
    """Validate EVERY line of a text exposition; return
    ``{name: {frozenset(labels): value}}``."""
    assert text.endswith("\n"), "exposition must end with a newline"
    samples: dict = {}
    typed: set = set()
    for line in text.splitlines():
        assert line == line.strip() and line, f"blank/padded line {line!r}"
        if line.startswith("# TYPE "):
            _, _, name, typ = line.split(" ", 3)
            assert typ in ("counter", "gauge", "histogram"), line
            assert name not in typed, f"duplicate TYPE for {name}"
            typed.add(name)
            continue
        if line.startswith("# HELP "):
            assert len(line.split(" ", 3)) == 4, line
            continue
        assert not line.startswith("#"), f"unknown comment {line!r}"
        m = _SAMPLE_RE.fullmatch(line)
        assert m, f"unparseable sample line {line!r}"
        name, rawlabels, value = m.groups()
        labels = {}
        if rawlabels:
            inner = rawlabels[1:-1]
            labels = dict(_LABEL_RE.findall(inner))
            # nothing between the matched pairs but commas
            assert _LABEL_RE.sub("", inner).strip(",") == "", line
        v = float("inf") if value == "+Inf" else float(value)
        samples.setdefault(name, {})[
            frozenset(labels.items())] = v
        # every sample's base name must have a TYPE declaration
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        assert name in typed or base in typed, f"untyped sample {name}"
    return samples


def test_prom_text_rendering():
    p = PromText()
    p.counter("t_total", 3, {"model": "m"}, help="a counter")
    p.counter("t_total", 4, {"model": 'q"uote\n'})  # HELP/TYPE once
    p.gauge("t_gauge", 0.25, help="a gauge")
    p.gauge("t_skipped", None)  # None samples are absent, never 0
    text = p.render()
    samples = _parse_prom(text)
    assert samples["t_total"][frozenset({("model", "m")})] == 3
    assert samples["t_gauge"][frozenset()] == 0.25
    assert "t_skipped" not in samples
    assert text.count("# TYPE t_total counter") == 1


def test_prom_histogram_cumulative_buckets():
    h = LatencyHistogram()
    obs = [1e-5, 1e-3, 1e-2, 1e-2, 5e3]  # underflow + overflow included
    for s in obs:
        h.record(s)
    p = PromText()
    p.histogram("lat_seconds", h.state_dict(), {"model": "m"},
                help="latency")
    samples = _parse_prom(p.render())
    buckets = [(dict(k).get("le"), v)
               for k, v in samples["lat_seconds_bucket"].items()]
    # every edge emitted, cumulative counts non-decreasing, +Inf = total
    assert len(buckets) == len(h.edges) + 1
    ordered = sorted(buckets, key=lambda kv: float(kv[0]))
    values = [v for _, v in ordered]
    assert values == sorted(values)
    assert values[0] >= 1  # the underfow observation folds into edge 0
    assert values[-1] == len(obs)  # +Inf parses as inf → sorts last
    assert samples["lat_seconds_count"][
        frozenset({("model", "m")})] == len(obs)
    assert samples["lat_seconds_sum"][
        frozenset({("model", "m")})] == pytest.approx(sum(obs))


def test_histogram_merge_matches_recompute():
    """The gateway's fleet-p99 contract: merging per-backend histogram
    states must give the SAME quantiles as one histogram that saw every
    observation directly."""
    rng = np.random.RandomState(0)
    a, b, ref = (LatencyHistogram(), LatencyHistogram(),
                 LatencyHistogram())
    for s in rng.lognormal(-4, 1, 500):
        a.record(s)
        ref.record(s)
    for s in rng.lognormal(-2, 0.5, 300):
        b.record(s)
        ref.record(s)
    merged = LatencyHistogram()
    merged.load_state_dict(a.state_dict())
    merged.merge(b.state_dict())
    assert merged.total == ref.total == 800
    mp, rp = merged.percentiles(), ref.percentiles()
    for k in ("p50_ms", "p95_ms", "p99_ms", "count"):
        assert mp[k] == rp[k]  # quantiles read from counts: exact
    assert mp["mean_ms"] == pytest.approx(rp["mean_ms"])


# -- spans & tracer ---------------------------------------------------------

def test_span_breakdown_sums_to_total():
    span = Span("rid0", origin="recv")
    for stage in ("decode", "admit", "staging", "compute_d2h",
                  "staging", "respond"):  # a repeated stage accumulates
        time.sleep(0.001)
        span.mark(stage)
    span.note("attempt", "b0")
    d = span.to_dict()
    assert d["request_id"] == "rid0" and d["origin"] == "recv"
    assert set(d["stages"]) == {"decode", "admit", "staging",
                                "compute_d2h", "respond"}
    # the ≥95% accounting criterion holds with equality by construction
    assert sum(d["stages"].values()) == pytest.approx(
        d["total_ms"], abs=0.005)
    assert d["notes"][0]["event"] == "attempt"


def test_span_intervals_tile_the_span_and_count_per_stage():
    """``intervals()`` is the marks read as segments: no hole, no overlap,
    first start = origin, last end = last mark; a stage's ordinal is its
    count so far, so a producer's and a consumer's spans number the same
    batch alike although their stages differ."""
    producer, consumer = Span("producer", "start"), Span("consumer", "start")
    for batch in range(3):
        for stage in ("prep_wait", "assemble", "h2d", "enqueue"):
            producer.mark(stage)
        consumer.mark("stall")
        consumer.mark("dispatch")
        if batch == 2:  # a stage that is not marked for every batch
            consumer.mark("fetch")
        consumer.mark("step")
    for span in (producer, consumer):
        ivs = span.intervals()
        assert len(ivs) == len(span.marks) - 1
        assert ivs[0][2] == span.marks[0][1] and ivs[-1][3] == span.marks[-1][1]
        assert all(a[3] == b[2] for a, b in zip(ivs, ivs[1:]))
        assert all(t0 <= t1 for _, _, t0, t1 in ivs)
        assert sum(t1 - t0 for _, _, t0, t1 in ivs) == pytest.approx(
            span.total_s, abs=1e-9)
    assert [n for st, n, _, _ in producer.intervals() if st == "h2d"] == [0, 1, 2]
    assert [n for st, n, _, _ in consumer.intervals() if st == "stall"] == [0, 1, 2]
    assert [n for st, n, _, _ in consumer.intervals() if st == "dispatch"] == [0, 1, 2]
    assert [n for st, n, _, _ in consumer.intervals() if st == "fetch"] == [0]
    assert Span("empty").intervals() == []


def test_tracer_ring_disable_and_env(monkeypatch):
    tr = Tracer(ring=4)
    for i in range(10):
        tr.finish(tr.start(f"r{i}"))
    s = tr.summary()
    assert s["started"] == s["finished"] == 10
    assert s["ring"] == 4 and len(tr.recent(100)) == 4
    tr.finish(None)  # no-op by contract: tracing-off call sites pass None
    assert Tracer(enabled=False).start() is None
    monkeypatch.setenv("DVT_SERVE_TRACE", "0")
    assert not Tracer().enabled
    monkeypatch.delenv("DVT_SERVE_TRACE")
    assert Tracer().enabled


def test_slow_sampler_threshold():
    tr = Tracer(slow_ms=1.0)
    fast = tr.start("fast")
    tr.finish(fast)
    slow = tr.start("slow")
    time.sleep(0.005)
    slow.mark("work")
    tr.finish(slow)
    assert tr.summary()["slow_sampled"] == 1


# -- MFU meter --------------------------------------------------------------

def test_mfu_meter_arithmetic():
    m = MfuMeter(peak=100.0)
    m.set_bucket_flops(8, 50.0, "xla_cost_analysis")
    m.observe(8, images=8, compute_s=1.0)
    m.observe(8, images=4, compute_s=1.0)
    assert m.mfu() == pytest.approx(100.0 / 2.0 / 100.0)
    r = m.report()
    assert r["serving_mfu"] == pytest.approx(0.5)
    assert r["flops_source"] == "xla_cost_analysis"
    assert r["batches"] == 2 and r["images"] == 12
    m.observe(16, images=16, compute_s=0.5)  # bucket with unknown flops
    assert m.report()["unknown_flops_batches"] == 1
    assert MfuMeter(peak=1.0).mfu() is None  # no traffic → no gauge
    merged = MfuMeter.merged_report([m, m])
    assert merged["flops_total"] == 2 * m.report()["flops_total"]
    assert merged["serving_mfu"] == pytest.approx(
        m.report()["serving_mfu"])


def test_unknown_device_has_no_peak():
    """A device outside the table is an error from the lookup and a None
    from the meter — never another chip's peak."""
    from deep_vision_tpu.obs.mfu import peak_tflops

    assert peak_tflops("TPU v5 lite") == 197.0
    with pytest.raises(LookupError, match="cpu"):
        peak_tflops("cpu")
    m = MfuMeter()  # resolves against this process's CPU backend
    m.set_bucket_flops(8, 50.0, "xla_cost_analysis")
    m.observe(8, images=8, compute_s=1.0)
    r = m.report()
    assert r["serving_mfu"] is None and r["peak_flops_per_s"] is None
    assert r["flops_total"] == 50.0


# -- engine span plumbing ---------------------------------------------------

def test_engine_trace_normal_request_stages(lenet_serving):
    _, sm = lenet_serving
    tracer = Tracer(ring=64)
    with BatchingEngine(sm, buckets=[8], max_wait_ms=250,
                        tracer=tracer) as eng:
        for f in [eng.submit(im) for im in _images(8)]:
            assert f.result(60) is not None
    s = tracer.summary()
    assert s["started"] == s["finished"] == 8
    for trace in tracer.recent(8):
        assert set(trace["stages"]) >= {
            "admit", "queue_wait", "batch_form", "staging",
            "h2d_dispatch", "compute_d2h"}
        assert sum(trace["stages"].values()) == pytest.approx(
            trace["total_ms"], abs=0.005)


def test_engine_trace_shed_is_noted(lenet_serving):
    from deep_vision_tpu.serve.admission import Shed

    _, sm = lenet_serving
    tracer = Tracer(ring=16)
    with BatchingEngine(sm, buckets=[4], max_wait_ms=5,
                        tracer=tracer) as eng:
        img = _images(1)[0]
        assert eng.infer(img) is not None  # prime EWMA + compile
        assert isinstance(eng.infer(img, deadline_ms=0.0), Shed)
    shed_traces = [t for t in tracer.recent(16)
                   if any(n["event"] == "shed" for n in t["notes"])]
    assert len(shed_traces) == 1
    assert shed_traces[0]["notes"][0]["detail"].startswith("deadline")


def test_engine_trace_bisect_retry_and_quarantine(lenet_serving):
    from deep_vision_tpu.serve.faults import FaultPlane, Quarantined

    _, sm = lenet_serving
    tracer = Tracer(ring=16)
    with BatchingEngine(sm, buckets=[8],
                        faults=FaultPlane("compute:poison:nth=3"),
                        tracer=tracer) as eng:
        results = [f.result(60) for f in
                   [eng.submit(im) for im in _images(8)]]
    assert isinstance(results[3], Quarantined)
    traces = tracer.recent(16)
    assert len(traces) == 8
    retried = [t for t in traces
               if any(n["event"] == "bisect_retry" for n in t["notes"])]
    assert retried, "no bisect_retry notes on a poisoned cohort"
    quarantined = [t for t in traces
                   if any(n["event"] == "quarantined"
                          for n in t["notes"])]
    assert len(quarantined) == 1
    # innocents that re-executed carry the retry_exec stage AND still
    # account their full timeline
    rescued = [t for t in retried if "retry_exec" in t["stages"]]
    assert rescued
    for t in rescued:
        assert sum(t["stages"].values()) == pytest.approx(
            t["total_ms"], abs=0.005)


def test_engine_serving_mfu_sane_under_load(lenet_serving):
    _, sm = lenet_serving
    with BatchingEngine(sm, buckets=[8], max_wait_ms=2) as eng:
        for wave in range(4):
            for f in [eng.submit(im) for im in _images(8)]:
                assert f.result(60) is not None
        stats = eng.stats()
    mfu = stats["mfu"]
    # the CPU is not in the peak table: FLOPs and seconds are counted,
    # but nothing divides them by another chip's rate
    assert mfu["serving_mfu"] is None
    assert mfu["peak_flops_per_s"] is None
    assert mfu["flops_total"] > 0
    assert mfu["compute_s"] > 0
    assert mfu["flops_source"] in ("xla_cost_analysis",
                                   "params_lower_bound")
    assert mfu["batches"] == stats["batches"]
    assert mfu["flops_by_bucket"].get("8")


# -- HTTP front-end ---------------------------------------------------------

@pytest.fixture()
def serve_stack(lenet_serving):
    from deep_vision_tpu.serve.http import ServeServer

    reg, sm = lenet_serving
    eng = BatchingEngine(sm, buckets=[4], max_wait_ms=2).start()
    srv = ServeServer(reg, {sm.name: eng}, port=0).start_background()
    yield eng, srv, f"http://127.0.0.1:{srv.port}"
    srv.shutdown()
    eng.stop()


def _classify(base, rid=None, debug=False, timeout=60):
    body = json.dumps(
        {"pixels": np.zeros((32, 32, 1)).tolist()}).encode()
    headers = {"Content-Type": "application/json"}
    if rid:
        headers[REQUEST_ID_HEADER] = rid
    url = base + "/v1/classify" + ("?debug=1" if debug else "")
    req = urllib.request.Request(url, data=body, headers=headers)
    t0 = time.monotonic()
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return (r.status, dict(r.headers), json.loads(r.read()),
                (time.monotonic() - t0) * 1e3)


def test_http_debug_trace_and_request_id(serve_stack):
    _, _, base = serve_stack
    status, headers, payload, wall_ms = _classify(
        base, rid="cafe0123deadbeef", debug=True)
    assert status == 200
    assert headers[REQUEST_ID_HEADER] == "cafe0123deadbeef"
    trace = payload["trace"]
    assert trace["request_id"] == "cafe0123deadbeef"
    assert trace["origin"] == "recv"
    assert set(trace["stages"]) >= {"decode", "admit", "queue_wait",
                                    "compute_d2h", "respond"}
    # acceptance: the breakdown accounts ≥95% of the span total (exact
    # by construction) and the span total is within the client's wall
    assert sum(trace["stages"].values()) >= 0.95 * trace["total_ms"]
    assert trace["total_ms"] <= wall_ms
    # a request WITHOUT the header gets a minted id echoed back
    status, headers, payload, _ = _classify(base)
    assert status == 200 and len(headers[REQUEST_ID_HEADER]) == 16
    assert "trace" not in payload  # debug off → clean payload


def test_http_traces_endpoint(serve_stack):
    _, _, base = serve_stack
    _classify(base, rid="feedface00000001")
    with urllib.request.urlopen(base + "/v1/traces?n=8",
                                timeout=60) as r:
        doc = json.loads(r.read())
    assert doc["summary"]["finished"] >= 1
    assert any(t["request_id"] == "feedface00000001"
               for t in doc["traces"])


def test_http_metrics_parse_and_monotonic(serve_stack):
    _, _, base = serve_stack

    def scrape():
        with urllib.request.urlopen(base + "/metrics", timeout=60) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            return _parse_prom(r.read().decode())

    _classify(base)
    first = scrape()
    lab = frozenset({("model", "lenet5")})
    for name in ("dvt_serve_requests_submitted_total",
                 "dvt_serve_requests_served_total",
                 "dvt_serve_batches_total", "dvt_serve_up",
                 "dvt_serve_flops_total", "dvt_serve_compute_seconds_total",
                 "dvt_serve_traces_finished_total"):
        assert lab in first[name], f"{name} missing model label"
    assert first["dvt_serve_up"][lab] == 1
    assert first["dvt_serve_flops_total"][lab] > 0
    # no peak on record for the CPU → the MFU gauge is absent, not made up
    assert "dvt_serve_mfu" not in first
    assert frozenset({("model", "lenet5"), ("le", "+Inf")}) in \
        first["dvt_serve_request_latency_seconds_bucket"]
    _classify(base)
    # the handler seals its span AFTER replying — poll briefly so the
    # trace counters have landed before comparing scrapes
    monotone = ("dvt_serve_requests_served_total",
                "dvt_serve_batches_total",
                "dvt_serve_traces_finished_total",
                "dvt_serve_compute_seconds_total")
    deadline = time.monotonic() + 5.0
    while True:
        second = scrape()
        if all(second[n][lab] > first[n][lab] for n in monotone) \
                or time.monotonic() > deadline:
            break
        time.sleep(0.01)
    for name in monotone:
        assert second[name][lab] > first[name][lab], \
            f"{name} did not advance"
    assert second["dvt_serve_request_latency_seconds_count"][lab] > \
        first["dvt_serve_request_latency_seconds_count"][lab]


# -- gateway ----------------------------------------------------------------

def test_gateway_request_id_propagates_to_backend(lenet_serving):
    """One id names the whole client→gateway→backend→engine path: sent
    as a header to the gateway, it must come back on the response AND
    appear in the BACKEND's trace ring (a real HTTP hop away)."""
    from deep_vision_tpu.serve.gateway import Gateway, GatewayServer
    from deep_vision_tpu.serve.http import ServeServer

    reg, sm = lenet_serving
    engines = [BatchingEngine(sm, buckets=[4], max_wait_ms=2).start()
               for _ in range(2)]
    servers = [ServeServer(reg, {sm.name: eng}, port=0).start_background()
               for eng in engines]
    gw = Gateway([f"127.0.0.1:{s.port}" for s in servers],
                 probe_interval_s=0.05).start()
    gsrv = GatewayServer(gw, port=0).start_background()
    base = f"http://127.0.0.1:{gsrv.port}"
    try:
        rid = "0123456789abcdef"
        status, headers, payload, _ = _classify(base, rid=rid,
                                                debug=True)
        assert status == 200
        assert headers[REQUEST_ID_HEADER] == rid
        # the backend's own span rode back in the body (?debug=1) …
        assert payload["trace"]["request_id"] == rid
        # … and the gateway attached its proxy-side breakdown
        gtrace = payload["gateway_trace"]
        assert gtrace["request_id"] == rid
        assert "backend_hop" in gtrace["stages"]
        assert any(n["event"] == "attempt" for n in gtrace["notes"])
        # the id crossed the wire: some backend ring holds it
        ring_ids = []
        for eng in engines:
            ring_ids += [t["request_id"] for t in eng.tracer.recent(32)]
        assert rid in ring_ids
        # gateway ring holds it too
        assert rid in [t["request_id"]
                       for t in gw.tracer.recent(32)]
    finally:
        gsrv.shutdown()
        gw.stop()
        for srv in servers:
            srv.shutdown()
        for eng in engines:
            eng.stop()


def test_gateway_stats_merge_and_metrics(lenet_serving):
    """The fleet latency distribution in gateway /v1/stats must equal a
    local recomputation from the per-backend histogram states, and the
    gateway /metrics exposition must parse whole."""
    from deep_vision_tpu.serve.gateway import (Gateway, GatewayServer,
                                               render_gateway_metrics)
    from deep_vision_tpu.serve.http import ServeServer

    reg, sm = lenet_serving
    engines = [BatchingEngine(sm, buckets=[4], max_wait_ms=2).start()
               for _ in range(2)]
    servers = [ServeServer(reg, {sm.name: eng}, port=0).start_background()
               for eng in engines]
    gw = Gateway([f"127.0.0.1:{s.port}" for s in servers],
                 probe_interval_s=0.05).start()
    gsrv = GatewayServer(gw, port=0).start_background()
    base = f"http://127.0.0.1:{gsrv.port}"
    try:
        for _ in range(10):
            status, _, _, _ = _classify(base)
            assert status == 200
        # recompute the fleet histogram from each backend directly …
        expect = None
        for srv in servers:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/v1/stats",
                    timeout=60) as r:
                hist = json.loads(r.read())["lenet5"]["latency_hist"]
            if expect is None:
                expect = LatencyHistogram()
                expect.load_state_dict(hist)
            else:
                expect.merge(hist)
        # … and it must match what the gateway aggregated
        with urllib.request.urlopen(base + "/v1/stats",
                                    timeout=60) as r:
            stats = json.loads(r.read())
        g = stats["gateway"]
        assert g["backend_latency_hist"]["total"] == expect.total >= 10
        assert g["backend_latency"] == expect.percentiles()
        # CPU backends report no peak, so the fleet has no MFU either —
        # the summed numerator and denominator are still there
        assert g["mfu"]["serving_mfu"] is None
        assert g["mfu"]["flops_total"] > 0 and g["mfu"]["compute_s"] > 0
        assert g["latency"]["count"] >= 10  # gateway-side histogram
        # both backends saw probes; at least one served traffic
        assert set(stats["backends"]) == {b.name for b in gw.backends}
        # the full exposition parses, fleet gauges included
        samples = _parse_prom(render_gateway_metrics(gw))
        assert samples["dvt_gateway_proxied_total"][frozenset()] >= 10
        assert samples["dvt_gateway_routable_backends"][
            frozenset()] == 2
        assert "dvt_gateway_serving_mfu" not in samples
        assert frozenset({("le", "+Inf")}) in \
            samples["dvt_gateway_request_latency_seconds_bucket"]
        for b in gw.backends:
            assert samples["dvt_gateway_backend_up"][
                frozenset({("backend", b.name)})] == 1
    finally:
        gsrv.shutdown()
        gw.stop()
        for srv in servers:
            srv.shutdown()
        for eng in engines:
            eng.stop()


def _stub_backend(delay_s: float):
    """Minimal scriptable backend for the hedge-span test."""
    class H(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def _reply(self, status, payload):
            blob = json.dumps(payload).encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(blob)))
            self.end_headers()
            self.wfile.write(blob)

        def do_GET(self):
            self._reply(200, {"status": "ok"})

        def do_POST(self):
            self.rfile.read(
                int(self.headers.get("Content-Length") or 0))
            if delay_s:
                time.sleep(delay_s)
            self._reply(200, {"ok": True})

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd


def test_gateway_hedged_request_span(lenet_serving):
    """A hedged request's span records the hedge and the winner — noted
    from the forwarding thread only, so the trace is complete without
    the pool workers ever touching the span."""
    from deep_vision_tpu.serve.gateway import Gateway

    slow = _stub_backend(delay_s=0.4)
    fast = _stub_backend(delay_s=0.0)
    gw = Gateway([f"127.0.0.1:{slow.server_address[1]}",
                  f"127.0.0.1:{fast.server_address[1]}"],
                 probe_interval_s=0.05, hedge=True,
                 hedge_after_ms=20.0).start()
    try:
        # the round-robin scan starts at backend 0 (the slow one) on an
        # idle fleet, so the first request hedges to the fast one
        status, headers, payload = gw.forward(
            "/v1/classify", b'{"x": 1}', request_id="feedbead00000002")
        assert status == 200
        assert headers[REQUEST_ID_HEADER] == "feedbead00000002"
        assert gw.hedges == 1 and gw.hedge_wins == 1
        trace = gw.tracer.recent(4)[-1]
        assert trace["request_id"] == "feedbead00000002"
        events = [n["event"] for n in trace["notes"]]
        assert events.count("attempt") == 1
        assert "hedge" in events and "hedge_win" in events
        assert {"backend_hop", "respond"} <= set(trace["stages"])
        assert sum(trace["stages"].values()) == pytest.approx(
            trace["total_ms"], abs=0.005)
    finally:
        gw.stop()
        for httpd in (slow, fast):
            httpd.shutdown()
            httpd.server_close()


# -- structured logging -----------------------------------------------------

def test_event_emits_one_json_line(caplog):
    log = get_logger("dvt.serve.testsink")
    with caplog.at_level(logging.INFO, logger="dvt.serve.testsink"):
        event(log, "breaker_open", backend="127.0.0.1:1",
              consecutive_failures=3)
    assert len(caplog.records) == 1
    doc = json.loads(caplog.records[0].getMessage())
    assert doc["event"] == "breaker_open"
    assert doc["logger"] == "dvt.serve.testsink"
    assert doc["backend"] == "127.0.0.1:1"
    assert doc["consecutive_failures"] == 3
    assert isinstance(doc["ts"], float)
    # below-threshold events are guarded out before any JSON encoding
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="dvt.serve.testsink"):
        event(log, "suppressed", level=logging.INFO)
    assert not caplog.records


def test_configure_logging_idempotent():
    root = logging.getLogger("dvt")
    before = list(root.handlers)
    try:
        configure_logging("warning")
        configure_logging("info")  # re-configure: still ONE handler
        ours = [h for h in root.handlers if h not in before]
        assert len(ours) == 1
        assert root.level == logging.INFO
        assert root.propagate is False
    finally:
        for h in list(root.handlers):
            if h not in before:
                root.removeHandler(h)
        root.propagate = True
        root.setLevel(logging.NOTSET)


def test_overload_logging_is_edge_triggered(caplog):
    """A saturated engine must not saturate its own log: one line when
    queue_full shedding starts, one when it clears — not one per shed."""
    from deep_vision_tpu.serve.admission import AdmissionController

    adm = AdmissionController(max_queue=1)
    with caplog.at_level(logging.INFO, logger="dvt.serve.admission"):
        for _ in range(5):
            assert adm.admit(queue_depth=3, deadline=None) is not None
        assert adm.admit(queue_depth=0, deadline=None) is None
    events = [json.loads(r.getMessage())["event"]
              for r in caplog.records]
    assert events == ["overload_shed_start", "overload_cleared"]
    assert adm.stats()["shed_queue_full"] == 5


# -- the launch record (obs/launch.py) ---------------------------------------

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"


def compiled(log, fun: str, t0: float, secs: float, cache: str | None = None):
    """One program through the log's listeners, as JAX fires them: trace,
    lowering, then (the cache's word and its read's duration, then) the
    backend compile."""
    log._time_span(TRACE, t0, t0 + 0.1, fun_name=fun)
    log._time_span(LOWER, t0 + 0.1, t0 + 0.2, fun_name=f"jit({fun})")
    if cache == "hit":
        log._event("/jax/compilation_cache/cache_hits")
        log._duration("/jax/compilation_cache/cache_retrieval_time_sec", secs)
    elif cache == "miss":
        log._event("/jax/compilation_cache/cache_misses")
    log._time_span(BACKEND, t0 + 0.2, t0 + 0.2 + secs, fun_name=f"jit({fun})")


def test_launch_log_mark_and_since_give_what_chip_smoke_reads():
    from deep_vision_tpu.obs.launch import LaunchLog

    log = LaunchLog()
    compiled(log, "apply", 10.0, 3.0, "miss")
    mark = log.mark()
    compiled(log, "train_step", 20.0, 2.5, "miss")
    compiled(log, "eval_step", 30.0, 0.25, "hit")
    compiled(log, "train_step", 40.0, 0.75, "hit")
    compiled(log, "convert_element_type", 50.0, 0.01)
    assert log.since(mark, "train_step") == {
        "programs": 2, "total_s": 3.25, "cache_hits": 2, "cache_misses": 1,
        "seconds": [2.5, 0.75]}
    assert log.since(mark, "apply")["programs"] == 0
    assert log.since(mark) == {
        "programs": 4, "total_s": 3.51, "cache_hits": 2, "cache_misses": 1,
        "half_second_or_more": {"jit(train_step)": [2.5, 0.75]}}
    assert log.since((0, 0, 0))["programs"] == 5
    # what JAX said of the cache rides on the program's compile, and the
    # cache's read is an interval named after the program it served
    by_kind = {}
    for c in log.compiles:
        by_kind.setdefault(c[0], []).append(c)
    assert [c[4] for c in by_kind["backend_compile"]] == [
        "miss", "miss", "hit", "hit", None]
    assert [(c[1], c[4]) for c in by_kind["cache_retrieval"]] == [
        ("jit(eval_step)", "hit"), ("jit(train_step)", "hit")]
    assert all(c[5] == "caller" and c[6] is None for c in log.compiles)


def test_launch_log_keeps_the_outermost_trace():
    from deep_vision_tpu.obs.launch import LaunchLog

    log = LaunchLog()

    def enter(event, t0):  # JAX's record_scalar as an interval begins
        log._enter(event, t0, fun_name="f")

    enter(TRACE, 10.0)                      # train_step {
    enter(TRACE, 10.1)                      #   inner {
    enter(TRACE, 10.2)                      #     sin
    log._time_span(TRACE, 10.2, 10.3, fun_name="sin")
    log._time_span(TRACE, 10.1, 10.5, fun_name="inner")
    enter(TRACE, 10.6)
    log._time_span(TRACE, 10.6, 10.7, fun_name="matmul")
    log._time_span(TRACE, 10.0, 11.0, fun_name="train_step")
    # a lowering rule's own traces fall inside the lowering
    enter(LOWER, 11.1)
    enter(TRACE, 11.2)
    log._time_span(TRACE, 11.2, 11.3, fun_name="add")
    log._time_span(LOWER, 11.1, 11.5, fun_name="jit(train_step)")
    enter(TRACE, 12.0)
    log._time_span(TRACE, 12.0, 12.1, fun_name="later")
    assert [(c[0], c[1]) for c in log.compiles] == [
        ("trace", "train_step"), ("lower", "jit(train_step)"),
        ("trace", "later")]
    # an interval whose beginning was never announced (a listener that
    # registered inside it) is kept, and the count does not go below zero
    log._time_span(TRACE, 13.0, 13.1, fun_name="unannounced")
    assert log.compiles[-1][1] == "unannounced" and log._pending.depth == 0
    log._enter("/jax/some/other/event", 1.0)
    log._time_span("/jax/some/other/event", 1.0, 2.0)
    assert len(log.compiles) == 4


def test_launch_log_bound_counts_what_it_drops():
    from deep_vision_tpu.obs import launch

    log = launch.LaunchLog()
    for i in range(launch.MAX_INTERVALS + 7):
        log._time_span(BACKEND, float(i), i + 0.5, fun_name="jit(f)")
    assert len(log.compiles) == launch.MAX_INTERVALS and log.dropped == 7
    # a full record still names a program that compiles inside the loop
    with log.epoch():
        log.first("first_dispatch")
        log._time_span(BACKEND, 9000.0, 9012.3, fun_name="jit(train_step)")
        assert log.late == [("jit(train_step)", pytest.approx(12.3), None, None)]
    assert log.dropped == 8
    with log.epoch():
        assert log.late == []


def test_launch_log_stages_tile_from_the_process_start():
    from deep_vision_tpu.obs.launch import LaunchLog

    before = time.monotonic()
    log = LaunchLog()
    with log.stage("build"):
        with log.once("backend"):  # a stage inside a stage splits it
            pass
    with log.once("backend"):  # the second time it is no stage
        pass
    with log.stage("init"):
        pass
    for call in range(2):
        with log.epoch():
            log.first("first_dispatch")
            log.first("first_dispatch")  # idempotent within a call
            log.first("first_fetch")
            if call == 1:
                stages = log.stages()  # read while the epoch is open
    assert [s[0] for s in stages] == [
        "outside", "import", "caller", "build", "backend", "build", "caller",
        "init", "caller", "first_dispatch", "first_fetch", "epoch", "caller",
        "first_dispatch", "first_fetch", "epoch"]
    assert all(a[3] == b[2] for a, b in zip(stages, stages[1:]))
    assert all(t0 <= t1 for _, _, t0, t1 in stages)
    # the origin is the OS's start of this process: before this test, and
    # after nothing the interpreter did
    assert stages[0][2] < before and stages[0][2] == log.span.marks[0][1]
    assert stages[-1][3] >= stages[-1][2]
    assert [n for name, n, _, _ in stages if name == "epoch"] == [0, 1]
    assert log.epochs == 2 and not log._open


def test_launch_log_without_proc_starts_at_its_creation(monkeypatch):
    from deep_vision_tpu.obs import launch

    monkeypatch.setattr(launch, "process_start_monotonic", lambda: None)
    before = time.monotonic()
    log = launch.LaunchLog()
    with log.stage("build"):
        pass
    assert [s[0] for s in log.stages()] == ["caller", "build", "caller"]
    assert log.span.marks[0][1] >= before


def test_launch_summary_line_names_what_compiled_under_the_first_step():
    from deep_vision_tpu.obs.launch import LaunchLog

    log = LaunchLog()
    with log.stage("build"):
        pass
    compiled(log, "init", 5.0, 1.0, "hit")
    with log.epoch():
        compiled(log, "train_step", 100.0, 38.0, "miss")
        compiled(log, "convert_element_type", 139.0, 0.01)
        log.first("first_dispatch")
        log.first("first_fetch")
        compiled(log, "late", 200.0, 5.0, "miss")
    line = log.summary()
    assert re.fullmatch(
        r"\[launch\] outside \d+\.\ds import \d+\.\ds build 0\.0s "
        r"caller 0\.0s first step 0\.0s \(compile 38\.4s: jit\(train_step\) "
        r"miss\) first fetch 0\.0s", line), line


def test_launch_listener_registers_once_however_many_trainers(tmp_path):
    import jax
    import jax.numpy as jnp

    from deep_vision_tpu.core.compile_cache import enable_compile_cache
    from deep_vision_tpu.core.config import get_config
    from deep_vision_tpu.core.trainer import Trainer
    from deep_vision_tpu.obs import launch
    from deep_vision_tpu.parallel import make_mesh
    from deep_vision_tpu.tasks.classification import ClassificationTask

    log = launch.start()
    assert launch.start() is log
    cfg = get_config("lenet5")
    mesh = make_mesh(devices=jax.devices()[:1])
    for i in range(3):
        Trainer(cfg, cfg.model(), ClassificationTask(num_classes=10),
                mesh=mesh, workdir=str(tmp_path / str(i)))
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        enable_compile_cache()
        enable_compile_cache()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_min)
    stages = [s[0] for s in log.stages()]
    assert stages.count("build") >= 3 and stages.count("cache") <= 1
    mark = log.mark()
    dropped = log.dropped

    def never_jitted_before(x):
        return jnp.cos(x) * 3.25 + x

    jax.jit(never_jitted_before)(jnp.ones((3, 5))).block_until_ready()
    found = log.since(mark, "never_jitted_before")
    # one listener: the one program is one compile, not one a trainer
    assert found["programs"] + (log.dropped - dropped > 0) == 1
