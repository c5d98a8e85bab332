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
