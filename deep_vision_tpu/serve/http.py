"""Stdlib HTTP front-end for the batching engine — zero new dependencies.

Routes (JSON in, JSON out):

    GET  /v1/healthz   DEEP health: per-engine thread liveness,
                       heartbeat ages, last-completed-batch age,
                       consecutive failures, and the OK → DEGRADED →
                       DEAD state machine — 503 when any engine can't
                       serve (single engine: DEGRADED/DEAD; replicated
                       engine: every replica DEAD) so load balancers
                       drain traffic, 200 again after recovery
    GET  /v1/stats     per-model engine stats (latency p50/p95/p99,
                       throughput, shed counts, compile/bucket state,
                       the pipelined executor's overlap block, the
                       ``health`` block: state, failures, retries,
                       quarantines, watchdog restarts — plus the
                       ``mfu`` and ``trace`` observability blocks)
    GET  /metrics      Prometheus text exposition (format 0.0.4) of the
                       same stats: dvt_serve_* counters/gauges, the
                       request-latency histogram as cumulative ``le``
                       buckets, and the ``dvt_serve_mfu`` gauge
                       (docs/OBSERVABILITY.md has the full name table)
    GET  /v1/traces    recent finished request traces from the bounded
                       in-memory ring (``?n=`` caps the count) plus the
                       tracer summary (per-stage time aggregates)
    POST /v1/classify  {"pixels": [[...]] | "image_b64": "...",
                        "model"?, "deadline_ms"?, "top_k"?}
    POST /v1/detect    same inputs + "score_threshold"?; detection
                       models (YOLO, CenterNet) — decode → threshold →
                       top-k → class-wise NMS run ON DEVICE in the
                       fused epilogue, so D2H ships K fixed-size boxes
                       per image, and the reply carries
                       {"num_detections", "detections": [{box, score,
                       class}]} with no padded/invalid rows
    POST /v1/pose      same image inputs; heatmap models (Stacked
                       Hourglass) — the traced on-device epilogue
                       decodes heatmaps to {"keypoints": [{x, y,
                       score}]} (serve/workloads.py)
    POST /v1/generate  generative models: latent-in (DCGAN) bodies
                       carry {"latent": [...]} or {"seed": int}
                       (deterministic host draw); image-in translation
                       (CycleGAN) takes the usual image inputs.  The
                       reply is {"image": {"b64", "shape", "dtype"}} —
                       raw uint8 bytes encoded ON DEVICE by the fused
                       epilogue, so the bulk D2H moves 1 byte/pixel
    POST /v1/models/{name}/classify | /detect | /pose | /generate
                       same bodies with the model named in the PATH —
                       the multi-model route (a body "model" key must
                       match the path or 400).  The verb set derives
                       from the workload registry (serve/workloads.py);
                       unknown verbs 404 with the supported list in
                       the body
    GET  /v1/models    the model table: per name the active version +
                       full version history (step/digest/state) — the
                       control-plane listing when ``cli.serve --models``
                       booted a plane, a flat describe() map otherwise
    POST /v1/models/{name}/reload | /promote | /rollback
                       lifecycle endpoints (control plane required, 503
                       otherwise): reload kicks the background
                       load → shadow → canary walk (body: {"force"?,
                       "wait"?}); promote/rollback override the gates on
                       the in-flight candidate (docs/SERVING.md runbook)
    GET  /v1/deploy/{name}/history
                       the append-only deployment ledger for one model
                       (deploy/history.py): every candidate sighting,
                       gate verdict, promote/rollback/revert — ``?n=``
                       caps the tail (deploy pipeline required, 503
                       otherwise)
    POST /v1/deploy/{name}/revert
                       one-command rollback to the last previously
                       promoted version, through the plane's gated
                       state machine: 200 reverted / 409 while a
                       lifecycle is in flight or nothing to revert to /
                       500 when the restored version fails to boot
                       (docs/DEPLOY.md runbook)
    POST /v1/jobs      offline batch tier (serve/jobs.py): submit a
                       manifest {"items": [<request bodies>], "model"?,
                       "shard_size"?} → 202 with a job handle; the
                       trough-filling scheduler (serve/batch_sched.py)
                       drains it through the engines strictly below
                       interactive traffic.  503 unless the tier is
                       wired (cli.serve --jobs-dir)
    GET  /v1/jobs      job listing (status views, FIFO order)
    GET  /v1/jobs/{id} one job's status: state, shards done, images
    GET  /v1/jobs/{id}/results
                       chunked ndjson stream of the job's completed
                       results — the contiguous shard prefix, one
                       {"index": i, ...} line per item plus a trailing
                       {"status": ...} line; re-issue after completion
                       for the full set (results are durable)
    POST /v1/drain     zero-downtime shutdown hook: healthz flips to
                       503 ``draining`` IMMEDIATELY (so a gateway or
                       load balancer stops routing here), new requests
                       shed 429, and every engine finishes its admitted
                       in-flight work via ``stop(drain_deadline=)``
                       (body: {"drain_deadline_s"?: float, default 10})
                       before the 200 reply — no admitted request fails

Request tracing: every POST carries a request id — the client's
``X-DVT-Request-Id`` header if present (the gateway forwards its own),
else generated here — echoed on the response and stamped on the
request's span.  ``?debug=1`` on classify/detect adds the span's
per-stage timing breakdown to the response body; the same traces land
in the in-memory ring behind ``GET /v1/traces``.

Image payloads: ``pixels`` is an (H, W, C) array in the model's WIRE
dtype — raw 0–255 integers on the uint8 wire (the ``cli.serve``
default; the server normalizes on device), a host-preprocessed float
array on the float32 wire (the machine-to-machine back-compat path).
Non-finite float payloads reject 400 at decode.  ``image_b64`` is a
base64-encoded image file decoded + resized server-side in integer
space; the float32 wire additionally normalizes exactly like
``cli.infer`` (requires PIL).  Shed requests answer 429 with the
shed reason (queue-full sheds add a ``Retry-After`` header) so clients
can retry against another replica; quarantined (poison) requests answer
500 with the isolation detail.  Bodies over ``max_body_bytes`` (default
32 MiB) are rejected 413 before any buffer is allocated.

Each connection carries a socket timeout (``socket_timeout_s``, default
30 s): a client that opens a socket and never sends a request line gets
the connection closed, and one that stalls mid-body gets 408 — either
way a slow-loris can't pin a handler thread forever.

The front-end itself is the selector event loop in ``serve/edge.py``
(HTTP/1.1 keep-alive, pipelining, bounded connections) by default;
``edge=False`` keeps the original thread-per-request
``ThreadingHTTPServer`` as the baseline.  Either
way the routes above run unchanged.  Two optional edge services hook
the inference POST path: a content-addressed response cache
(``serve/cache.py`` — a repeat payload against the same model version
answers without touching the engine) and per-tenant QoS
(``serve/admission.py TenantQoS`` — the ``X-DVT-Tenant`` header maps
to a priority class with a token-bucket quota, checked before the
cache, and a weighted-shedding knee on engine queue pressure, checked
on cache misses only).
"""

from __future__ import annotations

import base64
import io
import json
import math
import threading
import time

from deep_vision_tpu.analysis.sanitizer import new_lock
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs

from deep_vision_tpu.obs.trace import REQUEST_ID_HEADER, new_request_id
from deep_vision_tpu.serve.admission import TENANT_HEADER
from deep_vision_tpu.serve.cache import ResponseCache, payload_digest
from deep_vision_tpu.serve.cascade import base_tier as cascade_base_tier
from deep_vision_tpu.serve.cascade import is_degraded as cascade_degraded
from deep_vision_tpu.serve.edge import (
    _CHUNK_END,
    DEFAULT_MAX_CONNECTIONS,
    EdgeServer,
    _chunk_frame,
)
from deep_vision_tpu.serve.workloads import (
    LIFECYCLE_VERBS,
    WORKLOADS,
    infer_paths,
    infer_verbs,
)

DEFAULT_MAX_BODY_BYTES = 32 * 2**20

#: which cascade tier produced this answer ("front"/"big") — set on
#: every cascaded 200 so clients and the bench can split per-tier
#: latency without a debug span (serve/cascade.py)
TIER_HEADER = "X-DVT-Tier"

#: set ("1") on answers the brownout ladder degraded deliberately — a
#: forced front-tier cascade answer (L2) or a stale response-cache hit
#: (L2).  Clients that care about full quality can retry later; ones
#: that don't get a fast answer instead of a 429 (serve/brownout.py)
DEGRADED_HEADER = "X-DVT-Degraded"


class ServeError(Exception):
    def __init__(self, status: int, message: str,
                 headers: dict | None = None):
        super().__init__(message)
        self.status = status
        self.headers = headers


def _decode_pixels(body: dict, model):
    """Body → one (H, W, C) image in the model's WIRE dtype + layout.

    ``pixels`` lists decode STRAIGHT to the wire dtype (no float64
    detour copy: json gives Python scalars, one ``np.asarray`` lands
    them in uint8 or float32).  ``image_b64`` decodes + resizes in
    integer space; on a uint8 wire the pixels ship raw (the bucket
    program normalizes on device), on a float32 wire the host applies
    the model family's normalization exactly like ``cli.infer``.
    """
    import numpy as np

    wire = np.dtype(getattr(model, "wire_dtype", np.float32))
    if "pixels" in body:
        try:
            x = np.asarray(body["pixels"], wire)
        except (ValueError, TypeError, OverflowError) as e:
            # ragged lists, non-numeric entries, or NaN/Inf → integer
            raise ServeError(400, f"bad pixels payload: {e}") from e
        if x.ndim == 2 and model.input_shape[-1] == 1:
            x = x[..., None]
        if x.shape != model.input_shape:
            raise ServeError(
                400, f"pixels shape {list(x.shape)} != model input "
                     f"{list(model.input_shape)}")
        if wire.kind == "f" and not np.isfinite(x).all():
            # NaN/Inf would propagate through the whole padded batch's
            # outputs — reject at the door, not in the batcher
            raise ServeError(
                400, "pixels contain non-finite values (NaN/Inf)")
        return x
    if "image_b64" in body:
        try:
            from PIL import Image
        except ImportError as e:
            raise ServeError(501, "image_b64 needs PIL on the server; "
                                  "send preprocessed 'pixels'") from e
        raw = base64.b64decode(body["image_b64"])
        size = model.input_shape[0]
        img = Image.open(io.BytesIO(raw))
        if model.input_shape[-1] == 1:
            # grayscale models (LeNet): MNIST-style geometry — resize to
            # size-4 and pad 2px each side, all in uint8
            arr = np.asarray(img.convert("L").resize((size - 4, size - 4)))
            u8 = np.pad(arr, 2)[:size, :size, None]
            if wire.kind == "u":
                return u8  # device prologue scales + standardizes
            from deep_vision_tpu.data.mnist import preprocess

            return preprocess(arr[None])[0][:size, :size]
        arr = np.asarray(img.convert("RGB"))
        if model.task == "classification":
            from deep_vision_tpu.data.transforms import (
                eval_transform,
                eval_transform_u8,
                imagenet_resize_for,
            )

            if wire.kind == "u":
                # same rescale→center-crop geometry, kept uint8
                return np.ascontiguousarray(eval_transform_u8(
                    arr, size, imagenet_resize_for(size)))
            return eval_transform(arr, size, imagenet_resize_for(size))
        # detection/pose/GAN: plain resize, family-specific scaling
        from deep_vision_tpu.data.detection import resize_square

        u8 = resize_square(arr, size)
        if wire.kind == "u":
            return np.asarray(u8, np.uint8)
        if str(model.task).startswith("gan_"):
            # image-in translation (CycleGAN) trained on [-1,1] inputs
            # (make_gan_preprocess); the float wire ships them as-is
            return u8.astype(np.float32) / 127.5 - 1.0
        return u8.astype(np.float32) / 255.0
    raise ServeError(400, "body needs 'pixels' or 'image_b64'")


def render_serve_metrics(stats: dict) -> str:
    """Render serve stats as Prometheus text — both shapes.

    Legacy shape: {model_name: engine.stats()}.  Control-plane shape
    (serve/models.py ``ModelControlPlane.stats()``): {"models": {name:
    {"engine": ..., "versions": [...]}}, "cache": ..., "plane": ...} —
    the plane shape additionally emits ``dvt_serve_model_up`` per
    version and the ``dvt_serve_weight_cache_*`` series.

    No parallel metric registry: the stats dicts stay the single source
    of truth and this snapshots them through ``core.metrics.PromText``
    (docs/OBSERVABILITY.md tabulates every name emitted here).
    """
    from deep_vision_tpu.core.metrics import PromText

    p = PromText()
    _render_edge_metrics(p, stats)
    if isinstance(stats.get("batch"), dict):
        _render_batch_metrics(p, stats["batch"])
    if isinstance(stats.get("cascade"), dict):
        _render_cascade_metrics(p, stats["cascade"])
    if isinstance(stats.get("brownout"), dict):
        _render_brownout_metrics(p, stats["brownout"])
    if isinstance(stats.get("models"), dict):
        for name, entry in stats["models"].items():
            if isinstance(entry.get("engine"), dict):
                _render_engine_metrics(p, name, entry["engine"])
            for v in entry.get("versions", []):
                p.gauge("dvt_serve_model_up",
                        1 if v.get("state") in ("active", "canary",
                                                "shadow") else 0,
                        {"model": name,
                         "version": str(v.get("version")),
                         "state": str(v.get("state"))},
                        help="1 while this model version takes traffic")
        cache = stats.get("cache")
        if isinstance(cache, dict):
            p.gauge("dvt_serve_weight_cache_budget_bytes",
                    cache.get("budget_bytes"), {},
                    help="HBM byte budget (0 = unbounded)")
            p.gauge("dvt_serve_weight_cache_resident_bytes",
                    cache.get("resident_bytes"), {},
                    help="Bytes of model weights resident on device")
            p.counter("dvt_serve_weight_cache_hits_total",
                      cache.get("hits"), {},
                      help="Batch dispatches finding weights resident")
            p.counter("dvt_serve_weight_cache_misses_total",
                      cache.get("misses"), {},
                      help="Dispatches that had to re-admit weights")
            p.counter("dvt_serve_weight_cache_evictions_total",
                      cache.get("evictions"), {},
                      help="LRU evictions (weights spilled to host)")
            p.counter("dvt_serve_weight_cache_admits_total",
                      cache.get("admits"), {},
                      help="Host→device weight re-admissions")
            p.counter("dvt_serve_weight_cache_spilled_bytes_total",
                      cache.get("spilled_bytes_total"), {},
                      help="Bytes D2H-copied at first eviction")
            for mname, ent in (cache.get("models") or {}).items():
                p.gauge("dvt_serve_weight_cache_resident",
                        1 if ent.get("resident") else 0,
                        {"model": mname},
                        help="1 while this model's weights are on device")
        plane = stats.get("plane")
        if isinstance(plane, dict):
            p.counter("dvt_serve_reloads_total", plane.get("reloads"),
                      {}, help="Reload lifecycles started")
            p.counter("dvt_serve_promotions_total",
                      plane.get("promotions"), {},
                      help="Versions auto- or operator-promoted")
            p.counter("dvt_serve_rollbacks_total",
                      plane.get("rollbacks"), {},
                      help="Versions rolled back by gates or operator")
            p.counter("dvt_serve_reload_resubmitted_total",
                      plane.get("resubmitted"), {},
                      help="Requests transparently resubmitted across "
                           "a version swap")
            p.counter("dvt_serve_reverts_total", plane.get("reverts"),
                      {}, help="One-command reverts to a prior "
                               "promoted version")
        dep = stats.get("deploy")
        if isinstance(dep, dict):
            _render_deploy_metrics(p, dep)
        return p.render()
    for name, s in stats.items():
        if name in ("edge", "response_cache", "qos", "batch",
                    "cascade", "brownout"):
            continue  # front-end blocks, rendered above
        _render_engine_metrics(p, name, s)
    return p.render()


def _render_edge_metrics(p, stats: dict) -> None:
    """Emit the front-end tier's series: the selector edge's
    connection counters, the response cache, and per-tenant-class QoS
    (docs/OBSERVABILITY.md tabulates these)."""
    edge = stats.get("edge")
    if isinstance(edge, dict):
        p.gauge("dvt_serve_open_connections",
                edge.get("open_connections"), {},
                help="Sockets currently open on the serving edge")
        p.gauge("dvt_serve_max_connections",
                edge.get("max_connections"), {},
                help="Connection cap (--max-connections)")
        p.counter("dvt_serve_edge_accepted_total", edge.get("accepted"),
                  {}, help="Connections accepted")
        p.counter("dvt_serve_edge_requests_total", edge.get("requests"),
                  {}, help="Requests parsed off edge connections")
        p.counter("dvt_serve_edge_keepalive_reuses_total",
                  edge.get("keepalive_reuses"), {},
                  help="Requests after the first on one connection")
        p.counter("dvt_serve_edge_evicted_idle_total",
                  edge.get("evicted_idle"), {},
                  help="Idle connections evicted to admit new ones")
        p.counter("dvt_serve_edge_accept_pauses_total",
                  edge.get("accept_pauses"), {},
                  help="Times the listener paused at the connection cap")
        p.counter("dvt_serve_edge_timeouts_408_total",
                  edge.get("timeouts_408"), {},
                  help="Stalled-body connections answered 408")
        p.counter("dvt_serve_edge_closed_idle_total",
                  edge.get("closed_idle"), {},
                  help="Idle/slow-loris connections closed silently")
    rcache = stats.get("response_cache")
    if isinstance(rcache, dict):
        p.counter("dvt_serve_cache_hits_total", rcache.get("hits"), {},
                  help="Inference answers served from the response cache")
        p.counter("dvt_serve_cache_misses_total", rcache.get("misses"),
                  {}, help="Cacheable lookups that missed")
        p.counter("dvt_serve_cache_stale_hits_total",
                  rcache.get("stale_hits"), {},
                  help="Brownout-L2 answers served from a retired "
                       "params version (marked X-DVT-Degraded)")
        p.counter("dvt_serve_cache_evictions_total",
                  rcache.get("evictions"), {},
                  help="LRU evictions from the response cache")
        p.counter("dvt_serve_cache_insertions_total",
                  rcache.get("insertions"), {},
                  help="Responses inserted into the cache")
        for tier, n in sorted(
                (rcache.get("insertions_by_tier") or {}).items()):
            p.counter("dvt_serve_cache_tier_insertions_total", n,
                      {"tier": str(tier)},
                      help="Cache inserts by the cascade tier that "
                           "produced the answer (the key itself stays "
                           "tier-agnostic)")
        p.gauge("dvt_serve_cache_bytes", rcache.get("bytes"), {},
                help="Bytes of cached serialized responses")
        p.gauge("dvt_serve_cache_entries", rcache.get("entries"), {},
                help="Entries in the response cache")
    qos = stats.get("qos")
    if isinstance(qos, dict):
        for cls, q in qos.items():
            lab = {"class": cls}
            p.counter("dvt_serve_tenant_served_total", q.get("served"),
                      lab, help="Requests served per tenant class")
            p.counter("dvt_serve_tenant_shed_total", q.get("shed_quota"),
                      {**lab, "reason": "quota"},
                      help="Requests shed by tenant QoS")
            p.counter("dvt_serve_tenant_shed_total",
                      q.get("shed_priority"),
                      {**lab, "reason": "priority"})
            p.counter("dvt_serve_tenant_cache_hits_total",
                      q.get("cache_hits"), lab,
                      help="Cache hits per tenant class")
            lat = q.get("latency") or {}
            for k in ("p50_ms", "p95_ms", "p99_ms"):
                p.gauge("dvt_serve_tenant_latency_seconds",
                        (lat.get(k) or 0.0) / 1e3,
                        {**lab, "quantile": k[1:-3]},
                        help="Per-class request latency quantiles")


def _render_deploy_metrics(p, dep: dict) -> None:
    """Emit the dvt_deploy_* series from ``DeployPipeline.stats()``."""
    hist = dep.get("history") or {}
    p.counter("dvt_deploy_history_records_total", hist.get("records"),
              {}, help="Deployment-ledger records appended")
    p.counter("dvt_deploy_history_write_errors_total",
              hist.get("write_errors"), {},
              help="Ledger appends that failed to reach disk")
    w = dep.get("watcher")
    if isinstance(w, dict):
        p.counter("dvt_deploy_watcher_polls_total", w.get("polls"), {},
                  help="Checkpoint-fingerprint polls")
        p.counter("dvt_deploy_watcher_debounces_total",
                  w.get("debounces"), {},
                  help="Candidates held one interval for stability")
        p.counter("dvt_deploy_deploys_total", w.get("deploys"), {},
                  help="Watcher-initiated rollouts that promoted")
        p.counter("dvt_deploy_gate_failures_total",
                  w.get("gate_failures"), {},
                  help="Candidates refused by the accuracy gate")
    for mname, a in (dep.get("autoscale") or {}).items():
        lab = {"model": mname}
        p.counter("dvt_deploy_scale_ups_total", a.get("scale_ups"),
                  lab, help="Autoscaler replica additions")
        p.counter("dvt_deploy_scale_downs_total", a.get("scale_downs"),
                  lab, help="Autoscaler replica drains")
        p.counter("dvt_deploy_scale_errors_total",
                  a.get("scale_errors"), lab,
                  help="Scale actions that raised (cooldown consumed)")
        p.gauge("dvt_deploy_pressure_ms", a.get("pressure_ms"), lab,
                help="queue_depth × exec EWMA — the scale-up signal")
        if a.get("occupancy") is not None:
            p.gauge("dvt_deploy_occupancy", a.get("occupancy"), lab,
                    help="Engine compute occupancy — the batchy-SLO "
                         "scale-up signal (queue depth misses "
                         "throughput saturation)")


def _render_batch_metrics(p, batch: dict) -> None:
    """Emit the offline batch tier's dvt_batch_* series from the
    ``batch`` stats block (jobs store + trough-filling scheduler +
    occupancy-weighted MFU; docs/BATCH.md tabulates these)."""
    jobs = batch.get("jobs") or {}
    sched = batch.get("scheduler") or {}
    p.counter("dvt_batch_jobs_submitted_total", jobs.get("submitted"),
              {}, help="Bulk jobs accepted via POST /v1/jobs")
    p.counter("dvt_batch_images_total", jobs.get("images_done"), {},
              help="Images with durable batch results (end-to-end "
                   "goodput; replayed checkpoint shards count once)")
    p.counter("dvt_batch_jobs_resumed_total", jobs.get("resumed"), {},
              help="Unfinished jobs resumed from the JSONL checkpoint "
                   "at boot")
    p.counter("dvt_batch_checkpoint_write_errors_total",
              jobs.get("write_errors"), {},
              help="Job-ledger appends that failed to reach disk")
    for state, n in (jobs.get("states") or {}).items():
        p.gauge("dvt_batch_jobs", n, {"state": state},
                help="Jobs by lifecycle state")
    p.counter("dvt_batch_shards_total", sched.get("shards_done"), {},
              help="Shards drained to a durable record this process")
    p.counter("dvt_batch_shards_shed_total", sched.get("shards_shed"),
              {}, help="Whole-shard retries after an engine shed")
    p.counter("dvt_batch_deferred_total", sched.get("deferred"), {},
              help="Trough checks that parked batch work behind "
                   "interactive pressure")
    p.counter("dvt_batch_frozen_deferred_total",
              sched.get("frozen_deferred"), {},
              help="Cohort admissions frozen outright at brownout L1+")
    p.gauge("dvt_batch_occupancy", sched.get("occupancy"), {},
            help="Fraction of the trailing window batch shards kept "
                 "an engine busy (the trough-filling duty cycle)")
    for mname, v in (batch.get("mfu_occupancy_weighted") or {}).items():
        p.gauge("dvt_batch_mfu_weighted", v, {"model": mname},
                help="serving MFU x engine compute occupancy — the "
                     "sustained-throughput MFU a saturating bulk job "
                     "should drive toward the interactive peak")


def _render_cascade_metrics(p, cas: dict) -> None:
    """Emit the dvt_cascade_* series from the reserved ``cascade``
    stats block (serve/cascade.py ``CascadeRouter.stats()``;
    docs/OBSERVABILITY.md tabulates these)."""
    lab = {"front": str(cas.get("front")), "big": str(cas.get("big"))}
    p.counter("dvt_cascade_escalations_total", cas.get("escalations"),
              lab, help="Requests a cheap tier escalated down the "
                        "chain (low confidence, tier errors, and "
                        "deadline-exhausted escalations)")
    for tier, n in sorted((cas.get("served") or {}).items()):
        p.counter("dvt_cascade_requests_total", n,
                  {**lab, "tier": tier},
                  help="Cascade requests answered, by the tier that "
                       "produced the answer")
    p.gauge("dvt_cascade_escalation_rate", cas.get("escalation_rate"),
            lab, help="Of requests the cheap tiers judged, the "
                      "fraction escalated — the live "
                      "cascade-economics gauge")
    # per-HOP threshold/agreement/calibrated series: each hop
    # calibrates tier-i-vs-big independently, so one scalar cannot
    # describe an N-tier chain
    for hop in (cas.get("hops") or []):
        hlab = {**lab, "hop": str(hop.get("hop")),
                "tier": str(hop.get("tier"))}
        p.gauge("dvt_cascade_threshold", hop.get("threshold"), hlab,
                help="Calibrated confidence threshold per hop (absent "
                     "while uncalibrated — fail-closed, that hop "
                     "escalates through)")
        cls_thr = hop.get("class_thresholds") or {}
        # None entries are fail-closed classes (measured-bad) — they
        # have no threshold value to chart
        vals = sorted(v for v in cls_thr.values() if v is not None)
        if vals:
            mid = vals[len(vals) // 2]
            p.gauge("dvt_cascade_class_threshold_min", vals[0], hlab,
                    help="Smallest per-class calibrated threshold at "
                         "this hop (per-class axis active)")
            p.gauge("dvt_cascade_class_threshold_median", mid, hlab,
                    help="Median per-class calibrated threshold at "
                         "this hop")
            p.gauge("dvt_cascade_class_threshold_max", vals[-1], hlab,
                    help="Largest per-class calibrated threshold at "
                         "this hop")
            p.gauge("dvt_cascade_class_thresholds", len(vals), hlab,
                    help="Classes with their own calibrated threshold "
                         "at this hop")
        p.gauge("dvt_cascade_hop_agreement", hop.get("agreement"),
                hlab, help="Tier-vs-big agreement over this hop's "
                           "live calibration sample")
        p.counter("dvt_cascade_hop_escalations_total",
                  hop.get("escalations"), hlab,
                  help="Requests this hop escalated onward")
    p.gauge("dvt_cascade_calibrated",
            1 if cas.get("calibrated") else 0, lab,
            help="1 while hop 0 holds a calibrated threshold")
    p.gauge("dvt_cascade_agreement", cas.get("agreement"), lab,
            help="Hop-0 tier-vs-big agreement over the live "
                 "calibration sample")
    p.counter("dvt_cascade_calibration_samples_total",
              cas.get("samples"), lab,
              help="Dual-run calibration samples taken")
    p.counter("dvt_cascade_forced_big_total", cas.get("forced_big"),
              lab, help="Requests routed straight to the big tier for "
                        "always-big QoS tenants")
    p.counter("dvt_cascade_recalibrations_total", cas.get("resets"),
              lab, help="Calibration drops after a tier version swap")
    p.counter("dvt_cascade_samples_paused_total",
              cas.get("samples_paused"), lab,
              help="Dual-run calibration samples skipped at brownout "
                   "L1+ (optional work shed first)")
    p.counter("dvt_cascade_degraded_served_total",
              cas.get("degraded_served"), lab,
              help="Sub-threshold front answers forced at brownout L2 "
                   "(marked X-DVT-Degraded)")
    p.gauge("dvt_cascade_restored",
            1 if cas.get("restored") else 0, lab,
            help="1 when this boot's calibration was restored from "
                 "the persisted ledger")
    p.counter("dvt_cascade_ledger_write_errors_total",
              cas.get("ledger_write_errors"), lab,
              help="Calibration-ledger appends that failed to reach "
                   "disk")
    for tier, hist in (cas.get("latency_hist") or {}).items():
        if hist:
            p.histogram("dvt_cascade_latency_seconds", hist,
                        {**lab, "tier": tier},
                        help="End-to-end cascade request latency by "
                             "answering tier (escalations land in "
                             "'big' and include the front attempt)")


def _render_brownout_metrics(p, bo: dict) -> None:
    """Emit the dvt_brownout_* series from the reserved ``brownout``
    stats block (serve/brownout.py ``BrownoutController.stats()``;
    docs/OBSERVABILITY.md tabulates these)."""
    p.gauge("dvt_brownout_level", bo.get("level"), {},
            help="Degradation ladder level: 0 normal, 1 shed-optional, "
                 "2 degrade-quality, 3 hard-shed")
    p.gauge("dvt_brownout_forced",
            -1 if bo.get("forced") is None else bo.get("forced"), {},
            help="Operator-pinned level (-1 = signals in control)")
    p.counter("dvt_brownout_transitions_total",
              bo.get("transitions_up"), {"direction": "up"},
              help="Edge-triggered ladder level changes")
    p.counter("dvt_brownout_transitions_total",
              bo.get("transitions_down"), {"direction": "down"})
    for lvl, n in sorted((bo.get("level_entries") or {}).items()):
        p.counter("dvt_brownout_level_entries_total", n,
                  {"level": str(lvl)},
                  help="Times the ladder entered each level going up")
    sig = bo.get("signals") or {}
    p.gauge("dvt_brownout_pressure_ms", sig.get("pressure_ms"), {},
            help="Max queue_depth x bucket exec EWMA across engines — "
                 "the engage signal")
    p.gauge("dvt_brownout_occupancy", sig.get("occupancy"), {},
            help="Max engine compute duty cycle at the last tick")
    p.gauge("dvt_brownout_shed_rate", sig.get("shed_rate"), {},
            help="Admission sheds / offered over the last tick window")
    p.counter("dvt_brownout_ticks_total", bo.get("ticks"), {},
              help="Ladder decisions taken")
    p.counter("dvt_brownout_signal_errors_total",
              bo.get("signal_errors"), {},
              help="Engine signal reads that raised mid-teardown")


def _render_engine_metrics(p, name: str, s: dict) -> None:
    """Emit one engine's dvt_serve_* series (shared by both shapes)."""
    lab = {"model": name}
    if s.get("weight_hbm_bytes") is not None:
        p.gauge("dvt_serve_weight_hbm_bytes", s["weight_hbm_bytes"],
                lab, help="Byte footprint of the served weights "
                          "(int8 models report the quantized size)")
    if s.get("param_shard_bytes") is not None:
        p.gauge("dvt_serve_param_shard_bytes", s["param_shard_bytes"],
                lab, help="PER-CHIP addressable weight bytes (a mesh "
                          "view prices one chip's shard, not the "
                          "global logical size)")
    mesh = s.get("mesh_shape")
    if isinstance(mesh, dict):
        for axis, size in mesh.items():
            p.gauge("dvt_serve_mesh_shape", size,
                    {**lab, "axis": str(axis)},
                    help="Serving mesh axis sizes (data/model); "
                         "absent off-mesh")
    p.counter("dvt_serve_requests_submitted_total", s["submitted"],
              lab, help="Requests entering submit (incl. shed)")
    p.counter("dvt_serve_requests_served_total", s["served"], lab,
              help="Requests served a model output")
    p.counter("dvt_serve_batches_total", s["batches"], lab,
              help="Executed batches (incl. retry executions)")
    p.counter("dvt_serve_compiles_total", s["compiles"], lab,
              help="Bucket program compiles")
    p.counter("dvt_serve_padded_images_total", s["padded_images"],
              lab, help="Pad rows executed beyond live requests")
    p.gauge("dvt_serve_queue_depth", s["queue_depth"], lab,
            help="Requests queued awaiting batch formation")
    routing = s.get("routing")
    if isinstance(routing, dict):
        p.gauge("dvt_serve_replicas", routing.get("replicas"), lab,
                help="Replica slots ever provisioned (append-only)")
        p.gauge("dvt_serve_live_replicas", routing.get("live_replicas"),
                lab, help="Non-retired replicas (the elastic capacity)")
        p.counter("dvt_serve_replicas_added_total",
                  routing.get("replicas_added"), lab,
                  help="Scale-up replica additions")
        p.counter("dvt_serve_replicas_removed_total",
                  routing.get("replicas_removed"), lab,
                  help="Scale-down replica retirements")
    adm = s.get("admission", {})
    h = s.get("health", {})
    p.counter("dvt_serve_shed_total", adm.get("shed_queue_full"),
              {**lab, "reason": "queue_full"},
              help="Requests shed at admission or formation")
    p.counter("dvt_serve_shed_total", adm.get("shed_deadline"),
              {**lab, "reason": "deadline"})
    p.counter("dvt_serve_shed_total", h.get("shed_shutdown"),
              {**lab, "reason": "shutdown"})
    p.counter("dvt_serve_batch_failures_total",
              h.get("batch_failures"), lab,
              help="Dispatched/drained cohorts that raised")
    p.counter("dvt_serve_retry_executions_total",
              h.get("retry_executions"), lab,
              help="Bisect-retry sub-cohort executions")
    p.counter("dvt_serve_quarantined_total", h.get("quarantined"),
              lab, help="Requests isolated as poison")
    p.counter("dvt_serve_exec_timeouts_total",
              h.get("exec_timeouts"), lab,
              help="In-flight windows fast-failed by the watchdog")
    p.counter("dvt_serve_watchdog_restarts_total",
              h.get("watchdog_restarts"), lab,
              help="Worker-thread restarts by supervision")
    p.gauge("dvt_serve_up",
            1 if h.get("can_serve") else 0, lab,
            help="1 while this engine can serve (healthz 200)")
    pipe = s.get("pipeline", {})
    p.gauge("dvt_serve_inflight", pipe.get("inflight"), lab,
            help="Dispatched-but-undrained batches")
    p.gauge("dvt_serve_occupancy", pipe.get("occupancy"), lab,
            help="Compute duty cycle over the trailing window — the "
                 "throughput-workload pressure signal")
    p.counter("dvt_serve_h2d_transfers_total",
              pipe.get("h2d_transfers"), lab,
              help="Staged-batch host-to-device transfers")
    p.counter("dvt_serve_h2d_bytes_total", pipe.get("h2d_bytes"),
              lab, help="Wire-format bytes shipped to the device")
    wl = s.get("workload")
    p.counter("dvt_serve_d2h_bytes_total", pipe.get("d2h_bytes"),
              {**lab, "workload": wl} if wl else lab,
              help="Output bytes the bulk device_get moved back "
                   "(generate's fused uint8 epilogue shrinks this 4x); "
                   "sum by (workload) for the per-workload series")
    for b, ms in (adm.get("exec_ewma_ms_by_bucket") or {}).items():
        p.gauge("dvt_serve_exec_ewma_seconds", ms / 1e3,
                {**lab, "bucket": b},
                help="Per-bucket batch execution EWMA")
    p.gauge("dvt_serve_img_per_sec", s.get("img_per_sec"), lab,
            help="Served images per second (post-warmup)")
    if "latency_hist" in s:
        p.histogram("dvt_serve_request_latency_seconds",
                    s["latency_hist"], lab,
                    help="Submit-to-result latency")
    mfu = s.get("mfu") or {}
    p.gauge("dvt_serve_mfu", mfu.get("serving_mfu"), lab,
            help="Model FLOPs utilization of the compute stage "
                 "(analytic FLOPs / measured compute time / peak)")
    p.counter("dvt_serve_compute_seconds_total",
              mfu.get("compute_s"), lab,
              help="Measured device-occupancy seconds")
    p.counter("dvt_serve_flops_total", mfu.get("flops_total"), lab,
              help="Analytic FLOPs executed")
    tr = s.get("trace") or {}
    p.counter("dvt_serve_traces_started_total", tr.get("started"),
              lab, help="Spans started")
    p.counter("dvt_serve_traces_finished_total", tr.get("finished"),
              lab, help="Spans sealed into the ring")
    p.counter("dvt_serve_slow_traces_total", tr.get("slow_sampled"),
              lab, help="Traces over the slow-request threshold")
    p.counter("dvt_serve_slow_suppressed_total",
              tr.get("slow_suppressed"), lab,
              help="Slow-trace emissions dropped at brownout L1+ "
                   "(ring and stage sums still record)")
    for stage, secs in (tr.get("stage_s_total") or {}).items():
        p.counter("dvt_serve_stage_seconds_total", secs,
                  {**lab, "stage": stage},
                  help="Cumulative per-stage span time")


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    # per-request trace state (set at the top of do_POST)
    _rid = None
    _span = None
    _raw_body = None  # raw payload bytes — the cache's content address
    _tier = None  # cascade tier that answered ("front"/"big")
    _degraded = False  # True when brownout degraded this answer
    # chunked-response state: edge._handle sets _edge_stream on its
    # shim; _reply_stream parks the body generator on _stream for the
    # event loop to pump (serve/edge.py), or drains inline without it
    _edge_stream = False
    _stream = None

    # -- plumbing ----------------------------------------------------------

    def setup(self):
        # StreamRequestHandler applies self.timeout to the connection
        # socket; a timeout on the request line makes the stdlib
        # handle_one_request close the connection, a timeout mid-body
        # raises TimeoutError in do_POST (answered 408 below)
        self.timeout = getattr(self.server, "socket_timeout_s", None)
        super().setup()

    def log_message(self, fmt, *args):  # route access logs off stderr spam
        if self.server.verbose:  # type: ignore[attr-defined]
            super().log_message(fmt, *args)

    def _reply(self, status: int, payload: dict,
               headers: dict | None = None):
        blob = json.dumps(payload).encode()
        self._reply_raw(status, blob, "application/json", headers)

    def _reply_raw(self, status: int, blob: bytes, ctype: str,
                   headers: dict | None = None):
        self.send_response(status)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(blob)))
        if self._rid is not None:
            self.send_header(REQUEST_ID_HEADER, self._rid)
        for k, v in (headers or {}).items():
            self.send_header(k, str(v))
        self.end_headers()
        self.wfile.write(blob)

    def _reply_stream(self, status: int, chunks,
                      ctype: str = "application/x-ndjson",
                      headers: dict | None = None):
        """Chunked-transfer reply: ``chunks`` is an iterator of body
        byte pieces.  Under the selector edge the generator is handed
        to the event loop, which frames and flushes each piece as the
        worker produces it — a result set bigger than any buffer bound
        streams in O(1) memory.  Under the threaded baseline server the
        same frames drain inline to the real socket."""
        self.send_response(status)
        self.send_header("Content-Type", ctype)
        self.send_header("Transfer-Encoding", "chunked")
        if self._rid is not None:
            self.send_header(REQUEST_ID_HEADER, self._rid)
        for k, v in (headers or {}).items():
            self.send_header(k, str(v))
        self.end_headers()
        if getattr(self, "_edge_stream", False):
            self._stream = chunks
            return
        for piece in chunks:
            if piece:
                self.wfile.write(_chunk_frame(piece))
        self.wfile.write(_CHUNK_END)

    def _body(self) -> dict:
        length = int(self.headers.get("Content-Length") or 0)
        if length <= 0:
            raise ServeError(400, "empty body")
        cap = getattr(self.server, "max_body_bytes",
                      DEFAULT_MAX_BODY_BYTES)
        if length > cap:
            # reject BEFORE allocating an attacker-sized buffer; the
            # connection is closed (the unread body would desync keep-alive)
            self.close_connection = True
            raise ServeError(
                413, f"body of {length} bytes exceeds the {cap}-byte cap")
        raw = self._raw_body = self.rfile.read(length)
        try:
            return json.loads(raw)
        except json.JSONDecodeError as e:
            raise ServeError(400, f"bad JSON: {e}") from e

    def _engine(self, body: dict, path_model: str | None = None):
        """Resolve the target model: the PATH param wins (a body
        "model" key must agree or 400); the control plane's routing
        table answers when one is wired, the flat registry otherwise.
        KeyError text passes through as the 404 body — ``e.args[0]``,
        not ``str(e)``, because KeyError's str() wraps the message in
        repr quotes."""
        name = body.get("model")
        if path_model is not None:
            if name is not None and name != path_model:
                raise ServeError(
                    400, f"body model '{name}' contradicts path model "
                         f"'{path_model}'")
            name = path_model
        plane = getattr(self.server, "plane", None)
        try:
            if plane is not None:
                model = plane.resolve(name)
                return model, plane.active_engine(model.name)
            model = self.server.registry.get(name)
        except KeyError as e:
            raise ServeError(404, e.args[0]) from e
        return model, self.server.engines[model.name]

    def _infer_row(self, body: dict, path_model: str | None = None):
        """Shared inference request path: decode → engine → row.  The
        model's workload adapter decodes first (DCGAN reads latent/seed
        from the body); None defers to the generic image decode.  A
        client that omits ``deadline_ms`` gets the workload's SLO-class
        default (generate's is longer — output-dominated batches)."""
        model, engine = self._engine(body, path_model)
        wl = getattr(model, "workload", None)
        if engine.faults.enabled:
            engine.faults.inject("decode")
        x = None
        if wl is not None:
            try:
                x = wl.decode(body, model)
            except ValueError as e:
                raise ServeError(400, str(e)) from e
        if x is None:
            x = _decode_pixels(body, model)
        if self._span is not None:
            self._span.mark("decode")
        deadline_ms = body.get("deadline_ms")
        if deadline_ms is None and wl is not None:
            deadline_ms = wl.slo.deadline_ms
        plane = getattr(self.server, "plane", None)
        cascade = getattr(self.server, "cascade", None)
        if cascade is not None and plane is not None \
                and cascade.serves(model.name):
            # cascade routing: the front tier answers when confident,
            # escalation to the big tier keeps the ORIGINAL deadline
            # budget.  Always-big QoS tenants skip the front entirely.
            qos = getattr(self.server, "qos", None)
            force_big = False
            if qos is not None:
                tenant = self.headers.get(TENANT_HEADER) or ""
                force_big = bool(qos.class_of(tenant).always_big)
            self._tier, result = cascade.infer(
                x, deadline_ms=deadline_ms, span=self._span,
                force_big=force_big)
            if cascade_degraded(self._tier):
                # brownout L2 forced a sub-threshold answer at some
                # hop: the tier header names that tier (it DID answer),
                # the degraded marker carries the quality caveat
                self._tier = cascade_base_tier(self._tier)
                self._degraded = True
        elif plane is not None:
            # plane routing: canary/shadow splits + cross-version
            # resubmission happen behind this call, not per-engine
            result = plane.infer(model.name, x,
                                 deadline_ms=deadline_ms,
                                 span=self._span)
        else:
            result = engine.infer(x, deadline_ms=deadline_ms,
                                  span=self._span)
        from deep_vision_tpu.serve.admission import Shed
        from deep_vision_tpu.serve.faults import Quarantined

        if isinstance(result, Shed):
            headers = None
            if result.retry_after_s:
                headers = {"Retry-After":
                           max(1, math.ceil(result.retry_after_s))}
            raise ServeError(429, f"shed: {result.reason} {result.detail}",
                             headers=headers)
        if isinstance(result, Quarantined):
            raise ServeError(
                500, f"quarantined: {result.reason} {result.detail}")
        return model, result

    @staticmethod
    def _shed_429(shed) -> ServeError:
        headers = None
        if shed.retry_after_s:
            headers = {"Retry-After": max(1, math.ceil(shed.retry_after_s))}
        return ServeError(429, f"shed: {shed.reason} {shed.detail}",
                          headers=headers)

    def _infer_route(self, path: str, body: dict,
                     path_model: str | None, debug: bool) -> bytes:  # dvtlint: hot
        """The inference POST path (every workload verb) with the edge
        services hooked in — returns the serialized 200 body.  Order
        matters:

          1. tenant quota (token bucket) — BEFORE the cache, so a hot
             payload can't make quotas unenforceable;
          2. response cache lookup — a hit returns the byte-identical
             serialized answer, skipping decode + engine + QoS pressure
             (a hit consumes no engine capacity);
          3. weighted shedding on engine queue pressure — misses only;
          4. engine inference, then cache insert — 200s only: every
             shed/quarantine/error path raises BEFORE the put, so a
             transient verdict is never replayed from cache.

        Debug-trace requests bypass the cache both ways (the attached
        span is per-request), and models without a ``params_digest``
        are never cached (no version identity → no safe invalidation).
        """
        span = self._span
        qos = getattr(self.server, "qos", None)
        bo = getattr(self.server, "brownout", None)
        tenant = ""
        t0 = time.monotonic()
        if qos is not None:
            tenant = self.headers.get(TENANT_HEADER) or ""
            shed = qos.check_quota(tenant)
            if shed is not None:
                raise self._shed_429(shed)
        model, engine = self._engine(body, path_model)
        # the verb names the workload; the model's task must serve it —
        # checked BEFORE cache and engine so a mis-verbed request never
        # costs a batch slot (or a poisoned cache entry)
        wl = WORKLOADS[path.rsplit("/", 1)[-1]]
        model_wl = getattr(model, "workload", None)
        if model_wl is not None and model_wl.verb != wl.verb:
            raise ServeError(400, f"'{model.name}' is a {model.task} "
                                  f"model; use /v1/{model_wl.verb}")
        cache = getattr(self.server, "response_cache", None)
        cascade = getattr(self.server, "cascade", None)
        if cascade is not None and not cascade.serves(model.name):
            cascade = None
        key = None
        if cache is not None and not debug \
                and self._raw_body is not None:
            # cascaded models key on the COMBINED front+big digest: a
            # hit is tier-agnostic (either tier's answer satisfies the
            # contract), and a reload of either tier invalidates
            digest = cascade.params_digest() if cascade is not None \
                else getattr(model, "params_digest", None)
            if digest is not None:
                key = ResponseCache.key(
                    path, model.name, digest,
                    str(getattr(model, "wire_dtype", "")),
                    str(getattr(model, "infer_dtype", "")),
                    payload_digest(self._raw_body))
                blob = cache.get(key)
                if blob is None and bo is not None and bo.at_least(2):
                    # brownout L2: an exact miss may still have an
                    # answer under a PRIOR params version — stale but
                    # well-formed beats a 429 when the engine is
                    # saturated; the response carries X-DVT-Degraded
                    blob = cache.get_stale(key)
                    if blob is not None:
                        self._degraded = True
                if blob is not None:
                    self._cache_hit = True
                    if span is not None:
                        span.mark("cache_hit")
                        span.mark("respond")
                    if qos is not None:
                        qos.record_served(
                            tenant, time.monotonic() - t0,
                            cache_hit=True)
                    return blob
        if qos is not None:
            adm = getattr(engine, "admission", None)
            shed = qos.check_pressure(
                tenant, getattr(engine, "queue_depth", 0),
                adm.max_queue if adm is not None else 0,
                floor=bo.qos_pressure_floor() if bo is not None
                else 0.0)
            if shed is not None:
                raise self._shed_429(shed)
        _, row = self._infer_row(body, path_model)
        payload = wl.respond(model, body, row)
        if span is not None:
            span.mark("respond")
            if debug:
                payload["trace"] = span.to_dict()
        blob = json.dumps(payload).encode()
        if key is not None and wl.cacheable(len(blob)):
            # during a canary window plane.infer may have routed this
            # request to the CANDIDATE — filing that answer under the
            # active version's digest would poison the cache, so
            # inserts pause until the canary resolves (for a cascade:
            # a canary on EITHER tier)
            plane = getattr(self.server, "plane", None)
            paused = cascade.canary_active() if cascade is not None \
                else (plane is not None
                      and plane.canary_active(model.name))
            if not paused:
                cache.put(key, blob, tier=self._tier)
        if qos is not None:
            qos.record_served(tenant, time.monotonic() - t0)
        return blob

    # -- routes ------------------------------------------------------------

    def _edge_blocks(self) -> dict:
        """The front-end's own stats blocks ("edge", "response_cache",
        "qos") — present only when the selector edge / cache / QoS are
        wired, so the legacy flat shape stays byte-identical without
        them.  Keys are reserved: no model may be named after them."""
        out = {}
        srv = self.server
        edge_stats = getattr(srv, "stats", None)
        if callable(edge_stats):
            out["edge"] = edge_stats()
        rcache = getattr(srv, "response_cache", None)
        if rcache is not None:
            out["response_cache"] = rcache.stats()
        qos = getattr(srv, "qos", None)
        if qos is not None:
            out["qos"] = qos.stats()
        bo = getattr(srv, "brownout", None)
        if bo is not None:
            out["brownout"] = bo.stats()
        return out

    def _add_batch_block(self, stats: dict) -> None:
        """Attach the offline batch tier's ``batch`` stats block (jobs
        store + scheduler + occupancy-weighted MFU) when the tier is
        wired.  Like "edge", the key is reserved: no model may be named
        "batch".  The weighted MFU multiplies each engine's serving MFU
        (compute-stage efficiency) by its rolling occupancy (how much
        of the wall clock that compute actually filled) — the
        sustained-throughput figure a saturating bulk job should push
        toward the interactive MFU."""
        store = getattr(self.server, "jobs", None)
        if store is None:
            return
        sched = getattr(self.server, "batch_sched", None)
        block = {"jobs": store.stats(),
                 "scheduler": sched.stats() if sched is not None
                 else None}
        models = stats.get("models")
        if isinstance(models, dict):
            eng_stats = {n: e.get("engine") for n, e in models.items()}
        else:
            eng_stats = {n: s for n, s in stats.items()
                         if isinstance(s, dict) and "pipeline" in s}
        from deep_vision_tpu.obs.mfu import round_mfu

        weighted = {}
        for name, s in eng_stats.items():
            if not isinstance(s, dict):
                continue
            mfu = (s.get("mfu") or {}).get("serving_mfu")
            occ = (s.get("pipeline") or {}).get("occupancy")
            if mfu is not None and occ is not None:
                weighted[name] = round_mfu(mfu * occ)
        block["mfu_occupancy_weighted"] = weighted
        stats["batch"] = block

    def _add_cascade_block(self, stats: dict) -> None:
        """Attach the cascade router's reserved ``cascade`` stats block
        (escalation counters, live threshold/agreement, per-tier
        latency) when one is wired.  Like "edge"/"batch", the key is
        reserved: no model may be named "cascade"."""
        cascade = getattr(self.server, "cascade", None)
        if cascade is not None:
            stats["cascade"] = cascade.stats()

    def _models_with_cascade(self, models: dict) -> dict:
        """Annotate /v1/models entries for chain members with the
        router's ``cascade`` block (chain, hop role, threshold source)
        — models outside the chain pass through untouched."""
        cascade = getattr(self.server, "cascade", None)
        if cascade is None:
            return models
        for name, entry in models.items():
            if not isinstance(entry, dict):
                continue
            block = cascade.describe_member(name)
            if block is not None:
                entry["cascade"] = block
        return models

    def _job_results_ndjson(self, job_id: str):
        """The results stream body: one JSON line per completed item
        (contiguous shard prefix, manifest order) and a trailing
        ``{"status": ...}`` line clients use to tell "all results
        delivered" from "drained so far"."""
        store = self.server.jobs
        for idx, item in store.results_items(job_id):
            yield json.dumps({"index": idx, **item}).encode() + b"\n"
        yield json.dumps({"status": store.status(job_id)}).encode() \
            + b"\n"

    def _jobs_get(self, path: str) -> None:
        store = getattr(self.server, "jobs", None)
        if store is None:
            self._reply(503, {"error": "batch jobs are not enabled "
                                       "(cli.serve --jobs-dir ...)"})
            return
        parts = path.split("/")
        if len(parts) == 3:  # /v1/jobs
            self._reply(200, {"jobs": store.jobs()})
            return
        try:
            status = store.status(parts[3])
        except KeyError:
            self._reply(404, {"error": f"no job '{parts[3]}'"})
            return
        if len(parts) == 4:  # /v1/jobs/<id>
            self._reply(200, status)
        elif len(parts) == 5 and parts[4] == "results":
            self._reply_stream(200, self._job_results_ndjson(parts[3]))
        else:
            self._reply(404, {"error": f"no route {self.path}"})

    def _jobs_post(self) -> tuple:
        """POST /v1/jobs → (status, payload): validate the manifest,
        resolve the target model, persist the job, kick the scheduler.
        202: the reply is a job HANDLE — results arrive via the
        trough-filling drain, not this request."""
        store = getattr(self.server, "jobs", None)
        if store is None:
            return 503, {"error": "batch jobs are not enabled "
                                  "(cli.serve --jobs-dir ...)"}
        body = self._body()
        items = body.get("items")
        if not isinstance(items, list) or not items:
            raise ServeError(
                400, "manifest 'items' must be a non-empty list of "
                     "request bodies")
        shard_size = body.get("shard_size")
        if shard_size is not None:
            try:
                shard_size = int(shard_size)
            except (TypeError, ValueError) as e:
                raise ServeError(
                    400, f"bad shard_size: {body['shard_size']!r}") from e
            if shard_size <= 0:
                raise ServeError(400, "shard_size must be >= 1")
        model, _ = self._engine(body)
        wl = getattr(model, "workload", None)
        verb = wl.verb if wl is not None else "classify"
        view = store.submit(model.name, verb, items, shard_size)
        sched = getattr(self.server, "batch_sched", None)
        if sched is not None:
            sched.kick()
        return 202, view

    def _live_engines(self) -> dict:
        """name → the engine taking that model's traffic right now:
        the plane's ACTIVE versions when one is wired (a mid-reload
        candidate never answers healthz), the static dict otherwise."""
        plane = getattr(self.server, "plane", None)
        if plane is not None:
            return plane.active_engines()
        return self.server.engines

    def do_GET(self):
        path, _, query = self.path.partition("?")
        plane = getattr(self.server, "plane", None)
        if path == "/v1/healthz":
            engines = self._live_engines()
            if getattr(self.server, "draining", False):
                # draining outranks engine health: traffic must move
                # away BEFORE the engines finish their in-flight work
                self._reply(503, {"status": "draining",
                                  "models": self.server.registry.names()})
                return
            reports = {name: eng.health_report()
                       for name, eng in engines.items()}
            # each engine decides its own serve-ability: a single
            # engine only while fully OK, a ReplicatedEngine while ANY
            # replica is routable (per-replica states are in its report)
            healthy = all(r.get("can_serve", r["state"] == "ok")
                          for r in reports.values())
            self._reply(200 if healthy else 503,
                        {"status": "ok" if healthy else "unhealthy",
                         "models": self.server.registry.names(),
                         "engines": reports})
        elif path == "/v1/stats":
            deploy = getattr(self.server, "deploy", None)
            if plane is not None:
                stats = plane.stats()
                if deploy is not None:
                    stats["deploy"] = deploy.stats()
                stats.update(self._edge_blocks())
                self._add_batch_block(stats)
                self._add_cascade_block(stats)
                self._reply(200, stats)
                return
            stats = {name: eng.stats()
                     for name, eng in self.server.engines.items()}
            stats.update(self._edge_blocks())
            self._add_batch_block(stats)
            self._add_cascade_block(stats)
            self._reply(200, stats)
        elif path == "/v1/models":
            if plane is not None:
                self._reply(200, {"models": self._models_with_cascade(
                    plane.models())})
                return
            self._reply(200, {"models": self._models_with_cascade({
                name: {"model": self.server.registry.get(name).describe()}
                for name in self.server.registry.names()})})
        elif path == "/metrics":
            if plane is not None:
                stats = plane.stats()
                deploy = getattr(self.server, "deploy", None)
                if deploy is not None:
                    stats["deploy"] = deploy.stats()
            else:
                stats = {name: eng.stats()
                         for name, eng in self.server.engines.items()}
            stats.update(self._edge_blocks())
            self._add_batch_block(stats)
            self._add_cascade_block(stats)
            text = render_serve_metrics(stats)
            self._reply_raw(
                200, text.encode(),
                "text/plain; version=0.0.4; charset=utf-8")
        elif path == "/v1/jobs" or path.startswith("/v1/jobs/"):
            self._jobs_get(path)
        elif path == "/v1/brownout":
            bo = getattr(self.server, "brownout", None)
            if bo is None:
                self._reply(503, {"error": "brownout controller is not "
                                           "enabled (cli.serve "
                                           "--brownout)"})
                return
            self._reply(200, bo.stats())
        elif path == "/v1/traces":
            params = parse_qs(query)
            n = int(params.get("n", ["32"])[0])
            tracer = getattr(self.server, "tracer", None)
            self._reply(200, {
                "traces": tracer.recent(n) if tracer is not None else [],
                "summary": tracer.summary() if tracer is not None
                else None})
        else:
            parts = path.split("/")
            # /v1/deploy/<name>/history: the deployment ledger
            if len(parts) == 5 and parts[1] == "v1" \
                    and parts[2] == "deploy" and parts[4] == "history":
                self._reply(*self._deploy_history(
                    parts[3], parse_qs(query)))
                return
            self._reply(404, {"error": f"no route {self.path}"})

    def do_POST(self):
        path, _, query = self.path.partition("?")
        debug = parse_qs(query).get("debug", ["0"])[0] not in ("", "0")
        # request id: the edge's header wins (a gateway hop forwards its
        # own, keeping one id across the whole path); else minted here
        self._rid = self.headers.get(REQUEST_ID_HEADER) \
            or new_request_id()
        tracer = getattr(self.server, "tracer", None)
        span = self._span = tracer.start(self._rid, origin="recv") \
            if tracer is not None else None
        try:
            if path == "/v1/drain":
                self._reply(200, self._drain())
                return
            if path == "/v1/jobs":
                self._reply(*self._jobs_post())
                return
            if path == "/v1/brownout":
                self._reply(*self._brownout_post())
                return
            path_model = None
            parts = path.split("/")
            # /v1/models/<name>/<verb>: the multi-model and lifecycle
            # routes (the name segment never contains "/")
            if len(parts) == 5 and parts[1] == "v1" \
                    and parts[2] == "models":
                path_model, verb = parts[3], parts[4]
                if verb in LIFECYCLE_VERBS:
                    self._reply(*self._lifecycle(path_model, verb))
                    return
                if verb in infer_verbs():
                    path = f"/v1/{verb}"
            if len(parts) == 5 and parts[1] == "v1" \
                    and parts[2] == "deploy" and parts[4] == "revert":
                self._reply(*self._deploy_revert(parts[3]))
                return
            if path not in infer_paths():
                self._body()  # consistent 400 on empty/oversized bodies
                self._reply(404, {
                    "error": f"no route {self.path}",
                    "supported_verbs": sorted(
                        infer_verbs() + LIFECYCLE_VERBS)})
                return
            body = self._body()
            self._cache_hit = False
            self._tier = None
            self._degraded = False
            blob = self._infer_route(path, body, path_model, debug)
            # X-DVT-Cache lets clients (and the trace bench) split
            # hit/miss latency without a debug span per request;
            # X-DVT-Tier reports which cascade tier answered;
            # X-DVT-Degraded marks brownout-degraded answers
            headers = {}
            if self._cache_hit:
                headers["X-DVT-Cache"] = "hit"
            if self._tier is not None:
                headers[TIER_HEADER] = self._tier
            if self._degraded:
                headers[DEGRADED_HEADER] = "1"
            self._reply_raw(200, blob, "application/json",
                            headers=headers or None)
        except ServeError as e:
            self._reply(e.status, {"error": str(e)}, headers=e.headers)
        except TimeoutError:
            # client stalled mid-body: answer 408 and drop the
            # connection instead of pinning this handler thread
            self.close_connection = True
            self._reply(408, {"error": "timed out reading request body"})
        except Exception as e:  # noqa: BLE001 — surface, don't kill worker
            self._reply(500, {"error": f"{type(e).__name__}: {e}"})
        finally:
            # this handler created the span, so it seals it — error
            # paths included (finish is idempotent and never raises)
            if tracer is not None:
                tracer.finish(span)
            self._span = None
            self._rid = None

    def _drain(self) -> dict:
        """Flip healthz to draining, then finish admitted work.

        The flag flips BEFORE any engine stops so probes see 503 while
        in-flight requests are still completing; draining twice is a
        no-op reply.  An empty body is fine — the route predates the
        body parse precisely so `curl -XPOST .../v1/drain` works."""
        length = int(self.headers.get("Content-Length") or 0)
        body = self._body() if length > 0 else {}
        deadline = float(body.get("drain_deadline_s", 10.0))
        srv = self.server
        with srv.drain_lock:  # type: ignore[attr-defined]  # dvtlint: lock=serve.http.Server.drain_lock
            already = getattr(srv, "draining", False)
            srv.draining = True
            if not already:
                plane = getattr(srv, "plane", None)
                if plane is not None:
                    # the plane drains every version (and joins any
                    # in-flight reload worker) — not just the actives
                    plane.stop(drain_deadline=deadline)
                else:
                    for eng in srv.engines.values():
                        eng.stop(drain_deadline=deadline)
        return {"status": "draining", "already_draining": already,
                "drain_deadline_s": deadline}

    def _brownout_post(self) -> tuple:
        """POST /v1/brownout → (status, payload): the operator
        override.  Body {"force": 0..3} pins the ladder at a level
        (pre-shedding load before a known spike, or testing the
        degraded path in prod); {"force": null} returns control to the
        signals.  The reply is the controller's live stats so the
        operator sees the resulting state in the same exchange."""
        bo = getattr(self.server, "brownout", None)
        if bo is None:
            return 503, {"error": "brownout controller is not enabled "
                                  "(cli.serve --brownout)"}
        body = self._body()
        if "force" not in body:
            raise ServeError(400, "body needs 'force': 0..3 to pin the "
                                  "ladder, null to release")
        force = body["force"]
        if force is not None:
            try:
                force = int(force)
            except (TypeError, ValueError) as e:
                raise ServeError(
                    400, f"bad force level: {body['force']!r}") from e
        bo.force(force)
        return 200, bo.stats()

    def _lifecycle(self, name: str, verb: str) -> tuple:
        """POST /v1/models/<name>/reload|promote|rollback → (status,
        payload).  Control-plane-only routes: a plain engine dict has
        no version table to act on."""
        plane = getattr(self.server, "plane", None)
        if plane is None:
            return 503, {"error": f"/v1/models/{name}/{verb} needs the "
                                  f"model control plane (cli.serve "
                                  f"--models ...)"}
        length = int(self.headers.get("Content-Length") or 0)
        body = self._body() if length > 0 else {}
        try:
            if verb == "reload":
                out = plane.reload(name,
                                   force=bool(body.get("force", False)),
                                   wait=bool(body.get("wait", False)))
            elif verb == "promote":
                out = plane.promote(name)
            else:
                out = plane.rollback(name)
        except KeyError as e:
            return 404, {"error": e.args[0]}
        return (409 if out.get("status") in ("refused", "in_progress")
                else 200), out

    def _deploy_history(self, name: str, params: dict) -> tuple:
        """GET /v1/deploy/<name>/history → (status, payload): the
        ledger tail for one model, 503 without a deploy pipeline."""
        deploy = getattr(self.server, "deploy", None)
        if deploy is None:
            return 503, {"error": f"/v1/deploy/{name}/history needs the "
                                  f"deploy pipeline (cli.serve --watch "
                                  f"or --max-replicas)"}
        n = int(params.get("n", ["0"])[0]) or None
        try:
            entries = deploy.entries(name, n)
        except KeyError as e:
            return 404, {"error": e.args[0]}
        return 200, {"model": name, "entries": entries}

    def _deploy_revert(self, name: str) -> tuple:
        """POST /v1/deploy/<name>/revert → (status, payload): the
        pipeline's status-map contract — reverted 200, a lifecycle in
        flight or nothing to revert to 409, boot failure 500."""
        deploy = getattr(self.server, "deploy", None)
        if deploy is None:
            return 503, {"error": f"/v1/deploy/{name}/revert needs the "
                                  f"deploy pipeline (cli.serve --watch "
                                  f"or --max-replicas)"}
        if int(self.headers.get("Content-Length") or 0) > 0:
            self._body()  # drain: revert takes no parameters
        try:
            out = deploy.revert(name)
        except KeyError as e:
            return 404, {"error": e.args[0]}
        status = out.get("status")
        if status in ("refused", "in_progress"):
            return 409, out
        return (500 if status == "failed" else 200), out

    # response building lives on the workload adapters now
    # (serve/workloads.py respond()) — the old _classify/_detect bodies
    # moved there verbatim when the verb set became registry-driven


class ServeServer:
    """HTTP front-end wired to a registry + one engine per model.

    ``edge=True`` (default) runs the selector event loop from
    ``serve/edge.py`` — keep-alive, pipelining, bounded connections;
    ``edge=False`` keeps the original thread-per-request
    ``ThreadingHTTPServer`` (the baseline).  Both
    carry the same context attributes, so ``self.httpd`` stays the
    single handle tests and the CLI reach through."""

    def __init__(self, registry, engines: dict, host: str = "127.0.0.1",
                 port: int = 0, verbose: bool = False,
                 max_body_bytes: int = DEFAULT_MAX_BODY_BYTES,
                 socket_timeout_s: float | None = 30.0,
                 tracer=None, plane=None, deploy=None, edge: bool = True,
                 max_connections: int = DEFAULT_MAX_CONNECTIONS,
                 http_workers: int = 8, response_cache=None, qos=None,
                 jobs=None, batch_sched=None, cascade=None,
                 brownout=None):
        if edge:
            self.httpd = EdgeServer((host, port), _Handler,
                                    max_connections=max_connections,
                                    workers=http_workers, name="serve")
        else:
            self.httpd = ThreadingHTTPServer((host, port), _Handler)
        self.httpd.registry = registry
        self.httpd.engines = engines
        # model control plane (serve/models.py): when wired, routing /
        # stats / lifecycle endpoints go through it; None keeps the
        # original single-version behaviour byte-for-byte
        self.httpd.plane = plane
        # deploy pipeline (deploy/__init__.py): ledger + watcher +
        # autoscalers behind /v1/deploy/... and the dvt_deploy_* series
        self.httpd.deploy = deploy
        self.httpd.verbose = verbose
        self.httpd.max_body_bytes = max_body_bytes
        self.httpd.socket_timeout_s = socket_timeout_s
        self.httpd.draining = False
        self.httpd.drain_lock = new_lock("serve.http.Server.drain_lock")
        # optional edge services (None = off): the content-addressed
        # response cache and per-tenant QoS, hooked into _infer_route
        self.httpd.response_cache = response_cache
        self.httpd.qos = qos
        # offline batch tier (None = off): the job store behind
        # /v1/jobs and the trough-filling scheduler it kicks
        self.httpd.jobs = jobs
        self.httpd.batch_sched = batch_sched
        # confidence-routed cascade (serve/cascade.py, None = off):
        # requests naming its big model route front-first with
        # calibrated escalation; needs the plane (both tiers live there)
        self.httpd.cascade = cascade
        # brownout ladder (serve/brownout.py, None = off): the request
        # path probes it for the L2 stale-cache/degraded answers and
        # the L3 QoS pressure floor; /v1/brownout exposes force/stats
        self.httpd.brownout = brownout
        if tracer is None:
            # share the first engine's tracer so handler-created spans
            # land in the same ring /v1/traces reads
            for eng in engines.values():
                tracer = getattr(eng, "tracer", None)
                if tracer is not None:
                    break
        self.httpd.tracer = tracer
        self._thread: threading.Thread | None = None

    @property
    def host(self) -> str:
        return self.httpd.server_address[0]

    @property
    def port(self) -> int:
        return self.httpd.server_address[1]

    def serve_forever(self):
        self.httpd.serve_forever()

    def start_background(self) -> "ServeServer":
        self._thread = threading.Thread(target=self.serve_forever,
                                        name="serve-http", daemon=True)
        self._thread.start()
        return self

    def shutdown(self):
        self.httpd.shutdown()
        self.httpd.server_close()
        if self._thread is not None:
            self._thread.join(5.0)
            self._thread = None
