"""In-process serving subsystem: dynamic micro-batching with deadlines,
load shedding, fault tolerance, and latency metrics over the training
stack's restore path.

    registry.py   checkpoint / StableHLO blob → ServingModel (donated
                  inputs, device-native unblocked outputs)
    engine.py     pipelined background-thread dynamic batcher: bucketed
                  jit cache, reused staging buffers, bounded in-flight
                  window, one bulk D2H per batch; supervised by a
                  watchdog (thread restarts, exec-timeout fast-fail)
                  with bisect-retry poison isolation
    admission.py  deadline-aware load shedding + queue-depth bound
                  (per-bucket exec-time EWMAs, Retry-After hints)
    health.py     heartbeats + the OK → DEGRADED → DEAD state machine
    faults.py     deterministic fault-injection plane (seeded; enabled
                  via --faults / DVT_SERVE_FAULTS; chaos suite:
                  make serve-chaos)
    replicas.py   multi-device serving: N per-device engine replicas
                  behind one queue, least-outstanding-work routing,
                  DEAD-replica evacuation (--serve-devices); the
                  sharded big-batch path pairs registry.for_mesh with
                  engine.sharded_buckets (--shard-batches)
    http.py       stdlib HTTP front-end (/v1/classify, /v1/detect,
                  deep /v1/healthz with 503-on-degraded, /v1/drain
                  zero-downtime shutdown, per-connection socket
                  timeouts, Prometheus-text /metrics, /v1/traces,
                  ?debug=1 per-request timing breakdowns)
    gateway.py    cross-host front tier: proxies /v1/classify|detect
                  over a table of backend serve processes with active
                  healthz probing, per-backend circuit breakers,
                  least-outstanding-work routing, bounded retries with
                  failover (a SIGKILL'd backend loses zero admitted
                  requests), and optional tail hedging

Observability (docs/OBSERVABILITY.md) lives in the sibling
``deep_vision_tpu.obs`` package: per-request spans with request-id
propagation (``X-DVT-Request-Id``, gateway → backend), structured
JSON-line logging under the ``dvt.serve.*`` namespaces, and serving-MFU
accounting (analytic per-bucket FLOPs ÷ measured compute time).  Both
HTTP front-ends export ``GET /metrics`` in Prometheus text format.

Entry points: ``python -m deep_vision_tpu.cli.serve`` (one backend),
``python -m deep_vision_tpu.cli.gateway`` (front tier); architecture
notes: docs/SERVING.md.
"""

from deep_vision_tpu.serve.admission import AdmissionController, Shed
from deep_vision_tpu.serve.engine import BatchingEngine, StagingPool
from deep_vision_tpu.serve.faults import (
    FaultPlane,
    InjectedFault,
    Quarantined,
)
from deep_vision_tpu.serve.gateway import Gateway, GatewayServer
from deep_vision_tpu.serve.health import EngineHealth
from deep_vision_tpu.serve.registry import ModelRegistry, ServingModel
from deep_vision_tpu.serve.replicas import ReplicatedEngine

__all__ = ["AdmissionController", "BatchingEngine", "EngineHealth",
           "FaultPlane", "Gateway", "GatewayServer", "InjectedFault",
           "ModelRegistry", "Quarantined", "ReplicatedEngine",
           "ServingModel", "Shed", "StagingPool"]
