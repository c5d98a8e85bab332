"""Observability: spans, structured logging, MFU.

Four small, dependency-free pieces.  The serving stack
(``deep_vision_tpu/serve``) threads them through every layer — batcher,
drainer, router, watchdog, prober — without perturbing the clean hot
path (the same discipline as ``faults.py``: one ``enabled``/``is None``
read guards every touch point); the train loop keeps two ``Span``s an
epoch (``data/pipeline.py``: the prefetcher's producer and the loop
itself) and writes their intervals beside a profiler trace
(``Trainer.profile_steps`` → ``spans.jsonl``):

    trace.py  ``Span`` (stage timestamps + hop notes, read back as a
              breakdown or as intervals) and ``Tracer`` (bounded
              in-memory ring of recent traces, a slow-request JSONL
              sampler, per-stage aggregate sums).
              Request ids arrive at the edge (``X-DVT-Request-Id``,
              generated at gateway or backend, propagated via header);
              ``?debug=1`` echoes a request's own breakdown.
    launch.py the process's launch record: one more ``Span`` from the
              process's start through the stages the program marks (the
              package's import, ``enable_compile_cache``, the trainer's
              build and init, every ``train_epoch`` call) and every
              compile as an interval from JAX's own monitoring; an epoch's
              ``[launch]`` / ``[compile]`` lines and, beside a profiled
              epoch's ``spans.jsonl``, ``launch.jsonl``.
    log.py    ``logging``-based structured one-line-JSON events under
              the ``dvt.serve.*`` namespaces (watchdog restarts,
              breaker transitions, quarantines, evacuations each emit
              exactly one line with the request/batch context).
    mfu.py    serving MFU: per-bucket analytic FLOPs (XLA cost
              analysis, with a documented params-based fallback) over
              measured compute-stage seconds against the device peak —
              a ``serving_mfu`` gauge in ``/metrics`` and ``/v1/stats``.

The Prometheus text renderer the ``/metrics`` endpoints use lives in
``core/metrics.py`` (``PromText``) next to ``LatencyHistogram``, whose
fixed shared bin edges are what make cumulative-bucket export and
cross-process merging exact.  Docs: docs/OBSERVABILITY.md.
"""

from deep_vision_tpu.obs.log import configure_logging, event, get_logger
from deep_vision_tpu.obs.mfu import MfuMeter, peak_flops_per_s
from deep_vision_tpu.obs.trace import Span, Tracer, new_request_id

__all__ = ["MfuMeter", "Span", "Tracer", "configure_logging", "event",
           "get_logger", "new_request_id", "peak_flops_per_s"]
