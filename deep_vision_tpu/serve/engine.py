"""Pipelined background-thread dynamic micro-batcher.

The training stack hides host work behind device compute with async
dispatch and a staged prefetcher (the chip idles under 1% of a traced
window, PERF.md §5); the serving stack applies the same
argument to dynamically-formed request batches with a two-stage
pipeline:

  batcher thread   drains the queue up to ``max_batch``/``max_wait_ms``,
                   stages the batch into a REUSED preallocated host
                   buffer for its bucket (no per-batch ``np.zeros``),
                   issues the H2D transfer + compiled program
                   asynchronously (JAX dispatch returns before the
                   device finishes), and hands the in-flight record off;
  drainer thread   waits on completed batches in dispatch order, fetches
                   the WHOLE output pytree with one bulk
                   ``jax.device_get`` per batch (not one device slice
                   per request per leaf), and scatters numpy rows to
                   per-request futures on the host.

A ``pipeline_depth``-bounded semaphore caps dispatched-but-undrained
batches, so batch N+1's formation, staging, and H2D overlap batch N's
device compute while memory stays bounded.  ``pipeline_depth=1`` is the
synchronous mode: the batcher completes each batch inline (same staging
buffers, same single bulk transfer — bit-identical outputs, no overlap).

Bucketing is unchanged from the original engine: batches pad to a small
set of power-of-two buckets so every served shape hits an
already-compiled program (the bucket dict IS the jit cache — a miss is
an explicit, counted compile, never a surprise mid-request trace).
Compiled bucket programs donate their input buffer where the runtime
allows (registry.py), so the padded batch's device allocation is
recycled into the outputs.

Deadline handling is two-phase: admission (``admission.py``) sheds
requests that cannot possibly make their deadline at submit time —
using a per-bucket execution-time EWMA and the current in-flight depth
— and the batcher re-checks at batch-formation time so a request that
expired while queued is dropped rather than executed late.

Fault tolerance (docs/SERVING.md "Failure model & operations"):

  * both worker threads publish **heartbeats** (``serve/health.py``); a
    **watchdog** thread restarts a dead batcher/drainer (bounded by
    ``restart_budget``) and fast-fails the in-flight window when a
    batch's wall age exceeds ``exec_timeout`` = max(floor, k × the
    bucket's exec EWMA), so a hung device call can't park futures
    forever;
  * a dispatched batch that raises doesn't fail all N futures —
    **bisect-retry** re-executes cohort halves (bounded by
    ``retry_budget``, exponential backoff) to quarantine the poison
    request and serve the innocent ones; quarantined requests resolve
    to a structured ``Quarantined`` result;
  * failures feed the engine's OK → DEGRADED → DEAD **state machine**
    (``EngineHealth``), surfaced via ``/v1/healthz`` (503 when not OK)
    and the ``health`` block in stats;
  * ``submit`` before ``start()`` / after ``stop()`` fails fast with
    ``Shed("shutdown")``; ``stop(drain_deadline=...)`` rejects new
    submits immediately but finishes admitted work up to the deadline;
  * a deterministic **fault plane** (``serve/faults.py``, enabled via
    ``--faults`` / ``DVT_SERVE_FAULTS``) injects exceptions, latency,
    hangs, NaN output, poison requests, and thread deaths at each stage
    so all of the above is exercised by the chaos suite
    (``make serve-chaos``) — every injection point guards on
    ``faults.enabled`` first, keeping the no-faults hot path identical.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from concurrent.futures import Future

import numpy as np

from deep_vision_tpu.analysis.sanitizer import new_lock
from deep_vision_tpu.core.metrics import LatencyHistogram, ThroughputMeter
from deep_vision_tpu.obs.log import event, get_logger
from deep_vision_tpu.obs.mfu import MfuMeter
from deep_vision_tpu.obs.trace import Tracer
from deep_vision_tpu.serve.admission import AdmissionController, Shed
from deep_vision_tpu.serve.faults import (
    FaultPlane,
    InjectedFault,
    KillThread,
    Quarantined,
)
from deep_vision_tpu.serve.health import EngineHealth

_log = get_logger("dvt.serve.engine")


def power_of_two_buckets(max_batch: int) -> list[int]:
    """1, 2, 4, ... plus ``max_batch`` itself when it isn't a power of 2."""
    buckets, b = [], 1
    while b < max_batch:
        buckets.append(b)
        b *= 2
    buckets.append(max_batch)
    return buckets


def sharded_buckets(max_batch: int, num_devices: int) -> list[int]:
    """Bucket ladder for the sharded big-batch path (``--shard-batches``
    and mesh serving — pass the DATA-axis size, not the chip count: a
    2×2 data×model mesh splits each batch 2 ways): every bucket a
    multiple of ``num_devices`` so the padded mega-batch lays evenly
    across the mesh's data axis — n, 2n, 4n, ... max."""
    n = max(1, int(num_devices))
    top = max(1, max_batch // n)
    return [n * b for b in power_of_two_buckets(top)]


def device_hbm_headroom() -> int | None:
    """Per-chip free HBM bytes (``bytes_limit - bytes_in_use`` from the
    runtime's memory_stats), advertised through /v1/healthz so the
    gateway's fleet table can place models by capacity.  None where the
    backend doesn't report (host CPU devices) — absence means unknown,
    never zero."""
    import jax

    try:
        stats = jax.local_devices()[0].memory_stats()
        if not stats:
            return None
        limit = stats.get("bytes_limit")
        used = stats.get("bytes_in_use")
        if limit is None or used is None:
            return None
        return int(limit) - int(used)
    except Exception:  # noqa: BLE001 — memory_stats is best-effort, backend-specific
        return None


class _Request:
    __slots__ = ("image", "deadline", "enqueued_at", "future", "poison",
                 "span")

    def __init__(self, image, deadline, enqueued_at, future, poison=False,
                 span=None):
        self.image = image
        self.deadline = deadline
        self.enqueued_at = enqueued_at
        self.future = future
        self.poison = poison
        # obs.trace.Span or None (tracing off): every touch point on
        # the hot path guards on that single None read, faults.py-style
        self.span = span


class _Inflight:
    """One dispatched batch awaiting its bulk D2H + scatter."""

    __slots__ = ("requests", "bucket", "out", "buffer", "dispatched_at",
                 "cancelled", "cancel")

    def __init__(self, requests, bucket, out, buffer, dispatched_at,
                 cancel=None):
        self.requests = requests
        self.bucket = bucket
        self.out = out
        self.buffer = buffer
        self.dispatched_at = dispatched_at
        self.cancelled = False   # watchdog fast-failed this window
        self.cancel = cancel     # Event breaking injected hangs (faults on)


class StagingPool:
    """Per-bucket free-list of preallocated host batch buffers.

    A buffer is checked out at batch formation, pinned for the batch's
    whole device lifetime (the H2D may read it asynchronously), and
    returned after the drainer's bulk fetch — so steady state holds at
    most ``pipeline_depth + 1`` buffers per active bucket, reused
    forever.  ``allocated``/``reused`` make the reuse testable.

    Buffers carry the model's WIRE dtype: a uint8 wire stages (and
    H2D-transfers) 4× fewer bytes per padded batch than the float32
    wire (docs/SERVING.md "Wire format & inference dtype").
    """

    def __init__(self, input_shape: tuple, dtype=np.float32):
        self._input_shape = tuple(input_shape)
        self.dtype = np.dtype(dtype)
        self._free: dict[int, list[np.ndarray]] = {}  # guarded-by: _lock
        self._lock = new_lock("serve.engine.StagingPool._lock")
        self.allocated = 0  # guarded-by: _lock
        self.reused = 0  # guarded-by: _lock

    def acquire(self, bucket: int) -> np.ndarray:
        with self._lock:
            free = self._free.setdefault(bucket, [])
            if free:
                self.reused += 1
                return free.pop()
            self.allocated += 1
        return np.zeros((bucket, *self._input_shape), self.dtype)

    def release(self, bucket: int, buf: np.ndarray):
        with self._lock:
            self._free.setdefault(bucket, []).append(buf)

    def stats(self) -> dict:
        with self._lock:
            return {"allocated": self.allocated, "reused": self.reused,
                    "dtype": str(self.dtype),
                    "pooled": {b: len(v) for b, v in self._free.items()}}


class BatchingEngine:
    """Pipelined dynamic batcher for one ServingModel.

    Use as a context manager or call ``start()``/``stop()``.  ``submit``
    returns a ``concurrent.futures.Future`` resolving to the output
    pytree row (numpy, host-side) for that image, a ``Shed``, or a
    ``Quarantined``; ``infer`` is the blocking convenience wrapper.

    ``pipeline_depth`` bounds dispatched-but-undrained batches: depth 1
    is the strictly synchronous path (complete inline, no drainer
    thread); depth ≥ 2 overlaps batch N+1's formation/staging/H2D with
    batch N's device compute.

    Supervision knobs (all off the hot path — see module docstring):
    ``watchdog_interval_s`` (0 disables the watchdog), ``restart_budget``
    (thread restarts before the engine goes sticky-DEAD),
    ``exec_timeout_k``/``exec_timeout_min_s`` (stuck-batch fast-fail),
    ``retry_budget``/``singleton_retries``/``retry_backoff_ms``
    (bisect-retry isolation), ``degraded_after``/``dead_after`` (state
    machine thresholds), ``faults`` (injection plane; defaults to the
    ``DVT_SERVE_FAULTS`` env spec, disabled when unset).
    """

    def __init__(self, model, *, max_batch: int = 32,
                 max_wait_ms: float = 5.0, buckets: list[int] | None = None,
                 admission: AdmissionController | None = None,
                 pipeline_depth: int = 2,
                 faults: FaultPlane | None = None,
                 watchdog_interval_s: float = 0.05,
                 restart_budget: int = 3,
                 exec_timeout_k: float = 10.0,
                 exec_timeout_min_s: float = 2.0,
                 retry_budget: int = 16,
                 singleton_retries: int = 1,
                 retry_backoff_ms: float = 2.0,
                 retry_backoff_max_ms: float = 100.0,
                 degraded_after: int = 1, dead_after: int = 5,
                 external_batcher: bool = False,
                 rescue=None,
                 tracer: Tracer | None = None,
                 validate_outputs: bool | None = None):
        self.model = model
        if model.fixed_batch is not None:
            # a StableHLO blob serves exactly its traced shapes; an
            # explicitly conflicting bucket list is an operator error —
            # name the exported sizes instead of overriding silently
            available = getattr(model, "bucket_sizes",
                                [model.fixed_batch])
            if buckets and any(b not in available for b in buckets):
                raise ValueError(
                    f"model '{model.name}' was exported with bucket "
                    f"sizes {available}; requested buckets "
                    f"{sorted(buckets)} unavailable — re-export or "
                    f"serve from the checkpoint")
            buckets = buckets or list(available)
        self.buckets = sorted(buckets) if buckets else \
            power_of_two_buckets(max_batch)
        self.max_batch = self.buckets[-1]
        self.max_wait_s = max_wait_ms / 1e3
        self.pipeline_depth = max(1, int(pipeline_depth))
        self.admission = admission or AdmissionController(
            max_wait_ms=max_wait_ms)
        self.latency = LatencyHistogram()
        self.throughput = ThroughputMeter(warmup_steps=1)
        # request tracing + serving-MFU accounting (obs/): the tracer is
        # shared with the HTTP front-end (and across replicas) so one
        # ring holds the whole process's recent traces
        self.tracer = tracer or Tracer()
        self.mfu = MfuMeter()
        # the model's wire format IS the staging/H2D dtype: submit casts
        # to it, pooled buffers allocate in it, the bulk device_put
        # ships it (uint8 wire = 4× fewer staged bytes than float32)
        self.wire_dtype = np.dtype(getattr(model, "wire_dtype",
                                           np.float32))
        self.staging = StagingPool(model.input_shape, self.wire_dtype)
        self.faults = faults or FaultPlane.from_env()
        self.health = EngineHealth(degraded_after=degraded_after,
                                   dead_after=dead_after)
        self.watchdog_interval_s = watchdog_interval_s
        self.restart_budget = restart_budget
        self.exec_timeout_k = exec_timeout_k
        self.exec_timeout_min_s = exec_timeout_min_s
        self.retry_budget = retry_budget
        self.singleton_retries = singleton_retries
        self.retry_backoff_ms = retry_backoff_ms
        self.retry_backoff_max_ms = retry_backoff_max_ms
        # NaN-output validation only costs when the fault plane is live;
        # validate_outputs=False opts out even then (the control plane's
        # canary gate wants a fault-injected "bad" version to SERVE its
        # NaNs so the gate — not the engine — catches them)
        self._validate = self.faults.enabled \
            if validate_outputs is None else bool(validate_outputs)
        # replica mode (serve/replicas.py): the ReplicatedEngine owns
        # the queue + batch formation and feeds formed cohorts through
        # dispatch_cohort(); no batcher thread runs here and the
        # watchdog supervises only the drainer
        self.external_batcher = external_batcher
        # rescue(requests, err) -> bool: offered the still-pending
        # requests of a fast-failed in-flight window BEFORE they get
        # their TimeoutError; True = another replica took them over
        self._rescue = rescue
        self._queue: queue.Queue[_Request] = queue.Queue()
        self._executables: dict = {}
        self._lock = new_lock("serve.engine.BatchingEngine._lock")
        self._stop = threading.Event()
        self._accepting = False
        self._thread: threading.Thread | None = None
        self._drainer: threading.Thread | None = None
        self._watchdog: threading.Thread | None = None
        # in-flight window: acquired at dispatch, released after drain
        self._inflight_sem = threading.BoundedSemaphore(self.pipeline_depth)
        self._inflight_q: queue.Queue[_Inflight | None] = queue.Queue()
        self._inflight = 0  # guarded-by: _lock
        self._forming = 0  # requests the batcher holds but hasn't dispatched
        self._inflight_recs: list[_Inflight] = []  # watchdog visibility; guarded-by: _lock
        self.max_inflight = 0  # guarded-by: _lock
        self.submitted = 0  # guarded-by: _lock
        self.served = 0  # guarded-by: _lock
        self.batches = 0  # guarded-by: _lock
        self.compiles = 0  # guarded-by: _lock
        self.padded_images = 0  # guarded-by: _lock
        self.bulk_transfers = 0  # guarded-by: _lock
        self.bulk_transfer_bytes = 0  # guarded-by: _lock
        # H2D accounting: bytes of staged wire-format batches shipped to
        # the device (the observable 4× win of the uint8 wire) — counted
        # at both the pipelined dispatch and the synchronous retry path
        self.h2d_transfers = 0  # guarded-by: _lock
        self.h2d_bytes = 0  # guarded-by: _lock
        self.h2d_bytes_by_bucket: dict[int, int] = {}  # guarded-by: _lock
        # D2H accounting: bytes the bulk per-batch device_get moved
        # back to the host — the output-side mirror of h2d_bytes.  For
        # the generate workload (uint8 epilogue fused into the bucket
        # programs, serve/workloads.py) this is where the 4× output-
        # wire win shows up; counted at the pipelined drain and the
        # synchronous retry path, same as the H2D pair
        self.d2h_bytes = 0  # guarded-by: _lock
        self.d2h_bytes_by_bucket: dict[int, int] = {}  # guarded-by: _lock
        # fault-tolerance accounting
        self.batch_failures = 0  # guarded-by: _lock
        self.retry_executions = 0  # guarded-by: _lock
        self.quarantined = 0  # guarded-by: _lock
        self.exec_timeouts = 0  # guarded-by: _lock
        self.shed_shutdown = 0  # guarded-by: _lock
        # device-idle accounting (host proxy: wall time with an EMPTY
        # in-flight window between the first dispatch and the last drain)
        self._first_dispatch: float | None = None  # guarded-by: _lock
        self._last_done: float | None = None  # guarded-by: _lock
        self._idle_s = 0.0  # guarded-by: _lock
        # compute-occupancy window: (t_done, busy_s) per executed batch,
        # busy_s being the same compute-stage measurement admission and
        # the MFU meter consume.  A ROLLING gauge (unlike the span-long
        # _idle_s proxy): the batch scheduler's trough maths and the
        # batchy-SLO autoscaler both need "busy lately", not "busy ever"
        self.occupancy_window_s = 10.0
        self._busy_events: deque = deque()  # guarded-by: _lock

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "BatchingEngine":
        if not self._accepting:
            self._stop.clear()
            self.faults.cancel.clear()
            self.health.revive()
            if not self.external_batcher:
                self._thread = threading.Thread(
                    target=self._loop, name=f"batcher-{self.model.name}",
                    daemon=True)
                self._thread.start()
            if self.pipeline_depth > 1:
                self._drainer = threading.Thread(
                    target=self._drain_loop,
                    name=f"drainer-{self.model.name}", daemon=True)
                self._drainer.start()
            if self.watchdog_interval_s > 0:
                self._watchdog = threading.Thread(
                    target=self._watchdog_loop,
                    name=f"watchdog-{self.model.name}", daemon=True)
                self._watchdog.start()
            self._accepting = True
        return self

    def stop(self, timeout: float = 5.0,
             drain_deadline: float | None = None):
        """Stop the engine.  New submits fail fast immediately; with a
        ``drain_deadline`` (seconds) admitted work is finished first —
        whatever hasn't completed by the deadline sheds as shutdown."""
        was_running = self._accepting
        self._accepting = False
        if drain_deadline is not None and was_running:
            t_end = time.monotonic() + drain_deadline
            while time.monotonic() < t_end:
                with self._lock:
                    busy = self._inflight
                if busy == 0 and self._forming == 0 \
                        and self._queue.qsize() == 0:
                    break
                time.sleep(0.005)
        self._stop.set()
        self.faults.cancel.set()  # release any injected hang
        if self._watchdog is not None:
            self._watchdog.join(timeout)
            self._watchdog = None
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
        if self._drainer is not None:
            # batcher has exited: every dispatched batch is already in
            # the drain queue, so the sentinel lands after the last one
            self._inflight_q.put(None)
            self._drainer.join(timeout)
            self._drainer = None
        # anything still queued will never run — tell its caller
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            if not req.future.done():
                req.future.set_result(Shed("shutdown", "engine stopped"))

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    def warmup(self, buckets: list[int] | None = None):
        """Compile ahead of traffic (persisted via core/compile_cache)."""
        import jax

        for b in (buckets or self.buckets):
            jax.block_until_ready(self._compiled(b)(np.zeros(
                (b, *self.model.input_shape), self.wire_dtype)))

    # -- request path ------------------------------------------------------

    def submit(self, image, deadline_ms: float | None = None,
               span=None) -> Future:
        fut: Future = Future()
        # span ownership: a caller-provided span (HTTP front-end) is
        # marked here but finished by its creator; an engine-created
        # span seals itself on ANY terminal path via the future's
        # done-callback (served, shed, quarantined, timed out)
        if span is None and self.tracer.enabled:
            span = self.tracer.start()
            fut.add_done_callback(
                lambda _f, _s=span: self.tracer.finish(_s))
        if not self._accepting:
            # fail fast: nothing drains the queue before start()/after
            # stop(), so enqueueing would park the future forever
            with self._lock:
                self.submitted += 1
                self.shed_shutdown += 1
            if span is not None:
                span.note("shed", "shutdown")
            fut.set_result(Shed(
                "shutdown", "engine is not accepting requests "
                            "(stopped or not started)"))
            return fut
        now = time.monotonic()
        deadline = now + deadline_ms / 1e3 if deadline_ms is not None \
            else None
        with self._lock:
            self.submitted += 1
            inflight = self._inflight
        depth = self._queue.qsize()
        shed = self.admission.admit(
            depth, deadline, now,
            bucket=self._bucket_for(min(depth + 1, self.max_batch)),
            inflight=inflight)
        if shed is not None:
            if span is not None:
                span.note("shed", shed.reason)
            fut.set_result(shed)
            return fut
        self.admission.record_admit()
        poison = self.faults.mark_poison() if self.faults.enabled else False
        if span is not None:
            span.mark("admit")
        # the request rides the WIRE dtype end to end: uint8 clients hand
        # raw pixels straight through to the staged batch (no float copy)
        self._queue.put(_Request(np.asarray(image, self.wire_dtype),
                                 deadline, now, fut, poison, span))
        return fut

    def infer(self, image, deadline_ms: float | None = None,
              timeout: float | None = 30.0, span=None):
        return self.submit(image, deadline_ms, span=span).result(timeout)

    # -- batcher thread (stage + dispatch) ---------------------------------

    def _loop(self):  # dvtlint: hot
        try:
            while not self._stop.is_set():
                self.health.beat("batcher")
                if self.faults.enabled:
                    self.faults.inject("batcher", stop=self._stop)
                try:
                    first = self._queue.get(timeout=0.05)
                except queue.Empty:
                    continue
                if first.span is not None:
                    first.span.mark("queue_wait")
                # non-zero while requests are in hand but not yet in the
                # in-flight window, so stop(drain_deadline=...) can't
                # slip between queue drain and dispatch
                self._forming = 1
                try:
                    batch = [first]
                    drain_until = time.monotonic() + self.max_wait_s
                    while len(batch) < self.max_batch:
                        remaining = drain_until - time.monotonic()
                        if remaining <= 0:
                            break
                        try:
                            req = self._queue.get(timeout=remaining)
                        except queue.Empty:
                            break
                        if req.span is not None:
                            req.span.mark("queue_wait")
                        batch.append(req)
                    self.dispatch_cohort(batch)
                finally:
                    self._forming = 0
        except KillThread:
            return  # injected death: the watchdog notices and restarts

    def dispatch_cohort(self, batch: list[_Request]):  # dvtlint: hot
        """Dispatch an already-formed cohort into this engine's
        pipeline.  The internal batcher calls it after queue drain; in
        replica mode (``external_batcher=True``) the ReplicatedEngine's
        router calls it directly — blocking here while this replica's
        in-flight window is full is the router's backpressure.
        Exceptions are delivered to the cohort's futures, never raised
        (a failed batch must not kill the calling thread)."""
        self._forming = max(self._forming, len(batch))
        try:
            self._dispatch(batch)
        except Exception as e:  # noqa: BLE001 — deliver the failure to waiters, don't kill the caller
            for req in batch:
                if not req.future.done():
                    req.future.set_exception(e)
            self.health.record_failure()
        finally:
            self._forming = 0

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def _compiled(self, bucket: int):
        fn = self._executables.get(bucket)
        if fn is None:
            fn = self.model.compile_bucket(bucket)
            self._executables[bucket] = fn
            with self._lock:
                self.compiles += 1
            # registry attaches the bucket program's analytic FLOPs at
            # compile time (XLA cost analysis, or the documented
            # params-based lower bound) — the serving-MFU numerator
            self.mfu.set_bucket_flops(
                bucket, getattr(fn, "cost_flops", None),
                getattr(fn, "flops_source", None))
        return fn

    def _fill(self, buf: np.ndarray, requests: list[_Request]):
        """Stage a cohort into a pooled buffer: scatter rows, zero the
        stale pad tail (buffers are REUSED, so old rows linger)."""
        n = len(requests)
        for i, req in enumerate(requests):
            buf[i] = req.image
        if n < buf.shape[0]:
            buf[n:] = 0.0

    def _put(self, buf: np.ndarray):
        """H2D transfer honoring the model view's placement: the
        replica's pinned device or the big-batch mesh sharding
        (registry.for_device/for_mesh); None = runtime default.  Both
        the pipelined dispatch and the synchronous retry path transfer
        through here, so they can never diverge on placement."""
        import jax

        return jax.device_put(buf, self.model.placement)

    def _acquire_slot(self) -> bool:
        """Block until an in-flight slot frees (or the engine stops)."""
        while not self._stop.is_set():
            self.health.beat("batcher")
            if self._inflight_sem.acquire(timeout=0.05):
                return True
        return False

    def _dispatch(self, batch: list[_Request]):  # dvtlint: hot
        live = []
        for req in batch:
            expired = self.admission.expired(req.deadline)
            if expired is not None:
                if req.span is not None:
                    req.span.note("shed", "deadline expired in queue")
                req.future.set_result(expired)
            else:
                if req.span is not None:
                    req.span.mark("batch_form")
                live.append(req)
        if not live:
            return
        n = len(live)
        bucket = self._bucket_for(n)
        fn = self._compiled(bucket)  # compile OUTSIDE the in-flight window
        if not self._acquire_slot():
            for req in live:
                req.future.set_result(Shed("shutdown", "engine stopped"))
            return
        buf = self.staging.acquire(bucket)
        try:
            if self.faults.enabled:
                self.faults.inject("staging", stop=self._stop)
            self._fill(buf, live)
            # the staging segment covers compile (first hit only), the
            # in-flight-slot wait (pipeline backpressure) and the buffer
            # fill — everything between formation and the H2D issue
            for req in live:
                if req.span is not None:
                    req.span.mark("staging")
            t0 = time.monotonic()
            if self.faults.enabled:
                self.faults.inject("dispatch", stop=self._stop)
                self.faults.inject("compute", stop=self._stop)
                if self.faults.cohort_poisoned(live):
                    raise InjectedFault(
                        f"poisoned request in cohort of {n}")
            # async H2D + dispatch: jax returns device futures
            # immediately; the staged buffer stays checked out until the
            # drainer is done with the batch, so the transfer may read
            # it at its leisure
            out = fn(self._put(buf))
        except Exception as e:  # noqa: BLE001 — dispatch-side batch failure: free the slot, then isolate

            self.staging.release(bucket, buf)
            self._inflight_sem.release()
            self._cohort_failed(live, e)
            return
        for req in live:
            if req.span is not None:
                req.span.mark("h2d_dispatch")
        rec = _Inflight(live, bucket, out, buf, t0,
                        threading.Event() if self.faults.enabled else None)
        with self._lock:
            self.h2d_transfers += 1
            self.h2d_bytes += buf.nbytes
            self.h2d_bytes_by_bucket[bucket] = \
                self.h2d_bytes_by_bucket.get(bucket, 0) + buf.nbytes
            if self._inflight == 0 and self._last_done is not None:
                self._idle_s += t0 - self._last_done
            if self._first_dispatch is None:
                self._first_dispatch = t0
            self._inflight += 1
            self.max_inflight = max(self.max_inflight, self._inflight)
            self._inflight_recs.append(rec)
        if self.pipeline_depth > 1:
            self._inflight_q.put(rec)
        else:
            self._finish(rec)

    # -- drainer thread (bulk D2H + scatter) -------------------------------

    def _drain_loop(self):  # dvtlint: hot
        try:
            while True:
                self.health.beat("drainer")
                try:
                    rec = self._inflight_q.get(timeout=0.25)
                except queue.Empty:
                    if self._stop.is_set():
                        return
                    continue
                if rec is None:
                    if self._stop.is_set():
                        return  # shutdown sentinel
                    continue  # stale sentinel from a previous stop
                self._finish(rec)
        except KillThread:
            return  # injected death: the watchdog notices and restarts

    def _finish(self, rec: _Inflight):
        try:
            self._complete(rec)
        except Exception as e:  # noqa: BLE001 — completion failure fails the cohort, not the drainer
            self._cohort_failed(rec.requests, e)
        finally:
            self.staging.release(rec.bucket, rec.buffer)
            with self._lock:
                self._inflight -= 1
                try:
                    self._inflight_recs.remove(rec)
                except ValueError:
                    pass
                self._last_done = time.monotonic()
            self._inflight_sem.release()

    def _complete(self, rec: _Inflight):  # dvtlint: hot
        import jax

        mode = None
        if self.faults.enabled:
            mode = self.faults.inject("d2h", stop=self._stop,
                                      cancel=rec.cancel)
        # ONE bulk D2H for the whole output pytree — not a device slice
        # + transfer per request per leaf
        host = jax.device_get(rec.out)  # dvtlint: disable=DVT003 — the single bulk D2H per batch
        if mode == "nan":
            # corrupt only FLOAT leaves: integer outputs (class ids,
            # valid masks) can't hold NaN and _check_outputs skips them
            host = jax.tree_util.tree_map(
                lambda a: np.full_like(np.asarray(a), np.nan)
                if np.asarray(a).dtype.kind == "f" else np.asarray(a),
                host)
        if self._validate:
            self._check_outputs(host)
        if rec.cancelled:
            return  # watchdog already fast-failed these futures
        t_done = time.monotonic()
        n = len(rec.requests)
        # per-batch device occupancy ≈ completion minus the later of its
        # dispatch or the previous batch's completion (under pipelining,
        # dispatch→done includes waiting behind the batch ahead)
        with self._lock:
            busy_from = rec.dispatched_at if self._last_done is None \
                else max(rec.dispatched_at, self._last_done)
            self._busy_events.append((t_done, t_done - busy_from))
            self._prune_busy_locked(t_done)
        self.admission.observe_exec(t_done - busy_from, bucket=rec.bucket)
        # the same device-occupancy measurement is the serving-MFU
        # denominator: compute-stage seconds, not queue or drain wait
        self.mfu.observe(rec.bucket, n, t_done - busy_from)
        nbytes = int(sum(np.asarray(a).nbytes
                         for a in jax.tree_util.tree_leaves(host)))
        with self._lock:
            self.batches += 1
            self.served += n
            self.padded_images += rec.bucket - n
            self.bulk_transfers += 1
            self.bulk_transfer_bytes += nbytes
            self.d2h_bytes += nbytes
            self.d2h_bytes_by_bucket[rec.bucket] = \
                self.d2h_bytes_by_bucket.get(rec.bucket, 0) + nbytes
        self.throughput.update(n)
        for i, req in enumerate(rec.requests):
            self.latency.record(t_done - req.enqueued_at)
            if req.span is not None:
                # marked BEFORE resolving the future: the span's owner
                # (HTTP handler / done-callback) takes over at resolve,
                # so the engine never appends to a span concurrently
                req.span.mark("compute_d2h")
            if not req.future.done():
                req.future.set_result(
                    jax.tree_util.tree_map(lambda a: np.asarray(a)[i],
                                           host))
        self.health.record_success(t_done)

    @staticmethod
    def _check_outputs(host):
        import jax

        for leaf in jax.tree_util.tree_leaves(host):
            arr = np.asarray(leaf)
            if arr.dtype.kind == "f" and np.isnan(arr).any():
                raise InjectedFault("NaN in model output")

    # -- batch-failure isolation (bisect-retry) ----------------------------

    def _cohort_failed(self, requests: list[_Request], err: Exception):
        """A dispatched or drained cohort raised: record the failure,
        then bisect-retry to quarantine the poison request(s) and serve
        the innocent ones.  Runs synchronously in the failing thread —
        off the happy path, bounded by ``retry_budget``."""
        with self._lock:
            self.batch_failures += 1
        self.health.record_failure()
        pending = [r for r in requests if not r.future.done()]
        event(_log, "batch_failure", model=self.model.name,
              cohort=len(requests), pending=len(pending),
              error=f"{type(err).__name__}: {err}")
        if not pending:
            return
        for r in pending:
            if r.span is not None:
                r.span.note("batch_failure", type(err).__name__)
        budget = [self.retry_budget]
        self._isolate(pending, err, budget)

    def _backoff(self, budget: list[int]):
        attempt = self.retry_budget - budget[0]
        delay_ms = min(self.retry_backoff_max_ms,
                       self.retry_backoff_ms * (2 ** max(0, attempt)))
        if delay_ms > 0:
            time.sleep(delay_ms / 1e3)

    def _isolate(self, cohort: list[_Request], err: Exception,
                 budget: list[int]):
        if self._stop.is_set():
            for r in cohort:
                if not r.future.done():
                    r.future.set_result(Shed("shutdown", "engine stopped"))
            return
        if len(cohort) == 1:
            # transient benefit of the doubt before quarantining
            for _ in range(self.singleton_retries):
                if budget[0] <= 0:
                    break
                self._backoff(budget)
                budget[0] -= 1
                try:
                    self._execute_subset(cohort)
                    return
                except Exception as e:  # noqa: BLE001 — keep isolating
                    err = e
            self._quarantine(cohort[0], err, exhausted=False)
            return
        mid = len(cohort) // 2
        for sub in (cohort[:mid], cohort[mid:]):
            if budget[0] <= 0:
                for r in sub:
                    self._quarantine(r, err, exhausted=True)
                continue
            self._backoff(budget)
            budget[0] -= 1
            try:
                self._execute_subset(sub)
            except Exception as e:  # noqa: BLE001 — keep bisecting
                self._isolate(sub, e, budget)

    def _quarantine(self, req: _Request, err: Exception, exhausted: bool):
        with self._lock:
            self.quarantined += 1
        reason = "retry_budget" if exhausted else "poison"
        if req.span is not None:
            req.span.note("quarantined", reason)
        event(_log, "quarantine", model=self.model.name, reason=reason,
              request_id=req.span.request_id if req.span else None,
              error=f"{type(err).__name__}: {err}")
        if not req.future.done():
            req.future.set_result(Quarantined(
                reason, f"{type(err).__name__}: {err}"))

    def _execute_subset(self, requests: list[_Request]):
        """Synchronous re-execution of a retry cohort: own staging
        buffer, inline D2H — deliberately outside the pipeline window so
        retries can't wedge the happy path."""
        import jax

        with self._lock:
            self.retry_executions += 1
        n = len(requests)
        for req in requests:
            if req.span is not None:
                req.span.note("bisect_retry", f"cohort of {n}")
        bucket = self._bucket_for(n)
        fn = self._compiled(bucket)
        t0 = time.monotonic()
        # same allocation contract as the pipelined path: pooled staging
        # buffer + the shared placement-aware transfer — never a fresh
        # np.zeros / bare device_put per retry batch
        buf = self.staging.acquire(bucket)
        try:
            self._fill(buf, requests)
            if self.faults.enabled:
                self.faults.inject("compute", stop=self._stop)
                if self.faults.cohort_poisoned(requests):
                    raise InjectedFault(
                        f"poisoned request in retry cohort of {n}")
            with self._lock:
                self.h2d_transfers += 1
                self.h2d_bytes += buf.nbytes
                self.h2d_bytes_by_bucket[bucket] = \
                    self.h2d_bytes_by_bucket.get(bucket, 0) + buf.nbytes
            host = jax.device_get(fn(self._put(buf)))
            if self._validate:
                self._check_outputs(host)
        finally:
            self.staging.release(bucket, buf)
        t_done = time.monotonic()
        # the retry ran synchronously, so its wall time IS its compute
        # occupancy — feed the MFU meter the same way the drainer does
        self.mfu.observe(bucket, n, t_done - t0)
        nbytes = int(sum(np.asarray(a).nbytes
                         for a in jax.tree_util.tree_leaves(host)))
        with self._lock:
            self.batches += 1
            self.served += n
            self.padded_images += bucket - n
            self.bulk_transfers += 1
            self.bulk_transfer_bytes += nbytes
            self.d2h_bytes += nbytes
            self.d2h_bytes_by_bucket[bucket] = \
                self.d2h_bytes_by_bucket.get(bucket, 0) + nbytes
            self._busy_events.append((t_done, t_done - t0))
            self._prune_busy_locked(t_done)
        self.throughput.update(n)
        for i, req in enumerate(requests):
            self.latency.record(t_done - req.enqueued_at)
            if req.span is not None:
                req.span.mark("retry_exec")
            if not req.future.done():
                req.future.set_result(
                    jax.tree_util.tree_map(lambda a: np.asarray(a)[i],
                                           host))
        self.health.record_success(t_done)

    # -- watchdog thread (supervision) -------------------------------------

    def _watchdog_loop(self):
        while not self._stop.is_set():
            time.sleep(self.watchdog_interval_s)
            if self._stop.is_set():
                return
            try:
                self._watchdog_tick(time.monotonic())
            except Exception:  # noqa: BLE001 — the supervisor never dies
                pass

    def _watchdog_tick(self, now: float):
        t = self._thread
        if not self.external_batcher and t is not None \
                and not t.is_alive():
            self._restart("batcher")
        d = self._drainer
        if self.pipeline_depth > 1 and d is not None and not d.is_alive():
            self._restart("drainer")
        # stuck compute: any in-flight batch older than its exec budget
        with self._lock:
            recs = [r for r in self._inflight_recs if not r.cancelled]
        for rec in recs:
            ewma = self.admission.bucket_ewma_s(rec.bucket)
            limit = self.exec_timeout_min_s if not ewma else \
                max(self.exec_timeout_min_s, self.exec_timeout_k * ewma)
            if now - rec.dispatched_at > limit:
                self._fail_inflight_window(now - rec.dispatched_at, limit)
                break

    def _restart(self, which: str):
        if self._stop.is_set():
            return
        self.health.record_failure()
        if self.health.watchdog_restarts >= self.restart_budget:
            self.health.force_dead(
                f"{which} died and the restart budget "
                f"({self.restart_budget}) is exhausted")
            event(_log, "engine_dead", model=self.model.name, which=which,
                  restart_budget=self.restart_budget)
            return
        self.health.record_restart()
        event(_log, "watchdog_restart", model=self.model.name, which=which,
              restarts=self.health.watchdog_restarts,
              budget=self.restart_budget)
        thread = threading.Thread(
            target=self._loop if which == "batcher" else self._drain_loop,
            name=f"{which}-{self.model.name}", daemon=True)
        if which == "batcher":
            self._thread = thread
        else:
            self._drainer = thread
        thread.start()

    def _fail_inflight_window(self, age_s: float, limit_s: float):
        """A batch exceeded its exec timeout: fail every in-flight
        future fast so callers aren't parked behind a hung device call.
        The drainer's eventual result for a cancelled record is
        discarded (``rec.cancelled``); injected hangs are released via
        each record's cancel event."""
        with self._lock:
            recs = [r for r in self._inflight_recs if not r.cancelled]
            for rec in recs:
                rec.cancelled = True
            self.exec_timeouts += 1
        if not recs:
            return
        self.health.record_failure()
        event(_log, "exec_timeout", model=self.model.name,
              age_ms=round(age_s * 1e3, 1), limit_ms=round(limit_s * 1e3, 1),
              windows=len(recs))
        err = TimeoutError(
            f"in-flight batch exceeded exec timeout: age {age_s * 1e3:.0f}"
            f"ms > limit {limit_s * 1e3:.0f}ms; failing the window fast")
        for rec in recs:
            if rec.cancel is not None:
                rec.cancel.set()
            for r in rec.requests:
                if r.span is not None and not r.future.done():
                    r.span.note("exec_timeout",
                                f"age {age_s * 1e3:.0f}ms")
            pending = [r for r in rec.requests if not r.future.done()]
            if pending and self._rescue is not None:
                # replica mode: offer the cohort to a healthy replica
                # before failing anyone (serve/replicas.py bisect-retries
                # it there) — rescue must never raise into the watchdog
                try:
                    if self._rescue(pending, err):
                        continue
                except Exception:  # noqa: BLE001 — rescue is best-effort; fall through to deliver the error
                    pass
            for req in pending:
                if not req.future.done():
                    req.future.set_exception(err)

    # -- observability -----------------------------------------------------

    def health_report(self) -> dict:
        now = time.monotonic()
        rep = self.health.report(now)
        t, d = self._thread, self._drainer
        # external-batcher replicas have no batcher thread of their own
        rep["batcher_alive"] = None if self.external_batcher else \
            bool(t is not None and t.is_alive())
        rep["drainer_alive"] = bool(d is not None and d.is_alive()) \
            if self.pipeline_depth > 1 else None
        rep["accepting"] = self._accepting
        # what /v1/healthz keys 503 on: a single engine serves only
        # while fully OK; a ReplicatedEngine overrides this to "any
        # replica not DEAD" (docs/SERVING.md)
        rep["can_serve"] = rep["state"] == "ok"
        rep["placement"] = self.model.placement_desc() \
            if hasattr(self.model, "placement_desc") else None
        # mesh advertisement for the gateway's fleet table: how this
        # engine's weights are laid out and how much per-chip HBM is
        # left (None on backends without memory_stats, i.e. CPU)
        rep["mesh_shape"] = self.model.mesh_shape() \
            if hasattr(self.model, "mesh_shape") else None
        rep["param_shard_bytes"] = self.model.param_bytes() \
            if hasattr(self.model, "param_bytes") else None
        rep["hbm_headroom_bytes"] = device_hbm_headroom()
        with self._lock:
            rep["inflight"] = self._inflight
            rep["batch_failures"] = self.batch_failures
            rep["retry_executions"] = self.retry_executions
            rep["quarantined"] = self.quarantined
            rep["exec_timeouts"] = self.exec_timeouts
            rep["shed_shutdown"] = self.shed_shutdown
            done = self._last_done
        rep["last_batch_age_s"] = round(now - done, 4) \
            if done is not None else None
        if self.faults.enabled:
            rep["faults"] = self.faults.stats()
        return rep

    @property
    def queue_depth(self) -> int:
        """Requests awaiting batch formation right now — the edge QoS
        pressure signal (``Queue.qsize`` is already thread-safe)."""
        return self._queue.qsize()

    def _prune_busy_locked(self, now: float) -> None:
        horizon = now - self.occupancy_window_s
        while self._busy_events and self._busy_events[0][0] < horizon:
            self._busy_events.popleft()

    def _occupancy_locked(self, now: float) -> float:
        self._prune_busy_locked(now)
        busy = sum(dt for _, dt in self._busy_events)
        return min(1.0, max(0.0, busy / self.occupancy_window_s))

    def occupancy(self) -> float:
        """Fraction of the trailing ``occupancy_window_s`` spent in
        batch execution — the compute-stage duty cycle.  This is the
        throughput-workload pressure signal (deploy/autoscale.py): a
        saturated batchy engine shows occupancy →1 with queue depth 0,
        exactly the state queue-based pressure can't see."""
        with self._lock:
            return self._occupancy_locked(time.monotonic())

    def stats(self) -> dict:
        now = time.monotonic()
        with self._lock:
            span = None
            if self._first_dispatch is not None and \
                    self._last_done is not None:
                span = self._last_done - self._first_dispatch
            out = {"model": self.model.name,
                   "version": getattr(self.model, "serve_version", None),
                   "submitted": self.submitted,
                   "served": self.served,
                   "batches": self.batches,
                   "compiles": self.compiles,
                   "padded_images": self.padded_images,
                   "queue_depth": self._queue.qsize(),
                   "buckets": list(self.buckets),
                   "compiled_buckets": sorted(self._executables),
                   "max_wait_ms": self.max_wait_s * 1e3,
                   "workload": getattr(
                       getattr(self.model, "workload", None),
                       "verb", None),
                   "wire_dtype": str(self.wire_dtype),
                   "infer_dtype": getattr(self.model, "infer_dtype",
                                          "float32"),
                   # the served weights' byte footprint (int8 models
                   # report the true quantized size — the /metrics
                   # gauge).
                   # param_bytes is PER-CHIP on mesh views: a leaf
                   # split over ``model`` prices its addressable shard
                   "weight_hbm_bytes": self.model.param_bytes()
                   if hasattr(self.model, "param_bytes") else None,
                   "param_shard_bytes": self.model.param_bytes()
                   if hasattr(self.model, "param_bytes") else None,
                   "param_global_bytes": self.model.param_global_bytes()
                   if hasattr(self.model, "param_global_bytes")
                   else None,
                   "mesh_shape": self.model.mesh_shape()
                   if hasattr(self.model, "mesh_shape") else None,
                   "pipeline": {
                       "depth": self.pipeline_depth,
                       "inflight": self._inflight,
                       "max_inflight": self.max_inflight,
                       "bulk_transfers": self.bulk_transfers,
                       "bulk_transfer_bytes": self.bulk_transfer_bytes,
                       "h2d_transfers": self.h2d_transfers,
                       "h2d_bytes": self.h2d_bytes,
                       "h2d_bytes_by_bucket": dict(
                           self.h2d_bytes_by_bucket),
                       "d2h_bytes": self.d2h_bytes,
                       "d2h_bytes_by_bucket": dict(
                           self.d2h_bytes_by_bucket),
                       # host proxy: fraction of the first-dispatch →
                       # last-drain span with an empty in-flight window
                       "device_idle_frac": (
                           round(self._idle_s / span, 4)
                           if span and span > 0 else None),
                       # rolling compute duty cycle (trailing window) —
                       # the batch-tier/autoscaler signal
                       "occupancy": round(
                           self._occupancy_locked(now), 4)}}
        out["pipeline"]["staging"] = self.staging.stats()
        out["latency"] = self.latency.percentiles()
        # full histogram state rides along so upstream aggregators (the
        # gateway) can LatencyHistogram.merge real distributions instead
        # of eyeballing per-backend percentiles
        out["latency_hist"] = self.latency.state_dict()
        out["img_per_sec"] = self.throughput.images_per_sec
        out["admission"] = self.admission.stats()
        out["health"] = self.health_report()
        out["mfu"] = self.mfu.report()
        out["trace"] = self.tracer.summary()
        return out
