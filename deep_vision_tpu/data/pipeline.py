"""Staged train-input pipeline: host staging pool + ``DevicePrefetcher``.

This is the serving wire stack (PR 2's ``StagingPool``, PR 5's uint8 wire,
the engine's pipelined H2D) ported to the *training* side.
Per batch the producer thread runs four stages:

    prep_wait → assemble → h2d → enqueue

``prep_wait`` is time blocked on the upstream loader (worker pool /
augmentation), ``assemble`` copies the host batch into a reused staging
buffer (the DMA-source the runtime reads from — steady state holds at
most ``depth + 1`` buffers per distinct leaf shape when the backend
copies on H2D, one more when the CPU runtime zero-copies and release is
deferred to the device array's GC; reused forever either way),
``h2d`` issues the sharded ``device_put`` and waits for the transfer, and
``enqueue`` hands the *device* batch to the bounded queue.  The consumer
side marks its own span, in the :class:`deep_vision_tpu.obs.trace.Span`
style: ``stall`` (the wait on the queue, marked here at each dequeue) and
whatever the loop marks through :meth:`_EpochStream.mark` between two
dequeues (the trainer: ``dispatch``, ``fetch``, ``log``), with ``step``
closing what is left at the next dequeue.  Each side's stages sum exactly
to its wall time by construction, and

    input_stall_frac = stall / (stall + everything else)

is the share of the HOST LOOP's time parked on the queue.  It is not the
share of the epoch the chip waited for input: under async dispatch the
loop runs ahead of the device and the queue is where it parks, so the
number reads 17-25% while the device is 99% busy (PERF.md §6, PR 25).
What the chip waited for is read from a trace, with the intervals of both
spans (:meth:`_EpochStream.intervals`) laid on its clock
(``Trainer.profile_steps`` writes them to ``spans.jsonl``).  H2D traffic is
accounted per batch key (``h2d_bytes_by_key``) so the uint8-vs-float32
wire ratio is measured on the image tensor alone, not diluted by labels.

Unlike the legacy generator, an epoch here is abandonable: ``close()``
(called from ``Trainer.fit``'s finally path, and from the legacy shim's
``finally``) sets the stop event, drains the queue so a blocked producer
``put`` unblocks, and joins the thread — a preempted or diverged epoch
leaves no daemon thread behind and no device batches pinned in the queue.
"""

from __future__ import annotations

import queue
import threading
import weakref
from typing import Any, Callable, Iterable

import jax
import numpy as np

from deep_vision_tpu.analysis.sanitizer import new_lock
from deep_vision_tpu.obs.trace import Span
from deep_vision_tpu.parallel.mesh import shard_batch

__all__ = ["HostStagingPool", "DevicePrefetcher"]

_END = object()


def _leaf_name(path) -> str:
    parts = []
    for p in path:
        key = getattr(p, "key", getattr(p, "idx", getattr(p, "name", None)))
        parts.append(str(key) if key is not None else str(p))
    return "/".join(parts) if parts else "batch"


class HostStagingPool:
    """Per-(shape, dtype) free-list of host staging buffers.

    The serving ``StagingPool`` contract generalized to arbitrary batch
    pytrees: a buffer is checked out at assemble, pinned until its H2D
    completes (the runtime may read it asynchronously — or, CPU
    zero-copy, for the device array's whole life), then returned.
    ``allocated``/``reused`` make the reuse testable — an epoch of N
    batches must allocate at most ``depth + 2`` buffers per distinct
    leaf shape, not N.
    """

    def __init__(self):
        self._free: dict[tuple, list[np.ndarray]] = {}  # guarded-by: _lock
        self._lock = new_lock("data.pipeline.HostStagingPool._lock")
        self.allocated = 0  # guarded-by: _lock
        self.reused = 0  # guarded-by: _lock

    def acquire(self, shape: tuple, dtype) -> np.ndarray:
        key = (tuple(shape), np.dtype(dtype).str)
        with self._lock:
            free = self._free.setdefault(key, [])
            if free:
                self.reused += 1
                return free.pop()
            self.allocated += 1
        return np.empty(shape, dtype)

    def release(self, buf: np.ndarray):
        key = (buf.shape, buf.dtype.str)
        with self._lock:
            self._free.setdefault(key, []).append(buf)

    def stats(self) -> dict:
        with self._lock:
            return {
                "allocated": self.allocated,
                "reused": self.reused,
                "pooled": {str(k): len(v) for k, v in self._free.items()},
            }


class _EpochStream:
    """One epoch's staged batch stream (created by ``DevicePrefetcher.iterate``).

    Producer thread owns ``_pspan`` (prep_wait/assemble/h2d/enqueue marks),
    the consumer owns ``_cspan`` (stall, the stages its loop marks, step) —
    the Span ownership rule, so neither side's marks race the other's.
    """

    def __init__(self, mesh, iterable: Iterable, depth: int,
                 pool: HostStagingPool,
                 host_transform: Callable[[Any], Any] | None = None,
                 counters: Callable[[Any], dict] | None = None):
        self.mesh = mesh
        self.depth = depth
        self._pool = pool
        self._iterable = iterable
        self._host_transform = host_transform
        self._count = counters
        self.counters: dict[str, int] = {}  # producer-side, like h2d_bytes
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._error: BaseException | None = None
        self._pspan = Span(request_id="producer", origin="start")
        self._cspan = Span(request_id="consumer", origin="start")
        self._first_get = True
        self._done = False
        self.batches = 0            # consumer-side: batches yielded
        self.h2d_bytes = 0          # producer-side until join; then stable
        self.h2d_bytes_by_key: dict[str, int] = {}
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="dvt-prefetch")
        self._thread.start()

    # -- producer ------------------------------------------------------------

    def _offer(self, item) -> bool:
        """Bounded put that gives up when the epoch is closed — the fix for
        the legacy producer blocking forever on ``q.put`` after the consumer
        abandoned the iterator."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _stage(self, item):
        """Copy host leaves into pooled staging buffers (the DMA source).

        Returns the staged pytree plus the checked-out buffers; 0-d leaves
        and already-placed ``jax.Array`` leaves pass through un-pooled.
        """
        leaves, treedef = jax.tree_util.tree_flatten_with_path(item)
        staged, bufs = [], []
        for path, leaf in leaves:
            if isinstance(leaf, jax.Array):  # already on device: no H2D
                staged.append(leaf)
                continue
            arr = np.asarray(leaf)
            name = _leaf_name(path)
            self.h2d_bytes += arr.nbytes
            self.h2d_bytes_by_key[name] = \
                self.h2d_bytes_by_key.get(name, 0) + arr.nbytes
            if arr.ndim == 0:
                staged.append(arr)
                continue
            buf = self._pool.acquire(arr.shape, arr.dtype)
            np.copyto(buf, arr)
            bufs.append(buf)
            staged.append(buf)
        return jax.tree_util.tree_unflatten(treedef, staged), bufs

    @staticmethod
    def _zero_copied(dev_leaf, buf: np.ndarray) -> bool:
        """Did the backend alias ``buf`` instead of copying it?

        The CPU runtime zero-copies suitably-aligned host arrays into
        ``device_put`` results — the jax.Array then READS the host buffer
        for its whole lifetime, so the H2D fence proves nothing about
        reusability.  Compare device buffer pointers against the staging
        buffer's range; anything unprovable counts as aliased (release is
        deferred, never unsafe).  Real accelerator transfers are DMA
        copies and never hit this."""
        try:
            ptrs = [s.data.unsafe_buffer_pointer()
                    for s in dev_leaf.addressable_shards]
        except Exception:  # noqa: BLE001 — can't prove a copy happened
            return True
        lo = buf.ctypes.data
        return any(lo <= p < lo + buf.nbytes for p in ptrs)

    def _release(self, staged, dev, bufs: list):
        """Return staging buffers to the pool: immediately when the
        runtime copied them, else (CPU zero-copy) deferred to the device
        array's GC — releasing early lets the next batch overwrite bytes
        a queued batch still reads (batch N shows batch N+2's pixels)."""
        if not bufs:
            return
        by_id = {id(b): b for b in bufs}
        for s, d in zip(jax.tree_util.tree_leaves(staged),
                        jax.tree_util.tree_leaves(dev)):
            buf = by_id.pop(id(s), None)
            if buf is None:
                continue
            if self._zero_copied(d, buf):
                weakref.finalize(d, self._pool.release, buf)
            else:
                self._pool.release(buf)

    def _loop(self):  # dvtlint: hot
        try:
            it = iter(self._iterable)
            while not self._stop.is_set():
                try:
                    item = next(it)
                except StopIteration:
                    break
                self._pspan.mark("prep_wait")
                if self._host_transform is not None:
                    item = self._host_transform(item)
                if self._count is not None:
                    for name, n in self._count(item).items():
                        self.counters[name] = self.counters.get(name, 0) + n
                staged, bufs = self._stage(item)
                self._pspan.mark("assemble")
                dev = shard_batch(staged, self.mesh)
                # wait for the transfer so the staging buffers are reusable
                # (this thread overlaps the consumer's compute, so the wait
                # costs pipeline depth, not step time)
                jax.block_until_ready(dev)  # dvtlint: disable=DVT003 — H2D fence off the compute thread, releases staging buffers
                self._release(staged, dev, bufs)
                self._pspan.mark("h2d")
                if not self._offer(dev):
                    return
                self._pspan.mark("enqueue")
        except BaseException as e:  # noqa: BLE001 — re-raised consumer-side
            self._error = e
        finally:
            self._offer(_END)

    # -- consumer ------------------------------------------------------------

    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        if not self._first_get:
            self._cspan.mark("step")
        self._first_get = False
        while True:
            try:
                item = self._q.get(timeout=0.1)
                break
            except queue.Empty:
                if self._stop.is_set():
                    self._done = True
                    raise StopIteration from None
        self._cspan.mark("stall")
        if item is _END:
            self._done = True
            self._thread.join(timeout=5.0)
            if self._error is not None:
                raise self._error
            raise StopIteration
        self.batches += 1
        return item

    def mark(self, stage: str):
        """Close a segment of the consumer's span under the loop's own
        name for it; called from the consuming thread, between dequeues."""
        self._cspan.mark(stage)

    def close(self):
        """Stop the producer, drain pinned device batches, join the thread.

        Idempotent; safe mid-epoch (abandoned iteration) and after normal
        exhaustion."""
        self._stop.set()
        self._done = True
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)

    @property
    def alive(self) -> bool:
        return self._thread.is_alive()

    def intervals(self) -> list[tuple[str, str, int, float, float]]:
        """``(thread, stage, batch, t0, t1)`` of both spans, seconds on
        ``time.monotonic``.  ``batch`` is the number in the epoch of the
        batch the thread was on, the identifier the two threads share: the
        ordinal of the stage that opens a batch on that thread."""
        out = []
        for thread, span, opens in (("producer", self._pspan, "prep_wait"),
                                    ("consumer", self._cspan, "stall")):
            batch = 0
            for stage, n, t0, t1 in span.intervals():
                if stage == opens:
                    batch = n
                out.append((thread, stage, batch, t0, t1))
        return out

    def stats(self) -> dict:
        """Per-epoch input block (the trainer logs this verbatim).
        ``step_ms`` is every consumer stage but ``stall``;
        ``input_stall_frac`` is the host loop's share parked on the queue,
        which is high while the chip is busy (module docstring)."""
        prod = self._pspan.to_dict()["stages"]
        cons = self._cspan.to_dict()["stages"]
        stall_ms = cons.get("stall", 0.0)
        step_ms = sum(cons.values()) - stall_ms
        wall_ms = stall_ms + step_ms
        n = max(1, self.batches)
        return {
            "batches": self.batches,
            "input_stall_frac": stall_ms / wall_ms if wall_ms > 0 else 0.0,
            "stall_ms": round(stall_ms, 3),
            "step_ms": round(step_ms, 3),
            "h2d_bytes": self.h2d_bytes,
            "h2d_bytes_per_step": self.h2d_bytes / n,
            "h2d_bytes_by_key": dict(self.h2d_bytes_by_key),
            "counters": dict(self.counters),
            "producer_ms": {k: round(v, 3) for k, v in prod.items()},
            "pool": self._pool.stats(),
        }


class DevicePrefetcher:
    """Staged, abandonable host→device prefetcher for the train loop.

    One instance persists across epochs (the staging pool keeps its
    buffers, so epoch 2 allocates nothing); each ``iterate()`` call runs
    one epoch through a fresh producer thread and bounded queue of
    *device* batches.  ``depth`` bounds batches resident on device ahead
    of the consumer — depth 1 is classic double-buffering (one in
    compute, one staged), deeper absorbs burstier augmentation.

    ``host_transform`` runs producer-side just before staging (the GAN
    trainer threads ``task.host_prepare`` through it for prefetch-safe
    tasks).
    """

    def __init__(self, mesh, depth: int = 2):
        self.mesh = mesh
        self.depth = max(1, int(depth))
        self.pool = HostStagingPool()
        self._epoch: _EpochStream | None = None

    def iterate(self, iterable: Iterable,
                host_transform: Callable[[Any], Any] | None = None,
                counters: Callable[[Any], dict] | None = None
                ) -> _EpochStream:
        """Start (and return) one epoch's staged stream.  At most one epoch
        is live per prefetcher — starting a new one closes the previous.
        ``counters`` maps a host batch to named counts (a language task's
        tokens and documents); their sums are in the epoch's ``stats()``."""
        self.close()
        self._epoch = _EpochStream(self.mesh, iterable, self.depth,
                                   self.pool, host_transform, counters)
        return self._epoch

    def close(self):
        """Tear down the live epoch (if any): unblock + join its producer,
        drop queued device batches.  Called from ``Trainer.fit``'s finally
        path so preemption/divergence aborts leak nothing."""
        if self._epoch is not None:
            self._epoch.close()
            self._epoch = None

    def stats(self) -> dict:
        return self._epoch.stats() if self._epoch is not None else {}

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
