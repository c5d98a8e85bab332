"""The launch record: one process-wide ``Span`` from the process's start to
whatever the program is doing now, and every compile as an interval.

What a user of ``cli.train`` waits for before the first step is most of a
short run and none of it is a step: the interpreter, imports, the TPU
runtime's start, the trainer's build, the init program, the step's compile
or its load from the persistent cache.  The program marks its own
boundaries on one ``obs/trace.py::Span`` (``request_id="launch"``) whose
origin is the process's start as the OS has it:

    outside   process start -> the package's first import
    import    -> the package's first call (``enable_compile_cache``)
    cache     ``enable_compile_cache`` itself
    backend   the first ``jax.devices()`` the package makes (``make_mesh``)
    build     ``Trainer.__init__``
    init      ``Trainer.init_state``
    restore   ``Trainer.maybe_resume`` when it restores
    epoch     every call of ``Trainer.train_epoch``; the call opens with the
              segments ``first_dispatch`` (until its first jitted call
              returns) and ``first_fetch`` (until its metrics are first read)
    caller    whatever ran between two of these: the gap is the caller's

A stage is two marks (the gap before it closes as ``caller``, the stage
under its own name), so the segments tile the span as every ``Span``'s do.
A stage that opens inside another splits it: the outer one's segment closes
under the outer one's name and goes on after the inner one.

Compiles are JAX's own monitoring events.  JAX dates a trace, a lowering and
a backend compile with ``time.time()`` at both ends, which is the clock
``spans.jsonl`` and a profiler trace's ``profile_start_time`` are on, so a
compile lands on the device's timeline by ``t_ns - profile_start_time``
with nothing fitted.  Each becomes ``(kind, fun, t0, t1, cache, parent,
batch)``: the persistent cache's hit or miss where JAX reported one, the
stage that was open, and the epoch's batch number when inside one.  A
trace holds the traces of every jitted function it calls and a lowering
those of its rules; JAX announces each as it begins, so the log counts a
thread's open ones and keeps the outermost.  The first ``MAX_INTERVALS``
are kept and the rest counted.

Nothing here is written anywhere unless ``Trainer._write_spans`` runs (a
profiled epoch): it calls ``write`` for ``<workdir>/launch.jsonl`` beside
``spans.jsonl``.  What stays on otherwise is memory: a few dozen marks a
process and three appends a compiled program, none of it inside a step.
docs/OBSERVABILITY.md ("Launch record") has the file's schema.
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
import sys
import threading
import time

from deep_vision_tpu.obs.trace import Span

#: intervals kept; a process that compiles more counts the rest as dropped
MAX_INTERVALS = 4096

KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

#: programs named in an epoch's ``[compile]`` line
MAX_LATE = 64

#: segments of an ``epoch`` call that are written as its children
FIRSTS = ("first_dispatch", "first_fetch")


def process_start_monotonic() -> float | None:
    """The process's start on ``time.monotonic``: its start time in
    ``/proc/self/stat`` (clock ticks since boot) against the boot clock now.
    None where either cannot be read or they disagree (an age below zero)."""
    try:
        with open("/proc/self/stat") as f:
            # the command may hold spaces and parentheses: count from its end
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return time.monotonic() - age if age >= 0 else None


class LaunchLog:
    """The span, the compile intervals and the counters of one process."""

    def __init__(self):
        import deep_vision_tpu

        self.span = Span(request_id="launch", origin="process_start")
        started = process_start_monotonic()
        if started is not None:
            created = self.span.marks[0][1]
            self.span.marks[0] = ("process_start", started)
            imported = min(max(deep_vision_tpu.IMPORTED_AT, started), created)
            self.span.marks.append(("outside", imported))
            self.span.marks.append(("import", created))
        self.compiles: list[tuple] = []
        self.dropped = 0
        self.hits = self.misses = 0
        self.epochs = 0  # calls of train_epoch begun
        self._open: list[str] = []  # the stages open now, outermost first
        self._once: set[str] = set()
        self._firsts: set[str] = set()
        # compiles seen as the last epoch call began, and as its first
        # dispatch returned: what compiled under the first step
        self._epoch_from = self._first_to = 0
        # programs compiled in the open epoch after its first dispatch; kept
        # apart from ``compiles`` so that a full record still names them
        self.late: list[tuple] = []
        self._in_loop = False
        self._stream = None  # the open epoch's stream: its batch count
        # a thread's open traces and lowerings, its compile's hit or miss
        # and where its cache read went
        self._pending = threading.local()
        self._listening = False

    # ------------------------------------------------------------- stages

    @contextlib.contextmanager
    def stage(self, name: str):
        """Two marks: the gap before closes as ``caller`` (or as the stage
        this one opens inside), the stage under its own name."""
        self.span.mark(self._open[-1] if self._open else "caller")
        self._open.append(name)
        try:
            yield self
        finally:
            self.span.mark(name)
            self._open.remove(name)

    def once(self, name: str):
        """``stage(name)`` the first time, nothing after it."""
        if name in self._once:
            return contextlib.nullcontext(self)
        self._once.add(name)
        return self.stage(name)

    @contextlib.contextmanager
    def epoch(self):
        """One call of ``train_epoch``."""
        self.epochs += 1
        self._firsts.clear()
        self.late = []
        self._epoch_from = self._first_to = len(self.compiles)
        with self.stage("epoch"):
            try:
                yield self
            finally:
                self._stream = None
                self._in_loop = False

    def watch(self, stream):
        """The open epoch's stream: a compile's batch is the one the loop
        holds (``stream.batches - 1``) as the compile ends."""
        self._stream = stream

    def first(self, name: str):
        """Close the open epoch's segment under ``name``, once a call."""
        if name not in self._firsts:
            self._firsts.add(name)
            self.span.mark(name)
            if name == "first_dispatch":
                self._first_to = len(self.compiles)
                self._in_loop = True

    # ----------------------------------------------------------- compiles

    def listen(self) -> "LaunchLog":
        """Register on JAX's monitoring, once a process."""
        if not self._listening:
            self._listening = True
            import jax.monitoring as monitoring

            monitoring.register_scalar_listener(self._enter)
            monitoring.register_event_time_span_listener(self._time_span)
            monitoring.register_event_listener(self._event)
            monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def _add(self, kind, fun, t0, t1, cache=None) -> int | None:
        if len(self.compiles) >= MAX_INTERVALS:
            self.dropped += 1
            return None
        self.compiles.append((kind, fun, t0, t1, cache,
                              self._open[-1] if self._open else "caller",
                              self._batch()))
        return len(self.compiles) - 1

    def _batch(self) -> int | None:
        """The batch the open epoch's loop holds, by its stream's count."""
        stream = self._stream
        return stream.batches - 1 if stream is not None else None

    def _time_span(self, event, start_time, end_time, **kw):
        kind = KINDS.get(event)
        if kind is None:
            return
        pending = self._pending
        # JAX announces each of these as it begins (``_enter``): a trace
        # holds the traces of the jitted functions it calls and a lowering
        # those of its rules, thousands in one step's, and the outermost
        # covers them all
        depth = pending.depth = max(getattr(pending, "depth", 1) - 1, 0)
        if depth:
            return
        fun = str(kw.get("fun_name"))
        if kind != "backend_compile":
            self._add(kind, fun, start_time, end_time)
            return
        cache = getattr(pending, "cache", None)
        self._add(kind, fun, start_time, end_time, cache)
        if self._in_loop and len(self.late) < MAX_LATE:
            self.late.append((fun, end_time - start_time, cache,
                              self._batch()))
        at = getattr(pending, "retrieval", None)
        if at is not None:  # the cache's read of this program: name it
            read = self.compiles[at]
            self.compiles[at] = (read[0], fun, *read[2:])
        pending.cache = pending.retrieval = None

    def _enter(self, event, value, **kw):
        if event in KINDS:
            self._pending.depth = getattr(self._pending, "depth", 0) + 1

    def _event(self, event, **kw):
        if event == HIT:
            self.hits += 1
            self._pending.cache = "hit"
        elif event == MISS:
            self.misses += 1
            self._pending.cache = "miss"

    def _duration(self, event, secs, **kw):
        if event == RETRIEVAL:
            # JAX gives the read's duration as it ends: its start is a
            # place on the wall clock the other intervals are on, not an
            # elapsed time taken from it
            now = time.time()
            began = now - secs  # dvtlint: disable=DVT005
            self._pending.retrieval = self._add(
                "cache_retrieval", None, began, now, "hit")

    def mark(self) -> tuple[int, int, int]:
        return len(self.compiles), self.hits, self.misses

    def since(self, mark, name: str | None = None) -> dict:
        """Backend compiles after ``mark``: how many, their seconds, the
        cache's hits and misses; one by one for a named program
        (``"train_step"`` reads ``jit(train_step)``), else those of half a
        second or more by name.  Past ``MAX_INTERVALS`` it undercounts."""
        n, hits, misses = mark
        found = [(c[1], c[3] - c[2]) for c in self.compiles[n:]
                 if c[0] == "backend_compile"
                 and (name is None or c[1] == f"jit({name})")]
        out = {"programs": len(found),
               "total_s": round(sum(s for _, s in found), 2),
               "cache_hits": self.hits - hits,
               "cache_misses": self.misses - misses}
        if name is not None:
            out["seconds"] = [round(s, 2) for _, s in found]
        else:
            by_name: dict[str, list] = {}
            for fun, secs in found:
                by_name.setdefault(fun, []).append(round(secs, 2))
            out["half_second_or_more"] = {k: v for k, v in by_name.items()
                                          if sum(v) >= 0.5}
        return out

    # ------------------------------------------------------------ read out

    def stages(self) -> list[tuple[str, int, float, float]]:
        """``Span.intervals()`` with the stages still open closed now."""
        span = Span(request_id="launch")
        span.marks = list(self.span.marks)
        span.mark(self._open[-1] if self._open else "caller")
        return span.intervals()

    def summary(self) -> str:
        """The ``[launch]`` line of a process's first epoch: seconds by
        stage up to the first fetch, and what compiled under the first
        dispatch."""
        total: dict[str, float] = {}
        for name, _, t0, t1 in self.stages():
            total[name] = total.get(name, 0.0) + t1 - t0
            if name == "first_fetch":
                break
        parts = [f"{k} {total[k]:.1f}s" for k in
                 ("outside", "import", "cache", "backend", "build", "init",
                  "restore", "caller") if k in total]
        if "first_dispatch" in total:
            text = f"first step {total['first_dispatch']:.1f}s"
            under = self.compiles[self._epoch_from:self._first_to]
            programs = [c for c in under if c[0] == "backend_compile"]
            if programs:
                longest = max(programs, key=lambda c: c[3] - c[2])
                text += (f" (compile {_union_s(under):.1f}s: {longest[1]} "
                         f"{longest[4] or 'uncached'})")
            parts.append(text)
        if "first_fetch" in total:
            parts.append(f"first fetch {total['first_fetch']:.1f}s")
        return "[launch] " + " ".join(parts)

    def write(self, path: str, clock: list):
        """``launch.jsonl``: a header, a line a stage, a line a compile,
        all in ``time.time_ns`` terms by the first pair of ``clock`` (the
        rule ``spans.jsonl`` has)."""
        mono_ns, wall_ns = clock[0]

        def ns(t: float) -> int:
            return round(t * 1e9) - mono_ns + wall_ns

        stages = self.stages()
        with open(path, "w") as f:
            f.write(json.dumps({
                "clock": clock, "process_start_ns": ns(stages[0][2]),
                "pid": os.getpid(), "argv0": sys.argv[0] if sys.argv else "",
                "cache_hits": self.hits, "cache_misses": self.misses,
                "intervals": len(self.compiles),
                "dropped": self.dropped}) + "\n")
            opened = None  # where the epoch call began, if a first_* opened it
            epochs = 0
            for name, n, t0, t1 in stages:
                if name in FIRSTS:
                    opened = t0 if opened is None else opened
                    parent, ordinal = "epoch", epochs
                else:
                    if name == "epoch":
                        epochs += 1
                        if opened is not None:
                            t0, opened = opened, None
                    parent, ordinal = "launch", n
                f.write(json.dumps({
                    "name": name, "ordinal": ordinal, "parent": parent,
                    "t0_ns": ns(t0), "t1_ns": ns(t1)}) + "\n")
            for kind, fun, t0, t1, cache, parent, batch in list(self.compiles):
                f.write(json.dumps({
                    "kind": kind, "fun": fun, "t0_ns": round(t0 * 1e9),
                    "t1_ns": round(t1 * 1e9), "cache": cache,
                    "parent": parent, "batch": batch}) + "\n")


def _union_s(compiles: list[tuple]) -> float:
    """Seconds covered by the intervals of ``compiles``, overlaps once."""
    total, end = 0.0, float("-inf")
    for t0, t1 in sorted((c[2], c[3]) for c in compiles):
        if t1 > end:
            total += t1 - max(t0, end)
            end = t1
    return total


_LOG: LaunchLog | None = None


def start() -> LaunchLog:
    """The process's record; the first call closes ``outside`` and
    ``import``.  Idempotent."""
    global _LOG
    if _LOG is None:
        _LOG = LaunchLog()
    return _LOG


def staged(name: str):
    """Run the decorated function as the launch stage ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with start().listen().stage(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
