"""Reduction of a ``jax.profiler`` trace (one ``.xplane.pb``) to numbers.

Planes named ``/device:TPU:<n>`` are chips.  On a chip's plane the line
``XLA Ops`` holds one event per executed operation and ``XLA Modules`` one
per executed program.  Busy time is the union of the operations' intervals;
the traced window runs from the first operation's start to the last one's
end over all chips; busy seconds are averaged over the chips.  An idle gap
is named for the host event (any thread of ``/host:CPU``) that covers most
of it, or for the operation that ended it where the host shows nothing.
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
NS = 1e-9
NAME_CHARS = 120  # an operation's name is its whole HLO line: keep its head


def union(intervals: list) -> list:
    """Sorted, merged (start, end) intervals."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def reduce_planes(planes: dict, step_module: str | None = None,
                  top: int = 10) -> dict | None:
    """``planes``: plane name -> line name -> [(name, start_ns, duration_ns)].

    Returns None where no operation ran on any device plane.
    """
    chips = {p: lines for p, lines in planes.items()
             if p.startswith(DEVICE_PREFIX) and lines.get(OPS_LINE)}
    if not chips:
        return None
    starts = [s for lines in chips.values() for _, s, _ in lines[OPS_LINE]]
    ends = [s + d for lines in chips.values() for _, s, d in lines[OPS_LINE]]
    t0, t1 = min(starts), max(ends)
    busy_ns, op_ns, gaps, executions = 0.0, {}, [], 0
    for lines in chips.values():
        ops = lines[OPS_LINE]
        merged = union([(s, s + d) for _, s, d in ops])
        busy_ns += sum(e - s for s, e in merged)
        for name, _, d in ops:
            op_ns[name] = op_ns.get(name, 0.0) + d
        enders = {s: name for name, s, _ in ops}
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            gaps.append((e0, s1, enders.get(s1, "?")))
        if step_module:
            # a trace opens and closes inside an execution: count those two
            # as the share of a whole one (the median's) that they show
            spans = sorted(d for name, _, d in lines.get(MODULES_LINE, [])
                           if name.split("(")[0] == step_module)
            if spans:
                executions += sum(spans) / spans[len(spans) // 2]
    n = len(chips)
    host = [ev for line in planes.get(HOST_PLANE, {}).values() for ev in line]
    named: dict = {}
    for g0, g1, ender in sorted(gaps, key=lambda g: g[0] - g[1])[: 4 * top]:
        best, shortest = f"before:{ender}", float("inf")
        for name, s, d in host:
            over = min(g1, s + d) - max(g0, s)
            # the shortest host event that covers at least half of the gap
            if 2 * over >= g1 - g0 and d < shortest:
                best, shortest = f"host:{name}", d
        named[best] = named.get(best, 0.0) + (g1 - g0)
    return {
        "busy_s": busy_ns / n * NS,
        "window_s": (t1 - t0) * NS,
        "chips": n,
        "step_executions": executions / n if step_module else None,
        "device_ops": [[k[:NAME_CHARS], v / n * NS] for k, v in sorted(
            op_ns.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / n * NS] for k, v in sorted(
            named.items(), key=lambda kv: -kv[1])[:top]],
    }


def read_planes(path: str) -> dict:
    """An ``.xplane.pb`` as the plain dict ``reduce_planes`` takes; host
    events shorter than 50 us are dropped (they cannot name a gap)."""
    from jax.profiler import ProfileData

    planes = {}
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        if not device and plane.name != HOST_PLANE:
            continue
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                if device or ev.duration_ns >= 50_000:
                    events.append((ev.name, ev.start_ns, ev.duration_ns))
    return planes


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def summarize(trace_dir: str, step_module: str | None = None) -> dict | None:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    return reduce_planes(read_planes(path), step_module)
