"""The server's own spans and counters in a run, for the metric readers.

Built with a ``repro.obs.TraceRecorder`` and a ``MetricsRegistry``, the
server times its heartbeat as a tree of ``dataflow.*`` spans (DESIGN.md
§12) and counts bytes moved, retraces and slot-cycles.  The spans also
land in the profiler's trace as host annotations.  This module turns
both into what readers read:

- ``Window``: the spans begun and ended inside the measured window, and
  the counters' growth over it (a reader finds it as ``run.obs``);
- ``breakdown``: two keys beside ``trace_reduce.reduce``'s breakdown --
  device-idle time by the innermost ``dataflow.*`` span open, and device
  time per jitted module (the device plane's ``XLA Modules`` line).
  ``reduce`` does not return its idle intervals, so ``_idle`` finds them
  again from the same device plane: a copy, to be deleted when ``reduce``
  calls ``breakdown`` and hands it its own intervals;
- ``longest_heartbeat``: the longest heartbeat, span by span, with wall
  and CPU time.

A run whose program records nothing leaves ``run.obs`` absent or None;
every reader then reads None.
"""
from __future__ import annotations

import collections
import dataclasses
import re

import numpy as np

import trace_reduce

HEARTBEAT = "dataflow.heartbeat"
PREFIX = "dataflow."
MODULES_LINE = "XLA Modules"
_MODULE_ID = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Window:
    spans: dict        # TraceRecorder.span_arrays() columns, window only
    counters: dict     # counter key -> growth over the window

    @classmethod
    def of(cls, recorder, before: dict, after: dict, t0_s: float,
           t1_s: float) -> "Window":
        """The spans of ``recorder`` inside [t0_s, t1_s] (seconds of
        ``time.perf_counter``) and the counters' growth from ``before``
        to ``after`` (``MetricsRegistry.snapshot()["counters"]``)."""
        a = recorder.span_arrays()
        keep = ((a["t0_ns"] >= t0_s * 1e9) & (a["t1_ns"] >= 0)
                & (a["t1_ns"] <= t1_s * 1e9))
        index = np.cumsum(keep) - 1
        spans = {k: v[keep] for k, v in a.items()}
        p = spans["parent"]
        inside = (p >= 0) & keep[np.maximum(p, 0)]
        spans["parent"] = np.where(inside, index[np.maximum(p, 0)], -1)
        return cls(spans, {k: v - before.get(k, 0) for k, v in after.items()
                           if v != before.get(k, 0)})

    def heartbeats(self) -> int:
        return int((self.spans["name"] == HEARTBEAT).sum())

    def total_ns(self, name: str, column: str = "wall_ns") -> int:
        return int(self.spans[column][self.spans["name"] == name].sum())

    def counter(self, name: str) -> int:
        """A counter's growth, summed over its labels."""
        return sum(v for k, v in self.counters.items()
                   if k == name or k.startswith(name + "{"))


def per_heartbeat_ms(obs, name: str, column: str = "wall_ns"):
    """Time in the spans called ``name`` per heartbeat of the window, in
    ms; None where the program recorded no heartbeat."""
    if obs is None or not obs.heartbeats():
        return None
    return obs.total_ns(name, column) / obs.heartbeats() * 1e-6


def longest_heartbeat(obs) -> dict | None:
    """The window's longest heartbeat: its block, wall and CPU ms, and
    each span inside it, in order, as [name, depth, wall ms] (the
    program reads the thread CPU clock at the heartbeat's ends only)."""
    if obs is None or not obs.heartbeats():
        return None
    s = obs.spans
    beats = np.flatnonzero(s["name"] == HEARTBEAT)
    top = int(beats[np.argmax(s["wall_ns"][beats])])
    depth = {top: 0}
    inner = []
    for i in range(top + 1, len(s["name"])):
        p = int(s["parent"][i])
        if p not in depth:
            break
        depth[i] = depth[p] + 1
        inner.append([str(s["name"][i]), depth[i], s["wall_ns"][i] * 1e-6])
    return {"block": int(s["block"][top]),
            "wall_ms": s["wall_ns"][top] * 1e-6,
            "cpu_ms": s["cpu_ns"][top] * 1e-6, "spans": inner}


# -- the profiler's trace -----------------------------------------------------
def _self_intervals(events) -> dict:
    """name -> intervals in which a span of that name is the innermost
    open, from one thread's (start, end, name) spans, which nest."""
    out = collections.defaultdict(list)
    stack = []        # open spans: [name, end, where their self time resumes]

    def close(span):
        if span[1] > span[2]:
            out[span[0]].append([span[2], span[1]])

    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            top = stack[-1]
            if s > top[2]:
                out[top[0]].append([top[2], s])
            top[2] = e
        stack.append([name, e, s])
    while stack:
        close(stack.pop())
    return out


def _idle(devices, w0, w1) -> list:
    """The window's device-idle intervals, found as ``trace_reduce.reduce``
    finds them (which returns none): gaps between the union of each
    device's operations."""
    idle = []
    for spans in devices:
        busy = trace_reduce.union(trace_reduce.clip(spans, w0, w1))
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        idle += [[edges[i], edges[i + 1]] for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    return trace_reduce.union(idle)


def breakdown(pd) -> dict:
    """``idle_by_program_span``: seconds of device-idle time in the
    ``bench.window`` span by the innermost ``dataflow.*`` host span
    open, ``"none"`` where none is; ``device_by_module``: device seconds
    per jitted module.  Both averaged over the devices, as
    ``trace_reduce.reduce`` averages."""
    window, devices, modules = [], [], []
    host = collections.defaultdict(list)        # line -> program spans
    for plane in pd.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            devices.append([(e.start_ns, e.start_ns + e.duration_ns)
                            for e in getattr(lines.get(trace_reduce.OPS_LINE),
                                             "events", ())])
            modules.append([(_MODULE_ID.sub("", e.name), e.start_ns,
                             e.start_ns + e.duration_ns)
                            for e in getattr(lines.get(MODULES_LINE),
                                             "events", ())])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    end = e.start_ns + e.duration_ns
                    if e.name == "bench.window":
                        window.append((e.start_ns, end))
                    elif e.name.startswith(PREFIX):
                        host[(plane.name, line.name)].append(
                            (e.start_ns, end, e.name))
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    if len(window) != 1:
        raise ValueError(f"{len(window)} bench.window spans")
    w0, w1 = window[0]
    idle = _idle(devices, w0, w1)
    selfs = collections.defaultdict(list)
    for events in host.values():
        for name, ivs in _self_intervals(events).items():
            selfs[name] += ivs
    n_dev = len(devices)
    names = {n for events in host.values() for _, _, n in events}
    by_span = {k: trace_reduce.overlap(idle, trace_reduce.union(selfs[k]))
               for k in names}
    by_span["none"] = sum(e - s for s, e in idle) - sum(by_span.values())
    by_module = collections.Counter()
    for events in modules:
        for name, s, e in events:
            if s >= w0 and e <= w1:
                by_module[name] += e - s
    secs = lambda d: {k: v * 1e-9 / n_dev for k, v in sorted(
        d.items(), key=lambda x: -x[1])}
    return {"idle_by_program_span": secs(by_span),
            "device_by_module": secs(by_module)}
