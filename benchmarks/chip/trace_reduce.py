"""Reduce a profiler trace of the measured window to device numbers.

The window is the host span ``bench.window`` that the harness writes
into the trace.  Within it:

- busy: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane),
  averaged over the devices;
- kernels: count and summed device time of the operations whose name
  starts with a kernel's name (``dataflow_fire_block``);
- the device operations that took the most time;
- idle: each gap between busy intervals, attributed to what the host
  was doing then, by the innermost of the harness's host spans
  (``bench.reset_slots``, ``bench.step_block``, ``bench.harvest``
  inside ``bench.heartbeat``; the rest of a heartbeat is the server's
  own scheduling, time outside any heartbeat is the client).
"""
from __future__ import annotations

import collections
import glob
import os
import re

KERNELS = ("dataflow_fire_block", "dataflow_sched_slot", "dataflow_sched_run")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
INNER = ("reset_slots", "step_block", "harvest")


_OPCODE = re.compile(r"(?<![\w\]])([a-z][a-z0-9_-]*)\(\(?([a-z0-9]+\[[0-9,]*\])?")


def op_name(hlo: str) -> str:
    """A device op's event name is its HLO text; keep the instruction's
    name, its opcode and its first operand's shape:
    ``fusion.3 fusion(s32[2048,8])``."""
    lhs, _, rhs = hlo.partition(" = ")
    m = _OPCODE.search(rhs)
    name = lhs.lstrip("%")
    return f"{name} {m.group(1)}({m.group(2)})" if m else name


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} .xplane.pb under {trace_dir}")
    return paths[0]


def reduce_dir(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(find_xplane(trace_dir)))


def union(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def overlap(a, b) -> float:
    """Total length of the intersection of two merged interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def reduce(pd) -> dict:
    host = collections.defaultdict(list)        # span name -> intervals
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [line for line in plane.lines if line.name == OPS_LINE]
            devices.append([(op_name(e.name), e.start_ns, e.duration_ns)
                            for line in ops for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host[e.name[6:]].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    if len(host["window"]) != 1:
        raise ValueError(f"{len(host['window'])} bench.window spans")
    w0, w1 = host["window"][0]
    window_ns = w1 - w0
    busy_ns, op_ns = [], collections.Counter()
    kernels = {k: {"count": 0, "seconds": 0.0} for k in KERNELS}
    idle = []
    for events in devices:
        spans = [(s, s + d) for _, s, d in events]
        busy = union(clip(spans, w0, w1))
        busy_ns.append(sum(e - s for s, e in busy))
        for name, s, d in events:
            if s >= w0 and s + d <= w1:
                op_ns[name] += d
                for k in KERNELS:
                    if name.startswith(k):
                        kernels[k]["count"] += 1
                        kernels[k]["seconds"] += d * 1e-9
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        idle += [[edges[i], edges[i + 1]] for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    idle = union(idle)
    merged = {k: union(v) for k, v in host.items()}
    by_host = {k: overlap(idle, merged.get(k, [])) for k in INNER}
    in_beat = overlap(idle, merged.get("heartbeat", []))
    by_host["server_scheduling"] = in_beat - sum(by_host.values())
    by_host["client"] = sum(e - s for s, e in idle) - in_beat
    n_dev = len(devices)
    return {
        "busy_s": sum(busy_ns) / n_dev * 1e-9,
        "window_s": window_ns * 1e-9,
        "kernels": kernels,
        "breakdown": {
            "device_ops": [[n, t * 1e-9 / n_dev]
                           for n, t in op_ns.most_common(10)],
            "idle_gaps": sorted(([f"host:{k}", v * 1e-9 / n_dev]
                                 for k, v in by_host.items()),
                                key=lambda x: -x[1])[:10],
        },
    }
