"""TraceRecorder -- block-clock event tracing for the serving lifecycle.

Records the DESIGN.md §11 slot-lifecycle state machine as a flat event
log and exports Chrome trace-event JSON (the ``traceEvents`` array
format) that Perfetto / chrome://tracing load directly.

Event kinds (one per lifecycle edge):

========== ==========================================================
kind       meaning
========== ==========================================================
submit     request accepted into the queue
reject     bounded-admission rejection (never enters the queue)
drop       drop-oldest policy evicted a queued request  (terminal)
poison     fault injection corrupted the request's feeds on submit
admit      request bound to a slot (begins a slot span)
requeue    degradation unbound a resident request (ends its slot span)
retry      a dispatch attempt failed and was retried
wedge      fault injection wedged a slot (suppressed its quiescence)
degrade    backend degradation (compile- or dispatch-triggered)
expire     a *queued* request passed its deadline       (terminal)
harvest    a resident request finished; ``status`` says how (terminal)
========== ==========================================================

Timestamps: every event carries the server's deterministic block clock
(``block``) and a wall-clock offset (``wall_s``).  Export with
``clock="block"`` (default; 1 block = 1000 us so Perfetto shows block
numbers as milliseconds -- deterministic, diffable) or ``clock="wall"``
(real time).

Spans (DESIGN.md §12): :meth:`TraceRecorder.begin` / :meth:`end` (or the
:meth:`span` context manager) time one piece of host work -- its name,
wall start and end (``time.perf_counter_ns``), the span open around it
and the server's block.  A root span (one opened with none open: the
server's heartbeat) also takes the thread's CPU time at both ends
(``time.thread_time_ns``), so its off-CPU time shows.  Inner spans do
not: the thread CPU clock is a system call on some hosts (6 µs a read
on a TPU v5e host, against 0.1 µs for the wall clock), which at a dozen
spans a heartbeat would slow the heartbeat it times.  Spans live in one
flat integer array, so a long run adds no object per span to the heap
the garbage collector walks.  With an ``annotate`` factory (the server
injects ``jax.profiler.TraceAnnotation``) each span is also a host
annotation in the profiler's trace, on the same clock as the device's
operations.  One recorder serves one thread.

Track layout: one track (pid/tid pair) per slot under the "slots"
process, one per tenant under "tenants", plus a "server" track for
events not bound to a slot.  Slot spans run admit -> harvest/requeue;
tenant spans run submit -> terminal.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import struct
from array import array
from time import perf_counter_ns as _wall
from time import thread_time_ns as _cpu

import numpy as np

TERMINAL_KINDS = ("harvest", "expire", "drop")

# pids for the track groups in the chrome export
_PID_SLOTS, _PID_TENANTS, _PID_SERVER, _PID_SPANS = 1, 2, 3, 4

# one span is STRIDE int64 words of TraceRecorder._sp:
# name id, parent span (-1: none), block, wall start, wall end (-1 while
# open), cpu start, cpu end (both -1 but on a root span), then SPAN_ARGS
# argument slots
STRIDE = 10
SPAN_ARGS = 3
_ROW = struct.Struct(f"{STRIDE}q").pack    # packs a row faster than extend

US_PER_BLOCK = 1000  # block-clock export scale: 1 block == 1ms in Perfetto


class TraceInvariantError(AssertionError):
    """A trace export violated a lifecycle/clock invariant."""


@dataclasses.dataclass
class TraceEvent:
    kind: str
    block: int
    wall_s: float
    uid: int | None = None
    slot: int | None = None
    tenant: str | None = None
    status: str | None = None
    args: dict = dataclasses.field(default_factory=dict)


class TraceRecorder:
    """Append-only event log and span table with Chrome trace-event
    export."""

    def __init__(self, annotate=None) -> None:
        self.events: list[TraceEvent] = []
        self.annotate = annotate      # name -> context manager, or None
        self._t0_ns = _wall()
        self._sp = array("q")
        self._open: list[int] = []    # open spans, innermost last
        self._anns: list = []         # their annotations (or None)
        self._name_ids: dict[str, int] = {}
        self._names: list[str] = []
        self._arg_keys: list[dict[str, int]] = []   # per name id
        self._str_keys: list[set[str]] = []    # keys whose values are str
        self._str_ids: dict[str, int] = {}
        self._strs: list[str] = []

    def record(self, kind: str, *, block: int, uid: int | None = None,
               slot: int | None = None, tenant: str | None = None,
               status: str | None = None, **args) -> TraceEvent:
        ev = TraceEvent(kind=kind, block=int(block),
                        wall_s=(_wall() - self._t0_ns) * 1e-9,
                        uid=uid, slot=slot, tenant=tenant, status=status,
                        args=args)
        self.events.append(ev)
        return ev

    # ----------------------------------------------------------------- spans
    def begin(self, name: str, block: int | None = None, **args) -> int:
        """Open a span inside the innermost open one; returns its index.
        ``block`` defaults to the enclosing span's.  ``args`` take int or
        str values, at most SPAN_ARGS keys per span name."""
        nid = self._name_ids.get(name)
        if nid is None:
            nid = self._name_ids[name] = len(self._names)
            self._names.append(name)
            self._arg_keys.append({})
            self._str_keys.append(set())
        sp = self._sp
        parent = self._open[-1] if self._open else -1
        if block is None:
            block = sp[parent * STRIDE + 2] if parent >= 0 else -1
        ann = self.annotate
        if ann is not None:
            ann = ann(name)
            ann.__enter__()
        i = len(sp) // STRIDE
        sp.frombytes(_ROW(nid, parent, block, _wall(), -1,
                          _cpu() if parent < 0 else -1, -1, 0, 0, 0))
        self._open.append(i)
        self._anns.append(ann)
        if args:
            self._set_args(i, nid, args)
        return i

    def end(self, i: int, **args) -> None:
        """Close span ``i``, and with it any span still open inside it
        (one that an exception left open)."""
        open_ = self._open
        if not open_ or open_[-1] != i:
            if i not in open_:
                raise ValueError(f"span {i} is not open")
            self.unwind(open_.index(i))
        else:                       # the innermost: no loop
            t = _wall()
            open_.pop()
            sp, j = self._sp, i * STRIDE
            sp[j + 4] = t
            if not open_:
                sp[j + 6] = _cpu()
            ann = self._anns.pop()
            if ann is not None:
                ann.__exit__(None, None, None)
        if args:
            self._set_args(i, self._sp[i * STRIDE], args)

    @property
    def depth(self) -> int:
        """How many spans are open."""
        return len(self._open)

    def unwind(self, depth: int) -> None:
        """Close, now, every span opened since ``depth`` spans were
        open: those an exception left open."""
        t = _wall()
        sp = self._sp
        while len(self._open) > depth:
            j = self._open.pop()
            sp[j * STRIDE + 4] = t
            if not self._open:
                sp[j * STRIDE + 6] = _cpu()
            ann = self._anns.pop()
            if ann is not None:
                ann.__exit__(None, None, None)

    @contextlib.contextmanager
    def span(self, name: str, block: int | None = None, **args):
        i = self.begin(name, block, **args)
        try:
            yield i
        finally:
            self.end(i)

    def _set_args(self, i: int, nid: int, args: dict) -> None:
        keys = self._arg_keys[nid]          # key -> argument slot
        base = i * STRIDE + 7
        for k, v in args.items():
            j = keys.get(k)
            if j is None:
                if len(keys) == SPAN_ARGS:
                    raise ValueError(f"span {self._names[nid]!r} takes at "
                                     f"most {SPAN_ARGS} argument keys")
                j = keys[k] = len(keys)
            if isinstance(v, str):
                self._str_keys[nid].add(k)
                if v not in self._str_ids:
                    self._str_ids[v] = len(self._strs)
                    self._strs.append(v)
                v = self._str_ids[v]
            self._sp[base + j] = v

    @property
    def n_spans(self) -> int:
        return len(self._sp) // STRIDE

    def span_arrays(self) -> dict:
        """Every span, one numpy column per field, in the order they
        began: ``name``, ``parent`` (index, -1 for none), ``block``,
        ``t0_ns``/``t1_ns`` (``time.perf_counter_ns``; ``t1_ns`` is -1
        while open), ``wall_ns``, ``cpu_ns`` (-1 but on a closed root
        span), ``self_ns`` (wall time less what its child spans cover)
        and one column per argument key (0, or None for str keys, where
        a span lacks it)."""
        a = self._span_rows()
        n = len(a)
        done = a[:, 4] >= 0
        wall = np.where(done, a[:, 4] - a[:, 3], 0)
        child = np.zeros(n, np.int64)
        inner = a[:, 1] >= 0
        np.add.at(child, a[inner, 1], wall[inner])
        out = {"name": np.array(self._names, object)[a[:, 0]],
               "parent": a[:, 1], "block": a[:, 2],
               "t0_ns": a[:, 3], "t1_ns": a[:, 4],
               "wall_ns": wall,
               "cpu_ns": np.where(done & (a[:, 5] >= 0), a[:, 6] - a[:, 5],
                                  -1),
               "self_ns": wall - child}
        for nid, keys in enumerate(self._arg_keys):
            rows = a[:, 0] == nid
            for j, k in enumerate(keys):
                vals = a[rows, 7 + j]
                if k in self._str_keys[nid]:
                    col = out.setdefault(k, np.full(n, None, object))
                    col[rows] = [self._strs[v] for v in vals]
                else:
                    out.setdefault(k, np.zeros(n, np.int64))[rows] = vals
        return out

    def _span_rows(self) -> np.ndarray:
        # a copy: a live view would pin the array's buffer, and the next
        # span could then not grow it
        return np.frombuffer(self._sp, np.int64).reshape(-1, STRIDE).copy()

    def _span_events(self) -> list[dict]:
        """Closed spans as complete ("X") events on the wall clock."""
        out = [{"name": "process_name", "ph": "M", "pid": _PID_SPANS,
                "tid": 0, "args": {"name": "host spans"}}]
        for row in self._span_rows().tolist():
            nid, _, block, t0, t1, c0, c1 = row[:7]
            if t1 < 0:
                continue
            args = {"block": block}
            if c0 >= 0:
                args["cpu_us"] = (c1 - c0) / 1e3
            for j, k in enumerate(self._arg_keys[nid]):
                v = row[7 + j]
                args[k] = self._strs[v] if k in self._str_keys[nid] else v
            out.append({"name": self._names[nid], "ph": "X",
                        "pid": _PID_SPANS, "tid": 1,
                        "ts": (t0 - self._t0_ns) / 1e3,
                        "dur": (t1 - t0) / 1e3, "args": args})
        return out

    def __len__(self) -> int:
        return len(self.events)

    # ---------------------------------------------------------------- export
    def to_chrome(self, clock: str = "block") -> dict:
        """Render the log as a Chrome trace-event JSON object; on the
        wall clock the closed spans come too, on a track of their own."""
        if clock not in ("block", "wall"):
            raise ValueError(f"clock must be 'block' or 'wall', got {clock!r}")

        def ts(ev: TraceEvent) -> float:
            if clock == "block":
                return ev.block * US_PER_BLOCK
            return ev.wall_s * 1e6

        out: list[dict] = []
        tenant_tids: dict[str, int] = {}
        seen_slots: set[int] = set()

        def meta(pid: int, tid: int, what: str, name: str) -> dict:
            return {"name": what, "ph": "M", "pid": pid, "tid": tid,
                    "args": {"name": name}}

        out.append(meta(_PID_SLOTS, 0, "process_name", "slots"))
        out.append(meta(_PID_TENANTS, 0, "process_name", "tenants"))
        out.append(meta(_PID_SERVER, 0, "process_name", "server"))

        def tenant_tid(tenant: str) -> int:
            if tenant not in tenant_tids:
                tenant_tids[tenant] = len(tenant_tids) + 1
                out.append(meta(_PID_TENANTS, tenant_tids[tenant],
                                "thread_name", str(tenant)))
            return tenant_tids[tenant]

        def slot_tid(slot: int) -> int:
            tid = slot + 1  # tid 0 is reserved for process metadata
            if slot not in seen_slots:
                seen_slots.add(slot)
                out.append(meta(_PID_SLOTS, tid, "thread_name", f"slot {slot}"))
            return tid

        def base_args(ev: TraceEvent) -> dict:
            args = {"block": ev.block, "wall_s": round(ev.wall_s, 6)}
            if ev.uid is not None:
                args["uid"] = ev.uid
            if ev.slot is not None:
                args["slot"] = ev.slot
            if ev.status is not None:
                args["status"] = ev.status
            if ev.tenant is not None:
                args["tenant"] = ev.tenant
            args.update(ev.args)
            return args

        for ev in self.events:
            args = base_args(ev)
            # slot spans: admit opens, harvest/requeue closes
            if ev.kind == "admit" and ev.slot is not None:
                out.append({"name": f"uid {ev.uid}", "ph": "B",
                            "pid": _PID_SLOTS, "tid": slot_tid(ev.slot),
                            "ts": ts(ev), "args": args})
            elif ev.kind in ("harvest", "requeue") and ev.slot is not None \
                    and ev.slot >= 0:
                out.append({"name": f"uid {ev.uid}", "ph": "E",
                            "pid": _PID_SLOTS, "tid": slot_tid(ev.slot),
                            "ts": ts(ev), "args": args})
            # tenant spans: submit opens, terminal closes.  Requests of
            # one tenant overlap (queued + resident), so these are async
            # events keyed by uid, not B/E (which must nest per track).
            if ev.kind == "submit" and ev.tenant is not None:
                out.append({"name": f"uid {ev.uid}", "cat": "request",
                            "id": ev.uid, "ph": "b",
                            "pid": _PID_TENANTS, "tid": tenant_tid(ev.tenant),
                            "ts": ts(ev), "args": args})
            elif ev.kind in TERMINAL_KINDS and ev.tenant is not None:
                out.append({"name": f"uid {ev.uid}", "cat": "request",
                            "id": ev.uid, "ph": "e",
                            "pid": _PID_TENANTS, "tid": tenant_tid(ev.tenant),
                            "ts": ts(ev), "args": args})
            # every event also lands as an instant on its home track
            if ev.slot is not None and ev.slot >= 0:
                pid, tid = _PID_SLOTS, slot_tid(ev.slot)
            elif ev.tenant is not None:
                pid, tid = _PID_TENANTS, tenant_tid(ev.tenant)
            else:
                pid, tid = _PID_SERVER, 1
            out.append({"name": ev.kind, "ph": "i", "s": "t",
                        "pid": pid, "tid": tid, "ts": ts(ev), "args": args})

        if clock == "wall" and self.n_spans:
            out += self._span_events()
        return {"traceEvents": out,
                "displayTimeUnit": "ms",
                "otherData": {"clock": clock,
                              "us_per_block": US_PER_BLOCK if clock == "block" else None}}

    def save(self, path: str, clock: str = "block") -> None:
        with open(path, "w") as fh:
            json.dump(self.to_chrome(clock), fh, indent=1)


def load_chrome(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def validate_chrome(trace: dict) -> dict:
    """Check a chrome export against the §12 invariants; raise on violation.

    Invariants:
      1. shape: a ``traceEvents`` list whose entries all carry
         name/ph/pid/tid (+ts for non-metadata) -- what Perfetto requires;
      2. monotone clocks: per track, timestamps never decrease in
         emission order;
      3. balanced spans: per track, B/E nest and the stack drains
         empty; async b/e pairs (tenant request spans) balance per id;
      4. lifecycle: every uid has exactly one submit and exactly one
         terminal event, and admits == requeues + slot-harvests.

    Returns ``{"events": n, "uids": n, "tracks": n}`` on success so
    callers can assert non-emptiness in one place.
    """
    if not isinstance(trace, dict) or not isinstance(trace.get("traceEvents"), list):
        raise TraceInvariantError("missing traceEvents list")
    events = trace["traceEvents"]

    last_ts: dict[tuple, float] = {}
    span_stack: dict[tuple, list[str]] = {}
    async_open: dict[tuple, int] = {}
    submits: dict[int, int] = {}
    terminals: dict[int, int] = {}
    admits: dict[int, int] = {}
    closes: dict[int, int] = {}

    for i, ev in enumerate(events):
        for field in ("name", "ph", "pid", "tid"):
            if field not in ev:
                raise TraceInvariantError(f"event {i} missing {field!r}: {ev}")
        if ev["ph"] == "M":
            continue
        if "ts" not in ev:
            raise TraceInvariantError(f"event {i} missing ts: {ev}")
        track = (ev["pid"], ev["tid"])
        ts = ev["ts"]
        if ts < last_ts.get(track, float("-inf")):
            raise TraceInvariantError(
                f"clock went backwards on track {track}: {ts} after {last_ts[track]}")
        last_ts[track] = ts
        if ev["ph"] == "b":
            async_open[(ev.get("cat"), ev.get("id"))] = \
                async_open.get((ev.get("cat"), ev.get("id")), 0) + 1
        elif ev["ph"] == "e":
            key = (ev.get("cat"), ev.get("id"))
            if async_open.get(key, 0) <= 0:
                raise TraceInvariantError(f"async end without begin: {ev}")
            async_open[key] -= 1
        elif ev["ph"] == "B":
            span_stack.setdefault(track, []).append(ev["name"])
        elif ev["ph"] == "E":
            stack = span_stack.get(track, [])
            if not stack:
                raise TraceInvariantError(f"unmatched end on track {track}: {ev}")
            opened = stack.pop()
            if opened != ev["name"]:
                raise TraceInvariantError(
                    f"mismatched span on track {track}: began {opened!r}, "
                    f"ended {ev['name']!r}")
        args = ev.get("args", {})
        uid = args.get("uid")
        if uid is not None and ev["ph"] == "i":
            kind = ev["name"]
            if kind == "submit":
                submits[uid] = submits.get(uid, 0) + 1
            if kind in TERMINAL_KINDS:
                terminals[uid] = terminals.get(uid, 0) + 1
            if kind == "admit":
                admits[uid] = admits.get(uid, 0) + 1
            if kind == "requeue" or (kind == "harvest"
                                     and args.get("slot", -1) >= 0):
                closes[uid] = closes.get(uid, 0) + 1

    open_tracks = {t: s for t, s in span_stack.items() if s}
    if open_tracks:
        raise TraceInvariantError(f"unbalanced spans left open: {open_tracks}")
    open_async = {k: n for k, n in async_open.items() if n}
    if open_async:
        raise TraceInvariantError(f"unbalanced async spans left open: {open_async}")
    for uid, n in submits.items():
        if n != 1:
            raise TraceInvariantError(f"uid {uid} submitted {n} times")
        if terminals.get(uid, 0) != 1:
            raise TraceInvariantError(
                f"uid {uid} has {terminals.get(uid, 0)} terminal events, want 1")
    for uid, n in terminals.items():
        if uid not in submits:
            raise TraceInvariantError(f"uid {uid} terminated without a submit")
    for uid, n in admits.items():
        if closes.get(uid, 0) != n:
            raise TraceInvariantError(
                f"uid {uid}: {n} admits but {closes.get(uid, 0)} slot closes")

    return {"events": len(events), "uids": len(submits), "tracks": len(last_ts)}
