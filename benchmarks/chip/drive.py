"""The client side of a run: the measured window, the drain, the spans.

One process, one thread.  The client submits through
``DataflowServer.submit`` and turns the server's heartbeat
(``DataflowServer.step``) itself, so the host clock around those calls
is what a caller of the server sees.  Every request carries its due
time; a result is stamped with the host clock after the heartbeat that
returned it.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import random
import time

import numpy as np

DRAIN_S = 60.0    # how long past the window's close an answer may come


@dataclasses.dataclass
class Log:
    """What the client saw, per uid (all times in s from window open).

    Of the answers themselves it keeps only a sample for the check: a
    uniform reservoir of ``keep`` ok answers, drawn with ``rng``, and the
    answer that ran the most cycles.  Holding every result would grow
    the heap the process's garbage collector walks while the server
    runs."""
    keep: int = 0
    rng: random.Random = dataclasses.field(default_factory=random.Random)
    pool: dict = dataclasses.field(default_factory=dict)   # uid -> pool i
    due: dict = dataclasses.field(default_factory=dict)
    sent: dict = dataclasses.field(default_factory=dict)
    done: dict = dataclasses.field(default_factory=dict)
    status: dict = dataclasses.field(default_factory=dict)
    retries: int = 0            # dispatch retries the answers rode
    sample: list = dataclasses.field(default_factory=list)  # (uid, result)
    longest: tuple = (-1, None, None)   # (cycles, uid, result)
    n_ok: int = 0
    window_s: float = 0.0       # open to the end of the last heartbeat
    drained_s: float = 0.0      # open to the end of the drain
    heartbeats: int = 0         # heartbeats inside the window
    queued_at_close: int = 0    # requests waiting for a slot at close
    pending_at_close: int = 0   # waiting or resident at close
    t0: float = 0.0             # host clock at the window's open

    def due_in_window(self, seconds: float) -> list:
        return [u for u, t in self.due.items() if t < seconds]

    def kept(self) -> dict:
        """uid -> EngineResult of the sample and the longest answer."""
        out = dict(self.sample)
        if self.longest[1] is not None:
            out[self.longest[1]] = self.longest[2]
        return out


class Spans:
    """Host spans around the engine's slot API, written into the
    profiler's trace too.  Off (and free) unless ``enabled``."""

    NAMES = ("reset_slots", "step_block", "harvest")

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.recording = False
        self.seconds = collections.defaultdict(list)
        self.occupancy: list = []     # share of slots active, per step
        self.slot_cycles = 0          # slots x cycles the steps covered

    def wrap(self, engine) -> None:
        if not self.enabled:
            return
        for name in self.NAMES:
            setattr(engine, name, self._wrapped(name, getattr(engine,
                                                              name)))

    def unwrap(self, engine) -> None:
        for name in self.NAMES:
            engine.__dict__.pop(name, None)

    def _wrapped(self, name, fn):
        from jax.profiler import TraceAnnotation

        def call(state, *a, **kw):
            if not self.recording:
                return fn(state, *a, **kw)
            if name == "step_block" and state.active.any():
                n = kw.get("n_cycles") or (a[0] if a else None)
                self.occupancy.append(float(state.active.mean()))
                self.slot_cycles += state.slots * int(n)
            t = time.perf_counter()
            with TraceAnnotation(f"bench.{name}"):
                out = fn(state, *a, **kw)
            self.seconds[name].append(time.perf_counter() - t)
            return out
        return call

    def span(self, name):
        if not (self.enabled and self.recording):
            return contextlib.nullcontext()
        from jax.profiler import TraceAnnotation
        return _Timed(self.seconds[name], TraceAnnotation(f"bench.{name}"))


class _Timed:
    def __init__(self, sink, ann):
        self.sink, self.ann = sink, ann

    def __enter__(self):
        self.ann.__enter__()
        self.t = time.perf_counter()

    def __exit__(self, *exc):
        self.sink.append(time.perf_counter() - self.t)
        return self.ann.__exit__(*exc)


def _submit(srv, traffic, arcs, log, uid, i, due, now, Request):
    srv.submit(Request(uid=uid, feeds=traffic.feeds(i, arcs),
                       tenant=int(traffic.tenants[i])))
    log.pool[uid], log.due[uid], log.sent[uid] = i, due, now


def _record(log, done, t):
    for r in done:
        log.done[r.uid] = t
        log.status[r.uid] = r.status
        if r.metrics is not None:
            log.retries += r.metrics.retries
        if r.status != "ok":
            continue
        log.n_ok += 1
        if r.engine.cycles > log.longest[0]:
            log.longest = (r.engine.cycles, r.uid, r.engine)
        if len(log.sample) < log.keep:
            log.sample.append((r.uid, r.engine))
        else:
            j = log.rng.randrange(log.n_ok)
            if j < log.keep:
                log.sample[j] = (r.uid, r.engine)



def window(srv, traffic, arcs, seconds: float, spans: Spans,
           log: Log) -> Log:
    """Serve the cell's traffic for ``seconds``."""
    from repro.serve.types import Request
    pool = len(traffic.lengths)
    clock = time.perf_counter
    n_sent = 0
    spans.recording = True
    t0 = clock()
    with spans.span("window"):
        if traffic.mode == "backlog":
            while True:
                while len(srv.queue) < traffic.queued:
                    now = clock() - t0
                    _submit(srv, traffic, arcs, log, n_sent, n_sent % pool,
                            now, now, Request)
                    n_sent += 1
                with spans.span("heartbeat"):
                    done = srv.step()
                t = clock() - t0
                log.heartbeats += 1
                _record(log, done, t)
                if t >= seconds:
                    break
        else:
            due = traffic.arrivals
            while True:
                now = clock() - t0
                while n_sent < len(due) and due[n_sent] <= now:
                    _submit(srv, traffic, arcs, log, n_sent, n_sent % pool,
                            float(due[n_sent]), now, Request)
                    n_sent += 1
                if now >= seconds:
                    break
                if not srv.pending:
                    nxt = due[n_sent] if n_sent < len(due) else seconds
                    time.sleep(max(min(nxt, seconds) - now, 0.0))
                    continue
                with spans.span("heartbeat"):
                    done = srv.step()
                log.heartbeats += 1
                _record(log, done, clock() - t0)
    log.window_s = clock() - t0
    log.queued_at_close, log.pending_at_close = len(srv.queue), srv.pending
    spans.recording = False
    log.t0 = t0
    return log


def drain(srv, log: Log) -> None:
    """Stop sending and serve what is left, for at most ``DRAIN_S``."""
    clock = time.perf_counter
    while srv.pending and clock() - log.t0 < log.window_s + DRAIN_S:
        _record(log, srv.step(), clock() - log.t0)
    log.drained_s = clock() - log.t0


def latencies_ms(log: Log, seconds: float) -> np.ndarray:
    """Due-to-answer time of every request due in the window.  One that
    was never answered counts with the whole wait it was given, to the
    end of the drain: a lower bound on its latency."""
    uids = log.due_in_window(seconds)
    return np.array([(log.done.get(u, log.drained_s) - log.due[u]) * 1e3
                     for u in uids])
