"""Continuous-batching dataflow serving: per-slot stream lifecycle.

The paper's fabric serves one token stream; ``DataflowEngine.run_batch``
(PR 1) serves B streams as a *wave* — all admitted together, the
dispatch loop running until the slowest stream quiesces, so short
requests idle in their slots.  This module removes the wave barrier:

* a :class:`DataflowServer` owns a FIFO request queue and B live
  *slots* on one block-fused fabric (the engine's resumable slot API,
  DESIGN.md §7);
* after each K-cycle block it detects per-slot quiescence (idle block
  tail — idle is absorbing), harvests finished requests, and refills
  those slots from the queue *while the other slots keep running*;
* free/quiesced slots are clock-gated out of feed/fire/drain by the
  per-stream active mask in ``fire_block_batched_pallas`` (the
  "per-row cache clock" serve/engine.py flags as future work for the
  LM path).

This is the serving analogue of a circuit-switched reconfigurable
fabric multiplexing independent stream computations through shared
operators with per-stream flow control (Li et al., arXiv:1310.3356):
the node/arc tables are the shared operator array, a slot is a
circuit, and admission is reconfiguration-free because every request
of a graph signature reuses one compiled plan.

Determinism: admissions happen only at block boundaries and each slot
carries its own cycle clock, so every request's
:class:`~repro.core.engine.EngineResult` is bit-identical to running
it alone via ``DataflowEngine.run`` — regardless of what rides the
other slots or of admission order (property-tested in
tests/test_dataflow_server.py).

Traced programs (:mod:`repro.front`, DESIGN.md §9) serve through the
same machinery: a ``TracedProgram`` is a ``Graph``, so its assembler
emission is its cache signature like any hand-assembled fabric —
:meth:`DataflowServer.for_fn` traces and serves in one step.

Loop programs (DESIGN.md §10) are where per-slot lifecycle earns its
keep: a ``lax.while_loop``-bearing request has a *data-dependent trip
count*, so its residency is unknowable at admission.  Each request is
one loop initiation (:meth:`DataflowServer.submit_args`); the slot's
idle-tail detection IS the loop-termination signal (the exit BRANCH
drains the result and the cycle goes quiet), short loops harvest and
refill while long ones keep iterating, and a divergent loop is
force-harvested at its cycle cap with ``metrics.truncated`` set
instead of wedging its slot.

Fault tolerance (PR 6, DESIGN.md §11): the server is hardened for a
hostile multi-tenant environment, the setting Weisensee & Nathan's
self-reconfigurable platform targets (PAPERS.md, cs/0411075) — shared
reconfigurable hardware must survive misbehaving workloads:

* **bounded admission** — ``max_queue`` + ``policy`` ("reject" |
  "block" | "drop-oldest") with round-robin fairness across
  ``Request.tenant`` keys (:mod:`repro.serve.admission`);
* **deadlines and budgets** — ``Request.deadline_blocks`` expires a
  request (queued or resident) like truncation;
  ``Request.max_cycles`` overrides the engine cap per slot;
* **the stall watchdog** — a slot whose progress counters freeze for
  ``wedge_timeout_blocks`` without quiescing is force-harvested with
  ``metrics.wedged``;
* **error isolation and degradation** — dispatch failures retry with
  exponential backoff; persistent failures tear down only the failing
  backend: residents are re-queued (front of their tenant bucket) and
  restarted on the next backend of the ``pallas → xla → reference``
  chain, the terminal reference mode executing requests one-at-a-time
  on the host with per-request ``Result(error=...)`` capture.  The
  server *always* answers: ``step()``/``drain()`` never raise a
  workload-induced error (property-tested in
  tests/test_server_robustness.py under a seeded
  :class:`~repro.serve.faults.FaultPlan`), and a faulty slot is torn
  down without perturbing co-resident circuits — unfaulted requests
  stay bit-identical to solo runs (Li et al.'s per-circuit isolation).
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import logging
import time
from typing import Iterable, Mapping

import numpy as np

from repro.core import asm
from repro.core.engine import (BACKENDS, PLAN_CACHE_STATS, DataflowEngine,
                               run_reference)
from repro.core.partition import resolve_partition
from repro.core.graph import Graph
from repro.obs.probe import Probe
from repro.serve.admission import (POLICIES, DroppedError, FairQueue,
                                   QueueFullError, Rejected)
from repro.serve.types import (InvalidRequestError, Request,
                               RequestMetrics, Result)

log = logging.getLogger(__name__)

# ---------------------------------------------------------------------------
# Compiled-plan cache: many requests, one fabric
# ---------------------------------------------------------------------------
_ENGINE_CACHE: "collections.OrderedDict[tuple, DataflowEngine]" = \
    collections.OrderedDict()
_ENGINE_CACHE_MAX = 64      # LRU bound: a long-running service sees a
                            # finite fabric vocabulary; evicted engines
                            # stay alive wherever still referenced
CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0,
               # live view of the process-wide _plan memo (engine-level;
               # ROADMAP item 3): same dict object, not a snapshot
               "plan": PLAN_CACHE_STATS}


def graph_signature(graph: Graph) -> str:
    """Canonical text of a fabric (assembler emission: consts + node
    table with arc labels).  Two graphs with equal signatures compile
    to identical plans, so their requests can share one engine."""
    return asm.emit(graph)


def cached_engine(graph: Graph, *, backend: str = "xla",
                  block_cycles: int = 16,
                  max_cycles: int = 100_000,
                  token_shape: tuple = (), dtype=np.int32,
                  optimize: bool = False,
                  profile: bool = False,
                  schedule: bool | str = False,
                  partition=None) -> DataflowEngine:
    """Engine for (graph signature, backend, K, token_shape, dtype,
    optimize, profile, schedule, partition) — compiled once, shared by
    every server/request that presents the same fabric (the cache key
    hashes the signature, not the graph object, so structurally equal
    graphs share).

    token_shape/dtype/optimize/profile/schedule/partition are part of
    the key: two servers over the same fabric signature with different
    token shapes or opt flags compile to different plans and must not
    collide on one engine (a profiled engine threads §12 counter state
    through every step, so it cannot share dispatch plans with an
    unprofiled one; a scheduled engine replaces the block stepper
    entirely, so it cannot alias the dynamic engine for the same
    signature; a partitioned engine runs the §14 multi-fabric stepper
    whose state carries channel registers, so a sharded and an unsharded
    compile — or two different region assignments — must never alias).
    The partition key component is ``Partition.spec()``: region count +
    assignment hash."""
    if backend not in BACKENDS:
        raise ValueError(f"backend {backend!r} not in {BACKENDS}")
    token_shape = tuple(int(d) for d in token_shape)
    dtype = np.dtype(str(dtype)) if isinstance(dtype, str) \
        else np.dtype(dtype)
    part = resolve_partition(graph, partition)
    if part is not None and part.P <= 1:
        part = None            # degenerate: same engine as unsharded
    key = (hashlib.sha256(graph_signature(graph).encode()).hexdigest(),
           backend, int(block_cycles), int(max_cycles),
           token_shape, dtype.str, bool(optimize), bool(profile),
           str(schedule), "none" if part is None else part.spec())
    eng = _ENGINE_CACHE.get(key)
    if eng is None:
        CACHE_STATS["misses"] += 1
        eng = DataflowEngine(graph, token_shape, dtype,
                             backend=backend,
                             block_cycles=block_cycles,
                             max_cycles=max_cycles,
                             optimize=optimize,
                             profile=profile,
                             schedule=schedule,
                             partition=part)
        _ENGINE_CACHE[key] = eng
        while len(_ENGINE_CACHE) > _ENGINE_CACHE_MAX:
            _ENGINE_CACHE.popitem(last=False)
            CACHE_STATS["evictions"] += 1
    else:
        CACHE_STATS["hits"] += 1
        _ENGINE_CACHE.move_to_end(key)
    return eng


def clear_engine_cache() -> None:
    _ENGINE_CACHE.clear()
    CACHE_STATS["hits"] = CACHE_STATS["misses"] = 0
    CACHE_STATS["evictions"] = 0


# Degradation order: each backend's next-best survivor.  "reference" is
# terminal — the pure-host oracle has no device dispatch to fail, so a
# server can always still answer from there.
FALLBACK_CHAIN = ("pallas", "xla", "reference")

_NEVER = np.iinfo(np.int64).max     # expiry block of a request without one


# ---------------------------------------------------------------------------
# The server
# ---------------------------------------------------------------------------
class DataflowServer:
    """Request-level continuous batching over one block-fused fabric.

    Usage::

        srv = DataflowServer(graph, slots=8, block_cycles=16,
                             backend="pallas",
                             max_queue=64, policy="reject")
        srv.submit(feeds_a)            # returns uid (or typed Rejected)
        srv.submit(Request(uid=7, feeds=feeds_b, deadline_blocks=50))
        done = srv.step()              # one K-cycle block; may finish 0+
        rest = srv.drain()             # run until queue + slots empty

    ``step()`` is the scheduler heartbeat: expire deadline-blown
    requests, force-harvest budget-exhausted and wedged slots, admit
    from the queue into free slots (round-robin across tenants),
    advance every active slot by one K-cycle block (one device
    dispatch, retried with exponential backoff on transient failures),
    harvest slots whose block had an idle tail.  A persistent dispatch
    or compile failure degrades the server down the
    ``pallas → xla → reference`` chain instead of raising — every
    submitted request receives exactly one :class:`Result` (value,
    truncated, expired, wedged, or typed error).
    """

    def __init__(self, graph: Graph, slots: int = 8,
                 block_cycles: int = 16, backend: str = "xla",
                 max_cycles: int = 100_000,
                 engine: DataflowEngine | None = None,
                 optimize: bool = False,
                 max_queue: int | None = None, policy: str = "reject",
                 wedge_timeout_blocks: int = 32,
                 max_retries: int = 3, retry_backoff_s: float = 0.0,
                 faults=None, profile: bool = False,
                 trace=None, metrics=None,
                 schedule: bool | str = False,
                 partition=None):
        if slots < 1:
            raise ValueError("slots must be >= 1")
        if policy not in POLICIES:
            raise ValueError(f"policy {policy!r} not in {POLICIES}")
        if max_queue is not None and max_queue < 1:
            raise ValueError("max_queue must be >= 1 (or None: unbounded)")
        if wedge_timeout_blocks < 1:
            raise ValueError("wedge_timeout_blocks must be >= 1")
        self.graph = graph
        self.slots = slots
        self.max_cycles = int(max_cycles)
        self.max_queue = max_queue
        self.policy = policy
        self.wedge_timeout_blocks = int(wedge_timeout_blocks)
        self.max_retries = int(max_retries)
        self.retry_backoff_s = float(retry_backoff_s)
        self.faults = faults
        # observability (DESIGN.md §12): profile=True compiles §12
        # fabric counters into every slot step, so each harvested
        # Result carries result.engine.profile (a FabricProfile);
        # trace/metrics accept a repro.obs TraceRecorder /
        # MetricsRegistry (or None: zero recording overhead).  Either one
        # turns on the hot path's spans and counters through a Probe
        # bound to this server alone: the engine may be shared.
        self.profile = bool(profile)
        self.trace = trace
        self.metrics = metrics
        self._obs = None if trace is None and metrics is None \
            else Probe(trace, metrics)
        if trace is not None and trace.annotate is None:
            # spans land in the profiler's trace beside the device ops
            from jax.profiler import TraceAnnotation
            trace.annotate = TraceAnnotation
        if faults is not None and trace is not None \
                and getattr(faults, "notify", None) is None:
            # injected faults land on the trace timeline next to the
            # lifecycle events they cause
            faults.notify = lambda kind, *key: self._trace(
                "fault", injected=kind, key=list(map(str, key)))
        self._block_cycles = int(block_cycles)
        self._optimize = bool(optimize)
        # schedule="auto" serves static firing schedules (DESIGN.md
        # §13) when the fabric is schedulable, dynamic otherwise; it
        # rides the cache key so scheduled and dynamic engines for the
        # same fabric signature never alias
        self._schedule = schedule
        # partition=P|"auto"|Partition serves the fabric sharded across
        # regions (DESIGN.md §14) — results stay bit-identical, so the
        # reference fallback simply ignores it
        self._partition = partition
        self._input_arcs = tuple(graph.input_arcs())
        self.queue = FairQueue()
        self.block = 0            # server block clock (dispatches issued)
        self.admission_rounds = 0  # fused reset dispatches issued
        self.max_queue_depth = 0   # high-water mark of the queue
        self.events: list[dict] = []   # degradations/retries/drops log
        self._queued_at: dict[int, int] = {}     # uid -> block at submit
        self._resident: dict[int, tuple[Request, int]] = {}  # slot -> (req, admitted)
        # slot -> block at which its request expires (_NEVER: no
        # deadline), so the heartbeat finds expired slots in one pass
        self._expire_at = np.full((slots,), _NEVER, np.int64)
        self._retries: dict[int, int] = {}       # uid -> dispatch retries
        self._wedge_traced: set[int] = set()     # first-wedge trace dedupe
        self._degraded_uids: set[int] = set()    # restarted by degradation
        self._done: list[Result] = []  # results finished out-of-band
        #                                (drops, blocking-submit pumps)
        self._auto_uid = 0
        self._reference = False
        self.engine: DataflowEngine | None = None
        self.state = None
        build = -1 if self._obs is None else self._obs.begin(
            "dataflow.build", nodes=len(graph.nodes), arcs=len(graph.arcs))
        if engine is not None:
            # an explicit engine wins over backend/block_cycles/max_cycles
            # (block size is a perf knob, never a semantics one), but it
            # must serve THIS fabric — a mismatched plan would silently
            # produce another graph's results
            if graph_signature(engine.graph) != graph_signature(graph):
                raise ValueError(
                    "engine= was compiled for a different fabric "
                    f"({engine.graph.name!r}, not {graph.name!r})")
            self._primary_backend = engine.backend
            self.engine = engine
            self.max_cycles = engine.max_cycles
            self.profile = bool(engine.profile)  # the engine decides
        else:
            if backend not in BACKENDS:
                raise ValueError(f"backend {backend!r} not in {BACKENDS}")
            self._primary_backend = backend
            # construction-time fallback: a backend whose engine cannot
            # be built (fault-injected or real) degrades immediately —
            # the server comes up answering, just slower
            for be in self._chain_from(backend):
                if be == "reference":
                    self._enter_reference(None)
                    break
                try:
                    if self.faults is not None:
                        self.faults.check_compile(be)
                    # optimize=True shares the opcode-class-specialized
                    # plan (DESIGN.md §8) across every slot; it joins the
                    # cache key because specialized and dense plans
                    # compile differently
                    self.engine = cached_engine(
                        graph, backend=be, block_cycles=block_cycles,
                        max_cycles=max_cycles, optimize=optimize,
                        profile=self.profile, schedule=schedule,
                        partition=partition)
                    break
                except Exception as e:
                    self._log_event("compile-degrade", backend=be,
                                    error=repr(e))
        if self.engine is not None and not self._reference:
            self.state = self.engine.init_state(slots)
        if metrics is not None:
            # reads 0 until a queued request's deadline comes due
            metrics.counter("expiry_sweeps", where="queue")
        if self._obs is not None:
            self._obs.end(build, kernel="reference" if self.state is None
                          else self.engine.kernel_choice(slots))

    # -- construction helpers -------------------------------------------
    def _chain_from(self, backend: str) -> tuple[str, ...]:
        if backend in FALLBACK_CHAIN:
            return FALLBACK_CHAIN[FALLBACK_CHAIN.index(backend):]
        return (backend, *FALLBACK_CHAIN)

    def _log_event(self, kind: str, **kw) -> None:
        ev = dict(kind=kind, block=self.block, **kw)
        self.events.append(ev)
        log.warning("dataflow-server %s: %s", kind, kw)

    # -- observability plumbing (no-ops when trace/metrics are None) ----
    def _trace(self, kind: str, *, uid=None, slot=None, tenant=None,
               status=None, block=None, **args) -> None:
        """Record one lifecycle event at the server's block clock (or an
        explicit ``block`` when the event's RequestMetrics timestamp
        differs, e.g. the reference path's finished_block)."""
        if self.trace is not None:
            self.trace.record(
                kind, block=self.block if block is None else block,
                uid=uid, slot=slot,
                tenant=None if tenant is None else str(tenant),
                status=status, **args)

    def _count(self, name: str, n: int = 1, **labels) -> None:
        if self.metrics is not None:
            self.metrics.counter(name, **labels).inc(n)

    def _update_queue_metrics(self, tenants) -> None:
        """Set the total queue-depth gauge and the gauges of
        ``tenants`` (an iterable, left unread without a registry): the
        tenants whose depth just changed."""
        if self.metrics is None:
            return
        self.metrics.gauge("queue_depth").set(len(self.queue))
        for t in tenants:
            self.metrics.gauge("queue_depth", tenant=str(t)).set(
                self.queue.depth(t))

    def _observe_result(self, res: Result) -> Result:
        """Per-request terminal accounting — every Result passes
        through here exactly once, whichever path produced it."""
        if self.metrics is None:
            return res
        self._count("requests_finished", status=res.status)
        m = res.metrics
        if m is not None:
            self.metrics.histogram("queue_wait_blocks").observe(
                m.queue_wait_blocks)
            if m.residency_cycles:
                self.metrics.histogram("residency_cycles").observe(
                    m.residency_cycles)
            if m.backend:
                self._count("requests_served", backend=m.backend)
        return res

    @property
    def backend(self) -> str:
        """Backend currently serving (may differ from the requested one
        after degradation)."""
        return "reference" if self._reference else self.engine.backend

    @property
    def degraded(self) -> bool:
        return self.backend != self._primary_backend

    @classmethod
    def for_fn(cls, fn, *avals, const_args=None, name=None,
               **server_kw) -> "DataflowServer":
        """Serve a traced Python program: lower ``fn`` through the
        :mod:`repro.front` frontend and build the server on the
        synthesized fabric.  A traced program is just another asm
        signature to the compiled-plan cache, so structurally-equal
        traces (across servers, across processes re-tracing the same
        source) share one engine.  The program's positional feed
        adapter rides along as ``server.make_feeds``::

            srv = DataflowServer.for_fn(
                lambda x, y: jnp.where(x > y, x - y, y - x),
                np.int32, np.int32, slots=8, backend="pallas")
            srv.submit(srv.make_feeds([5, 1], [2, 9]))
        """
        from repro.front import trace
        prog = trace(fn, *avals, name=name, const_args=const_args)
        srv = cls(prog, **server_kw)
        srv.traced = prog
        srv.make_feeds = prog.make_feeds
        return srv

    def submit_args(self, *args) -> int:
        """Submit one *evaluation* of a traced program (``for_fn``
        servers): ``make_feeds(*args)`` + ``submit`` in one step.  This
        is the natural request shape for loop fabrics (DESIGN.md §10):
        one initiation per request, data-dependent trip count inside
        the slot, per-slot quiescence detection ending it — requests
        that never quiesce are force-harvested at their cycle cap with
        ``metrics.truncated`` set."""
        if not hasattr(self, "make_feeds"):
            raise AttributeError(
                "submit_args needs a server built by for_fn (only "
                "traced programs carry a positional feed adapter)")
        return self.submit(self.make_feeds(*args))

    # -- admission ------------------------------------------------------
    def submit(self, request):
        """Enqueue a request (a :class:`Request` or a bare feeds dict);
        returns its uid, or a typed :class:`Rejected` when the queue is
        at ``max_queue`` under ``policy="reject"``.  uids must be
        unique among in-flight requests — auto-assigned ones skip any
        the caller has taken."""
        if isinstance(request, Mapping) or request is None:
            while self._auto_uid + 1 in self._queued_at:
                self._auto_uid += 1
            self._auto_uid += 1
            request = Request(uid=self._auto_uid, feeds=dict(request or {}))
        if not isinstance(request, Request):
            raise TypeError(f"submit wants a Request or feeds dict, "
                            f"got {type(request).__name__}")
        # field validation (typed): a deadline or cycle budget below 1
        # could never run — deadline_blocks=0 would expire on the very
        # heartbeat that admits it, max_cycles=0 would truncate a slot
        # before its first cycle
        if request.deadline_blocks is not None \
                and request.deadline_blocks < 1:
            raise InvalidRequestError(
                f"request {request.uid}: deadline_blocks must be >= 1, "
                f"got {request.deadline_blocks}")
        if request.max_cycles is not None and request.max_cycles < 1:
            raise InvalidRequestError(
                f"request {request.uid}: max_cycles must be >= 1, "
                f"got {request.max_cycles}")
        if request.feeds is None:
            raise ValueError(f"request {request.uid} has no feeds — the "
                             "dataflow server serves feed-stream requests")
        if request.uid in self._queued_at:
            raise ValueError(f"uid {request.uid} is already in flight")
        # fail fast on feeds the fabric cannot take: admission batches
        # several requests into one fused reset, so a bad request must
        # be rejected here, not poison its fused reset batch.  Unknown
        # arcs have nowhere to go; MISSING arcs would strand the fabric
        # mid-computation waiting on tokens that never arrive (the slot
        # then burns its whole cycle budget before truncating).
        unknown = set(request.feeds) - set(self._input_arcs)
        if unknown:
            raise ValueError(f"request {request.uid}: feeds for "
                             f"non-input arcs: {sorted(unknown)}")
        missing = [a for a in self._input_arcs if a not in request.feeds]
        if missing:
            raise ValueError(
                f"request {request.uid}: missing feeds for input arcs "
                f"{missing} — every input arc needs a stream")
        # bounded admission (DESIGN.md §11)
        changed = {request.tenant}
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            if self.policy == "reject":
                self._trace("reject", uid=request.uid,
                            tenant=request.tenant,
                            queue_depth=len(self.queue))
                self._count("requests_rejected",
                            tenant=str(request.tenant))
                return Rejected(uid=request.uid,
                                reason=f"queue full ({self.max_queue})",
                                queue_depth=len(self.queue),
                                tenant=request.tenant)
            if self.policy == "drop-oldest":
                victim = self.queue.drop_oldest()
                changed.add(victim.tenant)
                queued = self._queued_at.pop(victim.uid)
                self._retries.pop(victim.uid, None)
                self._log_event("drop-oldest", uid=victim.uid,
                                tenant=victim.tenant)
                self._trace("drop", uid=victim.uid, tenant=victim.tenant,
                            status="error")
                self._count("requests_dropped", tenant=str(victim.tenant))
                self._done.append(self._observe_result(Result(
                    uid=victim.uid,
                    error=DroppedError(
                        f"request {victim.uid} dropped by admission "
                        f"(queue full at {self.max_queue}, "
                        f"policy=drop-oldest)"),
                    metrics=self._queue_only_metrics(queued))))
            else:       # "block": the submitting host pumps heartbeats
                guard = 0
                while len(self.queue) >= self.max_queue:
                    self._done.extend(self._step_inner())
                    guard += 1
                    if guard > 1_000_000:
                        raise QueueFullError(
                            "blocking submit pumped 1e6 heartbeats "
                            "without a queue slot freeing")
        if self.faults is not None and request.feeds:
            poisoned = self.faults.poison(request.feeds, request.uid,
                                          np.int32)
            if poisoned is not request.feeds:
                self._log_event("poison", uid=request.uid)
                self._trace("poison", uid=request.uid,
                            tenant=request.tenant)
                request = dataclasses.replace(request, feeds=poisoned)
        self.queue.push(request, self._expiry(request, self.block))
        self._queued_at[request.uid] = self.block
        self.max_queue_depth = max(self.max_queue_depth, len(self.queue))
        self._trace("submit", uid=request.uid, tenant=request.tenant,
                    queue_depth=len(self.queue))
        self._count("requests_submitted", tenant=str(request.tenant))
        self._update_queue_metrics(changed)
        return request.uid

    def _queue_only_metrics(self, queued: int,
                            expired: bool = False) -> RequestMetrics:
        """Metrics for a request that never reached a slot (dropped or
        expired while queued): slot == -1, no residency."""
        return RequestMetrics(
            slot=-1, queued_block=queued, admitted_block=-1,
            finished_block=self.block,
            queue_wait_blocks=self.block - queued,
            residency_blocks=0, residency_cycles=0, tokens_out=0,
            expired=expired, backend="",
            degraded=self.degraded)

    @staticmethod
    def _expiry(req: Request, queued: int) -> int | None:
        """Block at which ``req``, queued at block ``queued``, expires
        (None: it has no deadline)."""
        return None if req.deadline_blocks is None \
            else queued + req.deadline_blocks

    def _admit(self) -> None:
        if not self.queue:
            return
        free = self.state.free_slots()
        if not free:
            return
        obs = self._obs
        if obs is not None:
            sp = obs.begin("dataflow.admit")
        batch = [(b, self.queue.pop())
                 for b in free[:min(len(free), len(self.queue))]]
        self.state = self.engine.reset_slots(
            self.state, [b for b, _ in batch],
            [r.feeds for _, r in batch],
            caps=[r.max_cycles for _, r in batch], obs=obs)
        self.admission_rounds += 1
        for b, r in batch:
            self._resident[b] = (r, self.block)
            if r.deadline_blocks is not None:    # past int64: never
                self._expire_at[b] = min(
                    self._expiry(r, self._queued_at[r.uid]), _NEVER)
            self._trace("admit", uid=r.uid, slot=b, tenant=r.tenant,
                        queue_wait_blocks=self.block
                        - self._queued_at[r.uid])
            self._count("requests_admitted", tenant=str(r.tenant))
        self._update_queue_metrics(r.tenant for _, r in batch)
        if obs is not None:
            obs.admitted += len(batch)
            obs.end(sp, rows=len(batch))

    # -- heartbeat ------------------------------------------------------
    def step(self) -> list[Result]:
        """One scheduler heartbeat; returns the requests that finished
        (possibly none) — including any completed out-of-band since the
        last call (queue drops, blocking-submit pumps).

        A heartbeat's block never lets any slot cross its cycle cap
        (engine ``max_cycles`` or ``Request.max_cycles``): it is
        shortened to the smallest remaining per-slot budget when one
        nears its cap (block partitioning does not change cycle
        semantics — property-tested across K), so even a truncated
        request simulates exactly its cap, bit-identical to a solo
        ``run`` under the same cap.

        With a trace recorder the heartbeat is the span
        ``dataflow.heartbeat``; its self time is the scheduler's own
        (DESIGN.md §12)."""
        done, self._done = self._done, []
        obs = self._obs
        if obs is None:
            return done + self._step_inner()
        sp = obs.begin("dataflow.heartbeat", block=self.block)
        obs.admitted = obs.active = 0
        out = []
        try:
            out = done + self._step_inner()
        finally:
            obs.end(sp, active=obs.active, admitted=obs.admitted,
                    finished=len(out))
        return out

    def _step_inner(self) -> list[Result]:
        results = self._expire_queued()
        if self._reference:
            return results + self._step_reference()
        # 1. deadline / budget / watchdog exits on resident slots
        #    (precedence: expired > truncated > wedged), each found by
        #    one pass over the slot arrays, in ascending slot order
        if self._resident:
            st = self.state
            running = (st.active != 0) & ~st.quiesced
            expired = running & (self._expire_at <= self.block)
            truncated = running & ~expired & (st.base >= st.cap)
            wedged = (st.active != 0) & ~expired & ~truncated \
                & (st.stalled >= self.wedge_timeout_blocks)
            for mask, kind in ((expired, "expired"),
                               (truncated, "truncated"),
                               (wedged, "wedged")):
                results += self._harvest_slots(
                    np.flatnonzero(mask).tolist(), kind=kind)
        # 2. admission (round-robin across tenants)
        self._admit()
        if not self._resident:
            return results
        # 3. advance one block — with retry, then degradation
        st = self.state
        n_cycles = min(self.engine.block_cycles,
                       int((st.cap - st.base)[st.active != 0].min()))
        obs = self._obs
        if obs is not None:
            obs.active = len(self._resident)
            sp = obs.begin("dataflow.step", n_cycles=n_cycles)
        try:
            self.state = self._dispatch_block(n_cycles)
        except Exception as e:      # retries exhausted: degrade, requeue
            if obs is not None:
                obs.end(sp)
            self._degrade(e)
            return results
        if obs is not None:
            obs.end(sp)
        self.block += 1
        self._count("dispatches", backend=self.engine.backend)
        # 4. harvest quiesced slots; a fault-wedged request's quiescence
        #    signal is suppressed (the slot stalls until the watchdog)
        done = self.state.quiesced_slots()
        if self.faults is not None:
            wedged = [b for b in done
                      if self.faults.wedge(self._resident[b][0].uid)]
            for b in wedged:
                self.state.quiesced[b] = False
                req = self._resident[b][0]
                if req.uid not in self._wedge_traced:
                    # wedging suppresses quiescence every block; trace
                    # only the first suppression per request
                    self._wedge_traced.add(req.uid)
                    self._trace("wedge", uid=req.uid, slot=b,
                                tenant=req.tenant)
            done = [b for b in done if b not in wedged]
        return results + self._harvest_slots(done)

    def _expire_queued(self) -> list[Result]:
        """Deadline sweep over the queue: requests whose budget elapsed
        before admission are answered as expired without ever touching
        a slot.  The queue's deadline index says when one is due, so a
        heartbeat with none due walks nothing."""
        due = self.queue.earliest_expiry()
        if due is None or due > self.block:
            return []
        self._count("expiry_sweeps", where="queue")
        expired = self.queue.remove_if(
            lambda r: r.deadline_blocks is not None
            and self.block - self._queued_at[r.uid] >= r.deadline_blocks)
        results = []
        for r in expired:
            queued = self._queued_at.pop(r.uid)
            self._retries.pop(r.uid, None)
            self._trace("expire", uid=r.uid, tenant=r.tenant,
                        status="expired", queued_block=queued)
            results.append(self._observe_result(Result(
                uid=r.uid,
                metrics=self._queue_only_metrics(queued, expired=True))))
        if expired:
            self._update_queue_metrics(r.tenant for r in expired)
        return results

    def _dispatch_block(self, n_cycles: int):
        """One device dispatch, retried with exponential backoff on
        transient failures; raises once ``max_retries`` is exhausted
        (the caller degrades the backend)."""
        attempt = 0
        while True:
            try:
                if self.faults is not None:
                    err = self.faults.dispatch_error(
                        self.engine.backend, self.block, attempt)
                    if err is not None:
                        raise err
                return self.engine.step_block(self.state,
                                              n_cycles=n_cycles,
                                              obs=self._obs)
            except Exception as e:
                attempt += 1
                if attempt > self.max_retries:
                    raise
                for req, _ in self._resident.values():
                    self._retries[req.uid] = \
                        self._retries.get(req.uid, 0) + 1
                self._log_event("dispatch-retry", attempt=attempt,
                                backend=self.engine.backend,
                                error=repr(e))
                self._trace("retry", attempt=attempt,
                            backend=self.engine.backend, error=repr(e))
                self._count("dispatch_retries",
                            backend=self.engine.backend)
                if self.retry_backoff_s > 0.0:
                    time.sleep(self.retry_backoff_s * 2 ** (attempt - 1))

    def _degrade(self, err: Exception) -> None:
        """Tear down the failing backend: re-queue every resident
        request (front of its tenant bucket, original uid and deadline
        intact — execution restarts from the feeds, which is
        deterministic) and bring up the next backend in the chain."""
        failed = self.engine.backend
        seats = [(b, self._resident[b][0]) for b in sorted(self._resident)]
        victims = [req for _, req in seats]
        self._resident.clear()
        self._expire_at[:] = _NEVER
        for req in reversed(victims):
            self.queue.push_front(
                req, self._expiry(req, self._queued_at[req.uid]))
            self._degraded_uids.add(req.uid)
        self.max_queue_depth = max(self.max_queue_depth, len(self.queue))
        self._log_event("degrade", from_backend=failed, error=repr(err),
                        requeued=[r.uid for r in victims])
        self._trace("degrade", from_backend=failed, error=repr(err))
        self._count("degradations", from_backend=failed)
        for b, req in seats:
            # the requeue closes the victim's slot span on the trace
            self._trace("requeue", uid=req.uid, slot=b,
                        tenant=req.tenant, from_backend=failed)
        self._update_queue_metrics(r.tenant for r in victims)
        chain = self._chain_from(failed)
        for be in chain[1:] if chain[0] == failed else chain:
            if be == "reference":
                self._enter_reference(err)
                return
            try:
                if self.faults is not None:
                    self.faults.check_compile(be)
                self.engine = cached_engine(
                    self.graph, backend=be,
                    block_cycles=self._block_cycles,
                    max_cycles=self.max_cycles, optimize=self._optimize,
                    profile=self.profile, schedule=self._schedule)
                self.state = self.engine.init_state(self.slots)
                self._log_event("degrade-to", backend=be)
                return
            except Exception as e:
                self._log_event("compile-degrade", backend=be,
                                error=repr(e))
        self._enter_reference(err)      # unreachable fallback of fallbacks

    def _enter_reference(self, err: Exception | None) -> None:
        """Terminal degradation: serve from the pure-numpy oracle, one
        request per free capacity unit per heartbeat, every failure
        captured per-request.  No device, no dispatch — nothing left to
        fail wholesale."""
        self._reference = True
        self.engine = None
        self.state = None
        self._log_event("degrade-to", backend="reference",
                        error=repr(err) if err else None)

    def _step_reference(self) -> list[Result]:
        results, tenants = [], set()
        for _ in range(self.slots):
            if not self.queue:
                break
            req = self.queue.pop()
            tenants.add(req.tenant)
            queued = self._queued_at.pop(req.uid)
            cap = req.max_cycles or self.max_cycles
            er, err = None, None
            if self.faults is not None:
                err = self.faults.reference_error(req.uid)
            if err is None:
                try:
                    er = run_reference(self.graph, req.feeds, (),
                                       np.int32, cap,
                                       profile=self.profile)
                    er.dispatches = 1
                except Exception as e:
                    err = e
            res = Result(
                uid=req.uid, engine=er, error=err,
                metrics=RequestMetrics(
                    slot=-1, queued_block=queued,
                    admitted_block=self.block,
                    finished_block=self.block + 1,
                    queue_wait_blocks=self.block - queued,
                    residency_blocks=1,
                    residency_cycles=er.cycles if er else 0,
                    tokens_out=sum(er.counts.values()) if er else 0,
                    truncated=bool(er and er.cycles >= cap),
                    degraded=self.degraded,
                    retries=self._retries.pop(req.uid, 0),
                    backend="reference"))
            # slot == -1: reference requests never open a slot span, so
            # the harvest is an instant + tenant-span close only; the
            # block stamp matches metrics.finished_block
            self._trace("harvest", uid=req.uid, slot=-1,
                        tenant=req.tenant, status=res.status,
                        block=self.block + 1, backend="reference")
            results.append(self._observe_result(res))
        if results:
            self.block += 1
            self._update_queue_metrics(tenants)
        return results

    def _harvest_slots(self, done: list[int],
                       kind: str = "ok") -> list[Result]:
        if not done:
            return []
        obs = self._obs
        if obs is not None:
            sp = obs.begin("dataflow.harvest", rows=len(done), kind=kind)
        self.state, engine_results = self.engine.harvest(self.state, done,
                                                         obs=obs)
        if obs is not None:
            sp_results = obs.begin("dataflow.harvest.results")
        results = []
        self._expire_at[done] = _NEVER
        for b, er in zip(done, engine_results):
            req, admitted = self._resident.pop(b)
            # strict: a uid resident in a slot MUST have submit-time
            # accounting; a silent fallback here would mask the very
            # bookkeeping bug it pretends to tolerate
            queued = self._queued_at.pop(req.uid)
            self._wedge_traced.discard(req.uid)
            res = Result(
                uid=req.uid, engine=er,
                metrics=RequestMetrics(
                    slot=b, queued_block=queued, admitted_block=admitted,
                    finished_block=self.block,
                    queue_wait_blocks=admitted - queued,
                    residency_blocks=er.dispatches,
                    residency_cycles=er.cycles,
                    tokens_out=sum(er.counts.values()),
                    truncated=kind == "truncated",
                    expired=kind == "expired",
                    wedged=kind == "wedged",
                    degraded=(req.uid in self._degraded_uids
                              or self.degraded),
                    retries=self._retries.pop(req.uid, 0),
                    backend=self.engine.backend))
            self._trace("harvest", uid=req.uid, slot=b, tenant=req.tenant,
                        status=res.status, cycles=er.cycles,
                        fired=er.fired, tokens_out=res.metrics.tokens_out,
                        backend=self.engine.backend)
            results.append(self._observe_result(res))
        if obs is not None:
            obs.end(sp_results)
            obs.end(sp)
        return results

    def drain(self) -> list[Result]:
        """Step until the queue and every slot are empty."""
        out: list[Result] = []
        while self.queue or self._resident or self._done:
            out.extend(self.step())
        return out

    def run(self, requests: Iterable) -> list[Result]:
        """Serve a closed workload: submit everything, drain, return
        results sorted by uid."""
        for r in requests:
            self.submit(r)
        return sorted(self.drain(), key=lambda r: r.uid)

    @property
    def pending(self) -> int:
        return len(self.queue) + len(self._resident) + len(self._done)
