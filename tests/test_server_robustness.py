"""Fault-tolerance layer of the serving stack (DESIGN.md §11, PR 6).

The acceptance property: under a seeded
:class:`~repro.serve.faults.FaultPlan` — dispatch failures, wedged
slots, poisoned feeds — every submitted request receives exactly one
:class:`~repro.serve.types.Result` (value / truncated / expired /
wedged / typed error), ``step()``/``drain()`` never raise a
workload-induced error, and unfaulted co-resident requests stay
bit-identical to solo ``DataflowEngine.run`` results.
"""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import library
from repro.core.engine import DataflowEngine, run_reference
from repro.obs import MetricsRegistry
from repro.serve.admission import DroppedError, FairQueue, Rejected
from repro.serve.dataflow_server import DataflowServer
from repro.serve.faults import FaultPlan, InjectedFault
from repro.serve.types import Request

CHIP = Path(__file__).resolve().parent.parent / "benchmarks" / "chip"
MIXES = CHIP / "mixes"          # the chip benchmark's traffic mixes
sys.path.insert(0, str(CHIP))   # for its traffic generator


@pytest.fixture()
def bench():
    return library.vector_sum_graph(8)


def _feeds(bench, k, seed=0):
    return library.random_feeds("vector_sum", bench,
                                k, np.random.default_rng(seed))


def _same(got, want, tag=""):
    assert got.cycles == want.cycles, tag
    assert got.fired == want.fired, tag
    assert got.counts == want.counts, tag
    for a, c in want.counts.items():
        if c:
            assert int(np.asarray(got.outputs[a])) == \
                int(np.asarray(want.outputs[a])), (tag, a)


# ---------------------------------------------------------------------------
# bounded admission
# ---------------------------------------------------------------------------
def test_reject_policy_returns_typed_rejection(bench):
    srv = DataflowServer(bench.graph, slots=1, block_cycles=4,
                         max_queue=2, policy="reject")
    assert srv.submit(_feeds(bench, 2, 0)) == 1
    assert srv.submit(_feeds(bench, 2, 1)) == 2
    rej = srv.submit(Request(uid=99, feeds=_feeds(bench, 2, 2),
                             tenant="t9"))
    assert isinstance(rej, Rejected) and not rej     # falsy by design
    assert rej.uid == 99 and rej.queue_depth == 2 and rej.tenant == "t9"
    # the rejected request was never enqueued: exactly 2 results
    results = srv.drain()
    assert sorted(r.uid for r in results) == [1, 2]
    assert all(r.status == "ok" for r in results)
    # after the drain there is room again
    assert srv.submit(Request(uid=99, feeds=_feeds(bench, 2, 2))) == 99


def test_block_policy_applies_backpressure(bench):
    srv = DataflowServer(bench.graph, slots=2, block_cycles=4,
                         max_queue=1, policy="block")
    uids = [srv.submit(_feeds(bench, 1 + i % 3, i)) for i in range(6)]
    # blocking submits pumped heartbeats: some requests already finished
    # out-of-band and surface through step()/drain()
    results = {r.uid: r for r in srv.drain()}
    assert sorted(results) == sorted(uids)
    assert all(r.status == "ok" for r in results.values())
    assert srv.pending == 0


def test_drop_oldest_policy_answers_the_victim(bench):
    srv = DataflowServer(bench.graph, slots=1, block_cycles=4,
                         max_queue=2, policy="drop-oldest")
    u1 = srv.submit(Request(uid=1, feeds=_feeds(bench, 2, 0), tenant="a"))
    u2 = srv.submit(Request(uid=2, feeds=_feeds(bench, 2, 1), tenant="a"))
    u3 = srv.submit(Request(uid=3, feeds=_feeds(bench, 2, 2), tenant="b"))
    assert (u1, u2, u3) == (1, 2, 3)
    # tenant "a" is the most backlogged -> its oldest (uid 1) is evicted
    results = {r.uid: r for r in srv.drain()}
    assert sorted(results) == [1, 2, 3]
    assert isinstance(results[1].error, DroppedError)
    assert results[1].status == "error"
    assert results[1].metrics.slot == -1          # never reached a slot
    assert results[2].status == "ok" and results[3].status == "ok"
    assert any(e["kind"] == "drop-oldest" and e["uid"] == 1
               for e in srv.events)


def test_fair_queue_round_robins_across_tenants():
    q = FairQueue()
    for uid, t in [(1, "a"), (2, "a"), (3, "a"), (4, "b"), (5, None)]:
        q.push(Request(uid=uid, feeds={}, tenant=t))
    assert len(q) == 5
    assert [q.pop().uid for _ in range(5)] == [1, 4, 5, 2, 3]
    with pytest.raises(IndexError):
        q.pop()


def test_fairness_one_tenant_cannot_starve_another(bench):
    srv = DataflowServer(bench.graph, slots=1, block_cycles=4)
    for i in range(5):                       # tenant "flood" queues 5
        srv.submit(Request(uid=10 + i, feeds=_feeds(bench, 2, i),
                           tenant="flood"))
    srv.submit(Request(uid=1, feeds=_feeds(bench, 2, 9), tenant="solo"))
    order = [r.uid for r in srv.drain()]
    # round-robin: solo's single request rides the second admission,
    # not behind all five of flood's
    assert order.index(1) <= 1


# ---------------------------------------------------------------------------
# the queue's deadline index
# ---------------------------------------------------------------------------
def test_fair_queue_earliest_expiry_tracks_every_exit():
    rng = np.random.default_rng(5)
    q, expires, uid = FairQueue(), {}, 0
    assert q.earliest_expiry() is None
    for _ in range(600):
        op = rng.choice(["push", "push_front", "pop", "drop_oldest",
                         "remove_if"], p=[.35, .1, .3, .15, .1])
        if op in ("push", "push_front") or not len(q):
            uid += 1
            e = int(rng.integers(0, 50)) if rng.random() < 0.5 else None
            r = Request(uid=uid, feeds={}, tenant=int(rng.integers(3)))
            getattr(q, "push_front" if op == "push_front" else "push")(
                r, expires=e)
            expires[uid] = e
        elif op == "pop":
            expires.pop(q.pop().uid)
        elif op == "drop_oldest":
            expires.pop(q.drop_oldest().uid)
        else:
            cut = int(rng.integers(0, 50))
            for r in q.remove_if(lambda r: (expires[r.uid] or 99) <= cut):
                expires.pop(r.uid)
        assert sorted(r.uid for r in q) == sorted(expires)
        due = [e for e in expires.values() if e is not None]
        assert q.earliest_expiry() == (min(due) if due else None)


def test_queued_deadline_expires_in_its_block_with_one_sweep(bench):
    metrics = MetricsRegistry()
    srv = DataflowServer(bench.graph, slots=1, block_cycles=1,
                         metrics=metrics)
    srv.submit(Request(uid=1, feeds=_feeds(bench, 8, 0)))    # hogs the slot
    for uid in range(2, 6):
        srv.submit(Request(uid=uid, feeds=_feeds(bench, 2, uid)))
    srv.step()
    srv.submit(Request(uid=9, feeds=_feeds(bench, 2, 9), deadline_blocks=4))
    sweeps = metrics.counter("expiry_sweeps", where="queue")
    walks = []
    remove_if = srv.queue.remove_if
    srv.queue.remove_if = lambda pred: walks.append(srv.block) \
        or remove_if(pred)
    expired_at = None
    while srv.pending:
        block = srv.block
        for r in srv.step():
            if r.uid == 9:
                expired_at = block
                assert r.status == "expired" and r.metrics.slot == -1
                assert r.metrics.finished_block == block == 1 + 4
                assert r.metrics.queue_wait_blocks == 4
    assert expired_at == 5 and walks == [5] and sweeps.value == 1


def test_deadline_past_int64_never_expires(bench):
    srv = DataflowServer(bench.graph, slots=1, block_cycles=1)
    srv.submit(Request(uid=1, feeds=_feeds(bench, 3, 0)))
    srv.submit(Request(uid=2, feeds=_feeds(bench, 3, 1),
                       deadline_blocks=2 ** 70))
    assert [(r.uid, r.status) for r in srv.drain()] == [(1, "ok"), (2, "ok")]


@pytest.mark.parametrize("mix", sorted(p.stem for p in MIXES.glob("*.json")))
def test_traffic_without_deadlines_never_walks_the_queue(bench, mix):
    import traffic
    slots, arcs = 8, bench.graph.input_arcs()
    load = traffic.generate(json.loads((MIXES / f"{mix}.json").read_text()),
                            len(arcs), slots, 2 ** 40 + 7, 1.0)
    metrics = MetricsRegistry()
    srv = DataflowServer(bench.graph, slots=slots, block_cycles=16,
                         metrics=metrics)
    srv.queue.remove_if = None          # a walk would raise
    uid = 0
    for _ in range(30):
        while len(srv.queue) < load.queued:
            i = uid % len(load.lengths)
            srv.submit(Request(uid=uid, feeds=load.feeds(i, arcs),
                               tenant=int(load.tenants[i])))
            uid += 1
        srv.step()
    assert srv.block == 30
    assert metrics.snapshot()["counters"]["expiry_sweeps{where=queue}"] == 0


# ---------------------------------------------------------------------------
# deadlines and budgets
# ---------------------------------------------------------------------------
def test_deadline_expires_queued_request_without_a_slot(bench):
    srv = DataflowServer(bench.graph, slots=1, block_cycles=1)
    srv.submit(Request(uid=1, feeds=_feeds(bench, 8, 0)))    # hogs the slot
    srv.submit(Request(uid=2, feeds=_feeds(bench, 2, 1),
                       deadline_blocks=2))
    results = {r.uid: r for r in srv.drain()}
    assert results[2].status == "expired"
    assert results[2].metrics.slot == -1
    assert results[2].metrics.admitted_block == -1
    assert results[2].engine is None
    assert results[1].status == "ok"


def test_deadline_expires_resident_request_with_partial_results(bench):
    srv = DataflowServer(bench.graph, slots=2, block_cycles=1)
    srv.submit(Request(uid=1, feeds=_feeds(bench, 8, 0),
                       deadline_blocks=3))
    srv.submit(Request(uid=2, feeds=_feeds(bench, 2, 1)))
    results = {r.uid: r for r in srv.drain()}
    assert results[1].status == "expired" and results[1].metrics.expired
    assert results[1].metrics.slot >= 0          # it was resident
    assert results[1].engine is not None         # partial results delivered
    assert results[1].engine.cycles < 20
    # the co-resident request is untouched
    _same(results[2].engine,
          DataflowEngine(bench.graph, backend="xla",
                         block_cycles=1).run(_feeds(bench, 2, 1)))


def test_per_request_max_cycles_matches_solo_capped_run(bench):
    feeds = _feeds(bench, 8, 0)
    solo = DataflowEngine(bench.graph, backend="xla", block_cycles=4,
                          max_cycles=6).run(feeds)
    srv = DataflowServer(bench.graph, slots=2, block_cycles=4)
    srv.submit(Request(uid=1, feeds=feeds, max_cycles=6))
    srv.submit(Request(uid=2, feeds=_feeds(bench, 2, 1)))    # co-resident
    results = {r.uid: r for r in srv.drain()}
    assert results[1].status == "truncated"
    _same(results[1].engine, solo, "per-request cap")
    assert results[2].status == "ok"


# ---------------------------------------------------------------------------
# wedged-slot watchdog
# ---------------------------------------------------------------------------
def test_watchdog_harvests_wedged_slot(bench):
    plan = FaultPlan(wedge_uids={1})
    srv = DataflowServer(bench.graph, slots=2, block_cycles=4,
                         wedge_timeout_blocks=3, faults=plan)
    srv.submit(_feeds(bench, 2, 0))              # uid 1: wedged
    srv.submit(_feeds(bench, 3, 1))              # uid 2: clean
    results = {r.uid: r for r in srv.drain()}
    assert results[1].status == "wedged" and results[1].metrics.wedged
    assert results[2].status == "ok"
    # the wedge suppressed the *signal*, not the computation: the
    # harvested values still equal a solo run, and the slot was freed
    eng = DataflowEngine(bench.graph, backend="xla", block_cycles=4)
    _same(results[1].engine, eng.run(_feeds(bench, 2, 0)), "wedged")
    _same(results[2].engine, eng.run(_feeds(bench, 3, 1)), "clean")
    assert not srv.state.active.any() and srv.pending == 0


# ---------------------------------------------------------------------------
# retry / degradation chain
# ---------------------------------------------------------------------------
def test_transient_dispatch_fault_is_retried(bench):
    plan = FaultPlan(dispatch_fail_blocks={0, 1}, transient_attempts=2)
    srv = DataflowServer(bench.graph, slots=2, block_cycles=4,
                         max_retries=3, faults=plan)
    srv.submit(_feeds(bench, 2, 0))
    results = {r.uid: r for r in srv.drain()}
    assert results[1].status == "ok"
    assert results[1].metrics.retries >= 2
    assert not results[1].metrics.degraded       # retries never degrade
    assert any(e["kind"] == "dispatch-retry" for e in srv.events)
    _same(results[1].engine,
          DataflowEngine(bench.graph, backend="xla",
                         block_cycles=4).run(_feeds(bench, 2, 0)))


def test_persistent_fault_degrades_pallas_to_xla(bench):
    plan = FaultPlan(persistent_backends={"pallas"})
    srv = DataflowServer(bench.graph, slots=2, block_cycles=4,
                         backend="pallas", max_retries=1, faults=plan)
    feeds = [_feeds(bench, 2, 0), _feeds(bench, 3, 1)]
    for f in feeds:
        srv.submit(f)
    results = {r.uid: r for r in srv.drain()}
    assert srv.backend == "xla" and srv.degraded
    eng = DataflowEngine(bench.graph, backend="xla", block_cycles=4)
    for uid, f in zip((1, 2), feeds):
        r = results[uid]
        assert r.status == "ok"
        assert r.metrics.degraded and r.metrics.backend == "xla"
        _same(r.engine, eng.run(f), ("degraded", uid))
    kinds = [e["kind"] for e in srv.events]
    assert "degrade" in kinds and "degrade-to" in kinds


def test_persistent_fault_degrades_xla_to_reference(bench):
    plan = FaultPlan(persistent_backends={"xla"},
                     persistent_from_block=1)
    srv = DataflowServer(bench.graph, slots=2, block_cycles=4,
                         backend="xla", max_retries=1, faults=plan)
    feeds = [_feeds(bench, 2, 0), _feeds(bench, 3, 1), _feeds(bench, 4, 2)]
    for f in feeds:
        srv.submit(f)
    results = {r.uid: r for r in srv.drain()}
    assert srv.backend == "reference"
    assert sorted(results) == [1, 2, 3]
    for uid, f in zip((1, 2, 3), feeds):
        r = results[uid]
        assert r.status == "ok" and r.metrics.backend == "reference"
        _same(r.engine, run_reference(bench.graph, f), ("reference", uid))
    # the degraded server still accepts and answers new work
    uid = srv.submit(_feeds(bench, 2, 7))
    again = {r.uid: r for r in srv.drain()}
    assert again[uid].status == "ok"


def test_compile_fault_falls_back_at_construction(bench):
    plan = FaultPlan(compile_fail={"pallas"})
    srv = DataflowServer(bench.graph, slots=2, block_cycles=4,
                         backend="pallas", faults=plan)
    assert srv.backend == "xla" and srv.degraded
    srv.submit(_feeds(bench, 2, 0))
    results = srv.drain()
    assert results[0].status == "ok" and results[0].metrics.degraded
    assert any(e["kind"] == "compile-degrade" and e["backend"] == "pallas"
               for e in srv.events)


def test_reference_mode_server_and_per_request_errors(bench):
    plan = FaultPlan(reference_fail_uids={2})
    srv = DataflowServer(bench.graph, slots=2, backend="reference",
                         faults=plan)
    assert srv.backend == "reference"
    feeds = [_feeds(bench, 2, 0), _feeds(bench, 3, 1), _feeds(bench, 4, 2)]
    for f in feeds:
        srv.submit(f)
    results = {r.uid: r for r in srv.drain()}
    assert sorted(results) == [1, 2, 3]
    # the faulted request is *answered* with a typed error; its
    # neighbours compute normally
    assert results[2].status == "error"
    assert isinstance(results[2].error, InjectedFault)
    _same(results[1].engine, run_reference(bench.graph, feeds[0]))
    _same(results[3].engine, run_reference(bench.graph, feeds[2]))


# ---------------------------------------------------------------------------
# poisoned feeds
# ---------------------------------------------------------------------------
def test_poisoned_feeds_do_not_perturb_neighbours(bench):
    plan = FaultPlan(poison_uids={2})
    srv = DataflowServer(bench.graph, slots=3, block_cycles=4,
                         faults=plan)
    feeds = [_feeds(bench, 3, i) for i in range(3)]
    for f in feeds:
        srv.submit({a: np.array(v) for a, v in f.items()})
    results = {r.uid: r for r in srv.drain()}
    eng = DataflowEngine(bench.graph, backend="xla", block_cycles=4)
    # clean neighbours: bit-identical to solo runs on the clean feeds
    _same(results[1].engine, eng.run(feeds[0]), "clean 1")
    _same(results[3].engine, eng.run(feeds[2]), "clean 3")
    # the poisoned request computes deterministically over the poisoned
    # feeds (wraparound is the ALU contract) — compare against a solo
    # run over the same poison (poison() is idempotent)
    _same(results[2].engine, eng.run(plan.poison(feeds[1], 2)), "poisoned")
    assert ("poison", 2) in plan.log


# ---------------------------------------------------------------------------
# satellite bugfixes
# ---------------------------------------------------------------------------
def test_submit_rejects_missing_input_arcs(bench):
    srv = DataflowServer(bench.graph, slots=1)
    feeds = _feeds(bench, 2, 0)
    missing_arc = sorted(feeds)[0]
    bad = {a: v for a, v in feeds.items() if a != missing_arc}
    with pytest.raises(ValueError, match=missing_arc):
        srv.submit(bad)
    assert srv.pending == 0 and not srv._queued_at   # nothing half-queued
    srv.submit(feeds)                                # full feeds still fine
    assert srv.drain()[0].status == "ok"


def test_harvest_accounting_is_strict(bench):
    """The submit-time accounting for a resident uid must exist at
    harvest: a silent default would mask bookkeeping corruption, so the
    pop is strict (regression for the `.pop(uid, admitted)` fallback)."""
    srv = DataflowServer(bench.graph, slots=1, block_cycles=4)
    uid = srv.submit(_feeds(bench, 2, 0))
    srv.step()                              # admit + first block
    del srv._queued_at[uid]                 # corrupt the books
    with pytest.raises(KeyError):
        srv.drain()
