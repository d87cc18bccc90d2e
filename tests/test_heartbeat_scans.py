"""The server's heartbeat against a plain per-slot form of its scans.

``DataflowServer`` finds the expired, truncated and wedged slots, the
block length, the free and the quiesced slots with array passes over
the slot state, and sweeps the queue for expired requests only when its
deadline index says one is due.  :class:`PerSlotServer` keeps the plain
form of the same heartbeat: a comprehension over the resident slots for
each exit, a generator for the block length, a loop over every slot for
the free and the quiesced ones, and a walk of the whole queue on every
heartbeat.  Both serve the same requests, with deadlines, per-request
cycle caps, injected wedges, a ``drop-oldest`` queue and a late
failure of the backend (which re-queues the resident requests), and
must hand back the same Results in the same order on every heartbeat.
"""
import numpy as np
import pytest

from repro.core import library
from repro.obs import MetricsRegistry
from repro.serve.dataflow_server import DataflowServer
from repro.serve.faults import FaultPlan
from repro.serve.types import Request, Result


def free_slots(st) -> list[int]:
    return [b for b in range(st.slots) if not st.active[b]]


def quiesced_slots(st) -> list[int]:
    return [b for b in range(st.slots) if st.active[b] and st.quiesced[b]]


class PerSlotServer(DataflowServer):
    """The heartbeat with per-slot scans and an unindexed queue sweep."""

    def _deadline_blown(self, b: int) -> bool:
        req, _ = self._resident[b]
        return (req.deadline_blocks is not None
                and self.block - self._queued_at[req.uid]
                >= req.deadline_blocks)

    def _expire_queued(self) -> list[Result]:
        expired = self.queue.remove_if(
            lambda r: r.deadline_blocks is not None
            and self.block - self._queued_at[r.uid] >= r.deadline_blocks)
        results = []
        for r in expired:
            queued = self._queued_at.pop(r.uid)
            self._retries.pop(r.uid, None)
            results.append(self._observe_result(Result(
                uid=r.uid,
                metrics=self._queue_only_metrics(queued, expired=True))))
        return results

    def _admit(self) -> None:
        free = free_slots(self.state)
        if not (free and self.queue):
            return
        batch = []
        while free and self.queue:
            batch.append((free.pop(0), self.queue.pop()))
        self.state = self.engine.reset_slots(
            self.state, [b for b, _ in batch],
            [r.feeds for _, r in batch],
            caps=[r.max_cycles for _, r in batch])
        self.admission_rounds += 1
        for b, r in batch:
            self._resident[b] = (r, self.block)

    def _step_inner(self) -> list[Result]:
        results = self._expire_queued()
        if self._reference:
            return results + self._step_reference()
        results += self._harvest_slots(
            [b for b in sorted(self._resident)
             if not self.state.quiesced[b] and self._deadline_blown(b)],
            kind="expired")
        results += self._harvest_slots(
            [b for b in sorted(self._resident)
             if not self.state.quiesced[b]
             and self.state.base[b] >= self.state.cap[b]],
            kind="truncated")
        results += self._harvest_slots(
            [b for b in sorted(self._resident)
             if int(self.state.stalled[b]) >= self.wedge_timeout_blocks],
            kind="wedged")
        self._admit()
        if not self._resident:
            return results
        n_cycles = min(
            self.engine.block_cycles,
            min(int(self.state.cap[b]) - int(self.state.base[b])
                for b in self._resident))
        try:
            self.state = self._dispatch_block(n_cycles)
        except Exception as e:
            self._degrade(e)
            return results
        self.block += 1
        done = quiesced_slots(self.state)
        wedged = [b for b in done
                  if self.faults.wedge(self._resident[b][0].uid)]
        for b in wedged:
            self.state.quiesced[b] = False
        done = [b for b in done if b not in wedged]
        return results + self._harvest_slots(done)


def view(res: Result) -> tuple:
    """What a Result says: uid, status, metrics, and the fabric's answer."""
    e = res.engine
    answer = None if e is None else (
        e.cycles, e.fired, e.dispatches, dict(e.counts),
        {a: int(np.asarray(v)) for a, v in e.outputs.items()})
    return (res.uid, res.status, res.metrics, type(res.error), answer)


def requests(bench, rng, first_uid: int, n: int) -> list[Request]:
    """``n`` requests with random lengths, tenants, deadlines and caps."""
    out = []
    for uid in range(first_uid, first_uid + n):
        deadline = int(rng.integers(1, 12)) if rng.random() < 0.4 else None
        cap = int(rng.integers(3, 24)) if rng.random() < 0.3 else None
        out.append(Request(
            uid=uid, tenant=int(rng.integers(4)), deadline_blocks=deadline,
            max_cycles=cap, feeds=library.random_feeds(
                "vector_sum", bench, int(rng.integers(1, 10)), rng)))
    return out


def server(cls, bench, slots: int, seed: int, **kw):
    return cls(bench.graph, slots=slots, block_cycles=4, backend="xla",
               max_queue=slots, policy="drop-oldest",
               wedge_timeout_blocks=3,
               faults=FaultPlan(seed, wedge_rate=0.15,
                                persistent_backends={"xla"},
                                persistent_from_block=36), **kw)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("slots", [8, 64])
def test_heartbeat_matches_the_per_slot_scans(slots, seed):
    bench = library.vector_sum_graph(8)
    rng = np.random.default_rng([seed, slots])
    metrics = MetricsRegistry()
    srv = server(DataflowServer, bench, slots, seed, metrics=metrics)
    ref = server(PerSlotServer, bench, slots, seed)
    uid, beats, queue_expiries = 1, 0, 0
    seen = {"expired": 0, "truncated": 0, "wedged": 0, "error": 0, "ok": 0}
    while beats < 40 or srv.pending or ref.pending:
        if beats < 40:
            for r in requests(bench, rng, uid, int(rng.integers(0, slots))):
                assert srv.submit(r) == ref.submit(r) == r.uid
                uid += 1
        got, want = srv.step(), ref.step()
        assert [view(r) for r in got] == [view(r) for r in want], beats
        queue_expiries += any(r.status == "expired" and r.metrics.slot < 0
                              for r in want)
        for r in want:
            seen[r.status] += 1
        beats += 1
        assert srv.block == ref.block and beats < 500
    # the workload reached every exit of the slot lifecycle
    assert all(seen.values()), seen
    assert srv.backend == ref.backend == "reference"
    # the queue was swept exactly in the heartbeats that expired some of it
    assert metrics.counter("expiry_sweeps", where="queue").value \
        == queue_expiries > 0
