"""Pallas ``run()``/``run_batch()``: one wave of streams, held to the oracle.

A wave starts every stream together, steps them in blocks of K cycles
until the last one quiesces or the cap cuts it, and reads them all back.
Every stream's result must equal ``run_reference``'s in every field; its
``dispatches`` is the number of blocks the wave took, and its profile
counts every cycle the wave simulated, so a stream that quiesced early
keeps adding its idle cycle's stall and occupancy counts until the wave
ends.  A scheduled engine whose schedule bails runs the same wave.
"""
import numpy as np
import pytest

from repro.core import library, schedule
from repro.core.engine import DataflowEngine, run_reference

K = 8
LENGTHS = (1, 3, 6, 9)
UNCUT = 1001            # far past every stream, and not a multiple of K


def _batch(name):
    bench = library.BENCHES[name]()
    return bench, [library.random_feeds(name, bench, k,
                                        np.random.default_rng(k))
                   for k in LENGTHS]


def _cut_cap(graph, feeds_batch):
    """A cap that cuts the longest stream short, not a multiple of K."""
    cap = max(run_reference(graph, f).cycles for f in feeds_batch) - 2
    return cap - 1 if cap % K == 0 else cap


def _same(got, want, tag):
    assert got.cycles == want.cycles, tag
    assert got.fired == want.fired, tag
    assert got.counts == want.counts, tag
    for a, c in want.counts.items():
        if c:
            assert np.asarray(got.outputs[a]).item() == \
                np.asarray(want.outputs[a]).item(), (tag, a)


def _profile_over(graph, feeds, cap, cycles):
    """The oracle's profile counted over ``cycles`` simulated cycles:
    past quiescence every cycle repeats the oracle's last, idle one."""
    p = run_reference(graph, feeds, max_cycles=cap, profile=True).profile
    want = {f: getattr(p, f).astype(np.int64)
            for f in ("node_fires", "stall_in", "stall_out", "arc_busy",
                      "arc_hw")}
    extra = cycles - p.cycles
    if extra:
        prev = run_reference(graph, feeds, max_cycles=p.cycles - 1,
                             profile=True).profile
        for f in ("stall_in", "stall_out", "arc_busy"):
            want[f] += extra * (getattr(p, f) - getattr(prev, f))
    return want


@pytest.mark.parametrize("cut", (False, True), ids=("uncut", "cut"))
@pytest.mark.parametrize("profile", (False, True), ids=("plain", "profiled"))
@pytest.mark.parametrize("name", ("fir", "bubble_sort", "gcd"))
def test_pallas_wave_matches_reference(name, profile, cut):
    bench, fb = _batch(name)
    cap = _cut_cap(bench.graph, fb) if cut else UNCUT
    assert cap % K
    wants = [run_reference(bench.graph, f, max_cycles=cap) for f in fb]
    assert any(w.cycles == cap for w in wants) == cut
    blocks = (max(w.cycles for w in wants) - 1) // K + 1
    simulated = min(blocks * K, cap)
    eng = DataflowEngine(bench.graph, backend="pallas", block_cycles=K,
                         profile=profile)
    got = eng.run_batch(fb, max_cycles=cap)
    assert len(got) == len(fb)
    for b, (g, w) in enumerate(zip(got, wants)):
        tag = (name, b)
        _same(g, w, tag)
        assert g.dispatches == blocks, tag
        if not profile:
            assert g.profile is None, tag
            continue
        assert (g.profile.cycles, g.profile.dispatches) == \
            (simulated, blocks), tag
        g.profile.check()
        for f, x in _profile_over(bench.graph, fb[b], cap,
                                  simulated).items():
            np.testing.assert_array_equal(getattr(g.profile, f), x,
                                          err_msg=str((tag, f)))


@pytest.mark.parametrize("backend", ("xla", "pallas"))
def test_schedule_bail_falls_back_to_the_dynamic_run(backend, monkeypatch):
    """A schedule that finds no period in budget bails; run() then runs
    the dynamic fabric, bit-identical to the oracle, and counts the
    bail once (xla runs in one dispatch, pallas in one a block)."""
    bench = library.BENCHES["fir"]()
    feeds = library.random_feeds("fir", bench, 6, np.random.default_rng(0))
    want = run_reference(bench.graph, feeds, profile=True)
    eng = DataflowEngine(bench.graph, backend=backend, block_cycles=K,
                         schedule="auto", profile=True)
    assert eng._sched_on
    monkeypatch.setattr(schedule, "BAIL_STEPS", 0)
    got = eng.run(feeds)
    assert eng.sched_bails == 1
    _same(got, want, backend)
    np.testing.assert_array_equal(got.node_fires, want.node_fires)
    blocks = (want.cycles - 1) // K + 1
    assert got.dispatches == (1 if backend == "xla" else blocks)


def test_ragged_batch_on_a_scheduled_engine_runs_the_dynamic_wave():
    """A schedule covers one tuple of feed lengths, so a ragged batch
    on a scheduled pallas engine runs the dynamic wave: no bail is
    counted, and every stream matches the oracle."""
    bench, fb = _batch("fir")
    wants = [run_reference(bench.graph, f) for f in fb]
    eng = DataflowEngine(bench.graph, backend="pallas", block_cycles=K,
                         schedule="auto")
    assert eng._sched_on
    got = eng.run_batch(fb)
    assert eng.sched_bails == 0
    blocks = (max(w.cycles for w in wants) - 1) // K + 1
    for b, (g, w) in enumerate(zip(got, wants)):
        _same(g, w, b)
        assert g.dispatches == blocks, b
