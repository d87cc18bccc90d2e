"""Ragged admission staging in ``reset_slots``: only the admitted tokens
travel, and the device writes the admitted rows of the feed buffer.

Every case is checked against a solo ``run`` of each request, bit for
bit (outputs, counts, cycles, firings and, when profiled, each node's),
and against the feed buffer itself: an admitted row holds its stream and
zeros past it; a slot outside the round keeps its rows.  Streams of up
to 48 tokens leave the feed buffer at 64 lanes, which the XLA scatter
places; streams of up to 200 grow it to 256 lanes, which the Pallas
kernel places (interpreted on the CPU).
"""
import functools

import numpy as np
import pytest

from repro.core import library, passes
from repro.core.engine import (DataflowEngine, _slot_reset, feed_capacity,
                               pack_feeds)
from repro.obs import MetricsRegistry, Probe

MAXLEN = {"scatter": 48, "kernel": 200}
PLACEMENTS = sorted(MAXLEN)


@functools.lru_cache(maxsize=None)
def _graph():
    g, _ = passes.optimize_graph(library.BENCHES["fir"]().graph)
    return g


@functools.lru_cache(maxsize=None)
def _engine(kind: str = "dynamic") -> DataflowEngine:
    return DataflowEngine(_graph(), backend="xla", block_cycles=16,
                          profile=kind in ("profiled", "scheduled"),
                          schedule=kind == "scheduled")


def _feeds(seed: int, lens) -> dict:
    """Random int32 streams, one per input arc with its length in
    ``lens``; ``None`` leaves the arc out of the dict."""
    rng = np.random.default_rng(seed)
    return {a: rng.integers(-2 ** 31, 2 ** 31, n).astype(np.int32)
            for a, n in zip(_graph().input_arcs(), lens) if n is not None}


def _lens(seed: int, maxlen: int, n_in: int = 8) -> list:
    return list(np.random.default_rng(seed).integers(1, maxlen + 1, n_in))


def _same(tag, got, want):
    assert (got.cycles, got.fired, got.counts) == \
        (want.cycles, want.fired, want.counts), tag
    for a, c in want.counts.items():
        if c:
            assert int(np.asarray(got.outputs[a])) == \
                int(np.asarray(want.outputs[a])), (tag, a)
    if want.profile is not None:
        # a slot's stall and busy counters span its whole residency;
        # which nodes fired how often is the stream's alone
        got.profile.check()
        assert np.array_equal(got.profile.node_fires,
                              want.profile.node_fires), tag


def _check_rows(eng, st, slots, feeds):
    """Each slot's feed rows and lengths are its packed stream, zero
    past the stream."""
    fv, fl = np.asarray(st.fv), np.asarray(st.fl)
    for b, f in zip(slots, feeds):
        vals, lens = pack_feeds(eng.p["input_arcs"], f, pad_rows=1)
        want = np.zeros_like(fv[b])
        want[:, :vals.shape[1]] = vals
        assert np.array_equal(fv[b], want), b
        assert np.array_equal(fl[b], lens), b


def _finish(eng, st, slots):
    for _ in range(10_000):
        if st.quiesced[slots].all():
            return eng.harvest(st, slots)
        st = eng.step_block(st)
    raise AssertionError("slots never quiesced")


def _round(kind, placement):
    """(engine, slot ids, feed dicts) of one admission round."""
    m = MAXLEN[placement]
    if kind == "missing_and_empty":
        lens = [[m, None, 3, 0, 5, None, 1, 2], [0] * 8, [None] * 8,
                [m // 2, 1, 0, 0, 7, 3, 2, 9]]
        return _engine(), [0, 1, 2, 4], [_feeds(i, x)
                                         for i, x in enumerate(lens)]
    slots = [4, 0, 2] if kind == "out_of_order" else [0, 2, 3]
    feeds = [_feeds(10 + i, _lens(20 + i, m)) for i in range(3)]
    engine = kind if kind in ("profiled", "scheduled") else "dynamic"
    return _engine(engine), slots, feeds


@pytest.mark.parametrize("placement", PLACEMENTS)
@pytest.mark.parametrize("kind", ["unequal_arcs", "missing_and_empty",
                                  "out_of_order", "profiled", "scheduled"])
def test_round_matches_solo_runs(kind, placement):
    eng, slots, feeds = _round(kind, placement)
    st = eng.reset_slots(eng.init_state(5), slots, feeds)
    assert st.fv.shape[2] == (64 if placement == "scatter" else 256)
    _check_rows(eng, st, slots, feeds)
    assert np.array_equal(np.asarray(st.active_dev),
                          np.isin(np.arange(5), slots).astype(np.int32))
    _, got = _finish(eng, st, slots)
    for b, f, r in zip(slots, feeds, got):
        _same((kind, placement, b), r, eng.run(f))


@pytest.mark.parametrize("placement", PLACEMENTS)
def test_refill_with_a_shorter_stream_leaves_no_stale_tail(placement):
    """A slot refilled with a shorter stream than it held reads zeros
    past it, and the slot that rides alongside keeps its rows bit for
    bit across the round."""
    eng = _engine()
    m = MAXLEN[placement]
    long_ = _feeds(1, [m - 8] * 8)
    ride = _feeds(2, [m] * 8)
    st = eng.reset_slots(eng.init_state(3), [0, 2], [long_, ride])
    st, _ = _finish(eng, st, [0])
    kept = np.asarray(st.fv)[2].copy(), np.asarray(st.fl)[2].copy()
    short = _feeds(3, [3, 1, 2, 0, 5, 4, 1, 2])
    st = eng.reset_slots(st, [0], [short])
    _check_rows(eng, st, [0], [short])
    assert np.array_equal(np.asarray(st.fv)[2], kept[0])
    assert np.array_equal(np.asarray(st.fl)[2], kept[1])
    _, got = _finish(eng, st, [0, 2])
    _same((placement, "short"), got[0], eng.run(short))
    _same((placement, "ride"), got[1], eng.run(ride))


@pytest.mark.parametrize("placement", PLACEMENTS)
def test_overflowing_round_splits_over_dispatches(placement):
    """Twelve full-length requests hold 12 n_in L tokens, more than the
    capacity of 8 n_in L: the round takes two dispatches of one shape,
    ``admit_splits`` counts the second, and every result still matches
    its solo run."""
    eng = _engine()
    m = 64 if placement == "scatter" else 256
    n_in = len(eng.p["input_arcs"])
    feeds = [_feeds(30 + i, [m] * n_in) for i in range(12)]
    mr = MetricsRegistry()
    st = eng.reset_slots(eng.init_state(12), list(range(12)), feeds,
                         obs=Probe(metrics=mr))
    assert st.fv.shape[2] == m and 12 * n_in * m > feed_capacity(n_in, m)
    c = mr.snapshot()["counters"]
    assert c["admit_splits"] == 1
    assert c["h2d_bytes{site=admit}"] == \
        2 * 4 * (feed_capacity(n_in, m) + 12 * (n_in + 3))
    _check_rows(eng, st, range(12), feeds)
    _, got = _finish(eng, st, list(range(12)))
    for i, (f, r) in enumerate(zip(feeds, got)):
        _same((placement, i), r, eng.run(f))


def test_rounds_of_any_size_reuse_one_jitted_reset():
    """At a fixed feed buffer length, rounds admitting one, three or
    two requests add no jitted reset and count no retrace; a round
    that fits admits in one dispatch."""
    eng = _engine()
    mr = MetricsRegistry()
    obs = Probe(metrics=mr)
    st = eng.reset_slots(eng.init_state(6), [5], [_feeds(1, [40] * 8)],
                         obs=obs)
    size, c0 = _slot_reset._cache_size(), mr.snapshot()["counters"]
    slots = [[0, 1, 2], [3, 4]]
    for i, ids in enumerate(slots):
        feeds = [_feeds(50 + 10 * i + j, _lens(j, 40)) for j in ids]
        st = eng.reset_slots(st, ids, feeds, obs=obs)
        _check_rows(eng, st, ids, feeds)
    c = mr.snapshot()["counters"]
    assert _slot_reset._cache_size() == size
    assert c["retraces{what=feed_buffer}"] == \
        c0["retraces{what=feed_buffer}"] == 1
    assert "admit_splits" not in c
    assert c["h2d_bytes{site=admit}"] == 3 * c0["h2d_bytes{site=admit}"]
