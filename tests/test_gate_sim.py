"""Gate-level simulation of the ISCAS-85 c6288 array multiplier.

* the generated gate list multiplies: product bits equal ``a * b`` of
  the unpacked patterns;
* the fabric (4 and 8 bits built here, 16 bits from the benchmark's
  frozen netlist) answers, through ``DataflowServer``, what the plain
  gate-list evaluator and ``run_reference`` answer: compacted signatures,
  counts, cycles and firings;
* the 4-bit fabric does the same on the Pallas row kernel (interpret
  mode on the CPU);
* the frozen netlist is today's generator at 16 bits, with the counts
  its configuration states;
* one corrupted word mid-stream changes the compacted answer.
"""
import json
from pathlib import Path

import numpy as np
import pytest

from repro.core import asm, library
from repro.core.engine import run_reference
from repro.kernels import dataflow_fire
from repro.serve.dataflow_server import DataflowServer

CONFIGS = Path(__file__).resolve().parent.parent / "benchmarks" / "chip" \
    / "configs"


def _words(rng, k, bits):
    return rng.integers(-2 ** 31, 2 ** 31, (k, 2 * bits)).astype(np.int32)


def _unpack(words):
    """[bits, k] words (bit t of word j: bit j of pattern t) -> [k, 32]
    integer patterns."""
    w = np.asarray(words).astype(np.uint32).astype(np.uint64)
    bit = (w[:, :, None] >> np.arange(32, dtype=np.uint64)) & np.uint64(1)
    weight = np.uint64(1) << np.arange(w.shape[0], dtype=np.uint64)
    return (bit * weight[:, None, None]).sum(axis=0)


def _fabric(bits):
    if bits == 16:
        return asm.parse((CONFIGS / "c6288_regen.asm").read_text())
    return library.array_multiplier_graph(bits).graph


@pytest.mark.parametrize("bits", (4, 8, 16))
def test_products_equal_a_times_b(bits):
    gates, prod = library.multiplier_gates(bits)
    assert len(prod) == 2 * bits
    ab = _words(np.random.default_rng(bits), 3, bits)
    feeds = library.array_multiplier_graph(bits).make_feeds(ab)
    net = dict(feeds)
    for kind, out, a, b in gates:
        x = net[a] & net[b] if kind == "and" else ~(net[a] | net[b])
        net[out] = x.astype(np.int32)
    stack = lambda names: np.stack([net[x] for x in names])
    A = _unpack(stack([f"a{j}" for j in range(bits)]))
    B = _unpack(stack([f"b{j}" for j in range(bits)]))
    assert (_unpack(stack(prod)) == A * B).all()


def _check(graph, bits, feeds, res):
    """Each answer against the gate-list evaluator and run_reference."""
    for f, r in zip(feeds, res):
        assert r.status == "ok"
        e = r.engine
        sig = library.gate_level_signature(bits, f)
        want = run_reference(graph, f)
        for k in range(2 * bits):
            arc = f"sig_{k}"
            assert int(np.asarray(e.outputs[arc])) == int(sig[k]), k
            assert int(np.asarray(want.outputs[arc])) == int(sig[k]), k
            assert e.counts[arc] == want.counts[arc] == len(f["a0"])
        assert (e.cycles, e.fired) == (want.cycles, want.fired)


@pytest.mark.parametrize("bits,lengths", [(4, (1, 3, 6)), (8, (1, 4)),
                                          (16, (1, 2))])
def test_server_matches_the_plain_reference(bits, lengths):
    graph = _fabric(bits)
    rng = np.random.default_rng(bits)
    mk = library.array_multiplier_graph(bits).make_feeds
    feeds = [mk(_words(rng, k, bits)) for k in lengths]
    srv = DataflowServer(graph, slots=2, block_cycles=16, backend="xla")
    for f in feeds:
        srv.submit(f)
    res = sorted(srv.drain(), key=lambda r: r.uid)
    _check(graph, bits, feeds, res)


def test_row_kernel_serves_the_4_bit_multiplier():
    b = library.BENCHES["array_multiplier"]()
    rng = np.random.default_rng(7)
    feeds = [b.make_feeds(_words(rng, k, 4)) for k in (2, 5, 1)]
    srv = DataflowServer(b.graph, slots=2, block_cycles=16,
                         backend="pallas")
    for f in feeds:
        srv.submit(f)
    res = sorted(srv.drain(), key=lambda r: r.uid)
    assert not srv.degraded and not srv.events
    _check(b.graph, 4, feeds, res)


def test_frozen_netlist_is_the_generator_at_16_bits():
    text = (CONFIGS / "c6288_regen.asm").read_text()
    assert text == asm.emit(library.array_multiplier_graph(16).graph)
    config = json.loads((CONFIGS / "c6288_regen.json").read_text())
    g = asm.parse(text)
    assert config["fabric"] == {
        "nodes": len(g.nodes), "arcs": len(g.arcs),
        "inputs": len(g.input_arcs()), "outputs": len(g.output_arcs())}
    # the row kernel's tables at this size: every arc has a row, and a
    # const bus one row per reader
    lay = dataflow_fire.FabricSpec(dataflow_fire.block_plan_arrays(g)).rows
    assert (lay.dst >= 0).all() and len(set(lay.dst)) == len(lay.dst)
    assert lay.Np >= len(g.nodes) and lay.R >= len(g.arcs)


def test_a_corrupted_word_mid_stream_changes_the_answer():
    b = library.BENCHES["array_multiplier"]()
    ab = _words(np.random.default_rng(3), 5, 4)
    ab[:, 4] = -1                        # b0 = 1: every b is odd
    bad = ab.copy()
    bad[2, 1] ^= 1 << 17                 # pattern 17's bit a1, token 2
    feeds = [b.make_feeds(ab), b.make_feeds(bad)]
    good, wrong = (run_reference(b.graph, f) for f in feeds)
    sig = [library.gate_level_signature(4, f) for f in feeds]
    assert not np.array_equal(sig[0], sig[1])
    got = lambda r: [int(np.asarray(r.outputs[f"sig_{k}"])) for k in range(8)]
    assert got(good) == list(sig[0]) and got(wrong) == list(sig[1])
