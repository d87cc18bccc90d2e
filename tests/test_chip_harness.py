"""The chip benchmark's harness tests (``benchmarks/chip/tests``), run
with the rest of the suite.  They need no chip: runs go through the
harness on the CPU, with the Pallas kernel in interpret mode."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent
                       / "benchmarks" / "chip" / "tests"))

from test_chip_bench import *  # noqa: E402,F401,F403
from test_program_spans import *  # noqa: E402,F401,F403


# The harness's copy of this test (benchmarks/chip/tests) pins the dense
# staging bytes of every admission round, B + 4 B n_in L + ...; the
# engine now stages only the admitted tokens (ragged staging), so the
# suite runs this copy, which checks the new bytes, under the same name.
# The benchmark's copy is brought up to date by a benchmark change.
def test_readers_on_a_traced_tiny_run(root, on_cpu, program_traced):
    from repro.core.engine import _staged_size
    cell = cells.load_cell("tiny.backlog", root)
    s = run.serve(cell, BIG, 0.8, False, DEVICE)
    obs = program_traced["obs"]
    view = run.RunView("backlog", 0.8, s.setup_s, s.log, s.traffic, s.spans,
                       None, {}, 8, 4, None)
    view.obs = obs
    got = {m: cells.reader(m, root)(view) for m in NEW_METRICS}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert obs.heartbeats() == s.log.heartbeats > 0
    # the program's spans sit inside the harness's spans around the
    # engine's slot API
    admit = sum(program_spans.per_heartbeat_ms(obs, f"dataflow.admit.{k}")
                for k in ("pack", "h2d", "dispatch"))
    admit_ms = cells.reader("admit_ms", root)(view)
    assert 0.5 * admit_ms < admit <= admit_ms
    step = sum(program_spans.per_heartbeat_ms(obs, f"dataflow.step.{k}")
               for k in ("dispatch", "wait"))
    assert 0.5 * cells.reader("step_block_ms", root)(view) < step \
        <= cells.reader("step_block_ms", root)(view)
    # every dispatch of a round stages one buffer: the admitted streams
    # at the staging capacity, then each slot's lengths, mask, order
    # and active flag
    st = s.srv.state
    B, n_in, L = st.fv.shape
    dispatches = obs.counter("admit_splits") + int(
        (obs.spans["name"] == "dataflow.admit").sum())
    rows = obs.counter("requests_admitted")
    n = lambda name: int((obs.spans["name"] == name).sum())
    assert got["h2d_kib_per_admit.backlog"] == pytest.approx(
        dispatches * 4 * _staged_size(B, n_in, L) / rows / 1024, rel=1e-12)
    assert obs.counter("retraces") == 0 == s.compiles_in_window
    # each step reads back fired counts and last progress (one int32 a
    # slot each), each harvest the output registers of every slot
    d2h = n("dataflow.step.wait") * 8 * B \
        + n("dataflow.harvest.d2h") * (st.out_last.nbytes
                                       + st.out_count.nbytes)
    assert got["d2h_kib_per_heartbeat.backlog"] == pytest.approx(
        d2h / obs.heartbeats() / 1024, rel=1e-12)
    # the program counts the slot-cycles the harness's wrapper counts
    assert obs.counters["slot_cycles"] == s.spans.slot_cycles
    assert 0 < got["active_slot_share.backlog"] <= 100
    longest = program_spans.longest_heartbeat(obs)
    names = [n for n, *_ in longest["spans"]]
    assert "dataflow.step.wait" in names
    assert longest["wall_ms"] >= max(w for _, _, w in longest["spans"])
    assert longest["cpu_ms"] > 0


def test_fire_density_reads_the_firing_counter(root, on_cpu,
                                               program_traced):
    """``fire_density``: node firings over nodes x active slot-cycles,
    from the program's counters; ``None`` where the run gives no node
    count, as ``run.py`` does today."""
    cell = cells.load_cell("tiny.backlog", root)
    s = run.serve(cell, BIG, 0.8, False, DEVICE)
    obs = program_traced["obs"]
    nodes = len(s.srv.graph.nodes)
    read = cells.reader("fire_density", root)
    view = run.RunView("backlog", 0.8, s.setup_s, s.log, s.traffic, s.spans,
                       None, {}, 8, 4, None)
    view.obs = obs
    assert read(view) is None
    view.fabric = {"nodes": nodes}
    fired = obs.counter("node_firings")
    assert fired > 0
    assert read(view) == pytest.approx(
        100.0 * fired / (nodes * obs.counter("active_slot_cycles")))
    assert 0 < read(view) <= 100
