"""The fabric's device steps compile for a TPU v5e chip.

Nothing here runs: each test lowers a step at serving size for a
described (not attached) ``v5e:2x2`` topology and asks the installed
TPU compiler for the executable, so a kernel that interpret mode
accepts but Mosaic refuses (unaligned blocks, 1-D gathers, i1 selects)
fails here instead of on the chip.  The topology is described inside a
fixture, never at import, so every test worker collects the same tests.
"""
import json
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import asm, library, passes
from repro.core.engine import DataflowEngine, pack_feeds
from repro.kernels import dataflow_fire, feed_stage, schedule_fire

SLOTS = 2048
K = 16
FABRICS = ("fir", "bubble_sort")
CONFIGS = Path(__file__).resolve().parent.parent / "benchmarks" / "chip" \
    / "configs"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def chip(one_chip, no_persistent_cache, monkeypatch):
    """ShapeDtypeStruct factory on the described chip; the kernels are
    steered off interpret mode (this process's backend is the CPU)."""
    for mod in (dataflow_fire, feed_stage, schedule_fire):
        monkeypatch.setattr(mod, "interpret_mode", lambda: False)
    return lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                               sharding=one_chip)


def _compile(fn, *args):
    return fn.lower(*args).compile().as_text()


def _engine(name, **kw):
    bench = library.BENCHES[name]()
    return bench, DataflowEngine(bench.graph, block_cycles=K, **kw)


def _state(sp, chip, *lead):
    return [chip(*lead, sp.A2), chip(*lead, sp.A2), chip(*lead, sp.n_in),
            chip(*lead, sp.n_out), chip(*lead, sp.n_out)]


@pytest.mark.parametrize("name", FABRICS)
@pytest.mark.parametrize("profile", (False, True))
def test_batched_fire_kernel_compiles(name, profile, chip):
    _, eng = _engine(name, backend="pallas", profile=profile)
    sp = dataflow_fire.FabricSpec(eng._tables)
    args = [chip(SLOTS, sp.n_in, 1024), chip(SLOTS, sp.n_in),
            *_state(sp, chip, SLOTS), chip(SLOTS)]
    if profile:
        args += [chip(SLOTS, sp.N2)] * 3 + [chip(SLOTS, sp.A2)] * 2
    assert "tpu_custom_call" in _compile(eng._pallas_step(K, True), *args)


@pytest.mark.parametrize("profile", (False, True))
def test_fire_kernel_compiles_for_c6288(profile, chip):
    """ISCAS-85 c6288 at the chip benchmark's slots (``c6288_regen``):
    the static kernel's register file overran the 128 MiB of VMEM after
    minutes of lowering; the row kernel lowers and compiles in seconds."""
    config = json.loads((CONFIGS / "c6288_regen.json").read_text())
    graph = asm.parse((CONFIGS / config["netlist"]).read_text())
    eng = DataflowEngine(graph, backend="pallas", block_cycles=K,
                         profile=profile)
    sp = eng._fabric_spec()
    slots = config["slots"]
    # the slot state as the server holds it: registers in the row
    # kernel's tile layout
    T, sub = eng._row_tiles(slots)
    regs = chip(T, sp.rows.R, sub, 128)
    args = [chip(slots, sp.n_in, 32), chip(slots, sp.n_in), regs, regs,
            *_state(sp, chip, slots)[2:], chip(slots)]
    if profile:
        args += [chip(slots, sp.N2)] * 3 + [chip(slots, sp.A2)] * 2
    t = time.perf_counter()
    assert "tpu_custom_call" in _compile(eng._pallas_step(K, True), *args)
    assert time.perf_counter() - t < 60
    assert eng.kernel_choice(slots).startswith("rows")


@pytest.mark.parametrize("name", FABRICS)
def test_single_stream_fire_kernel_compiles(name, chip):
    _, eng = _engine(name, backend="pallas")
    sp = dataflow_fire.FabricSpec(eng._tables)
    args = [chip(sp.n_in, 1024), chip(sp.n_in), *_state(sp, chip)]
    assert "tpu_custom_call" in _compile(eng._pallas_step(K, False), *args)


def _sched(name):
    bench = library.BENCHES[name]()
    g, _ = passes.optimize_graph(bench.graph)
    eng = DataflowEngine(g, backend="pallas", block_cycles=K, schedule=True)
    assert eng._sched_on
    return bench, eng, eng._sched_ctx()


@pytest.mark.parametrize("name", FABRICS)
def test_scheduled_run_kernel_compiles(name, chip):
    bench, eng, ctx = _sched(name)
    feeds = library.random_feeds(name, bench, 1024,
                                 np.random.default_rng(0))
    fv, fl = pack_feeds(eng.p["input_arcs"], feeds)
    plan = ctx.plan_for(tuple(int(x) for x in fl))
    plan.ensure(eng.max_cycles)
    struct, reps = plan.trace_struct(min(plan.total, eng.max_cycles))
    for batched, lead in ((False, ()), (True, (256,))):
        run = ctx.runner(struct, fv.shape[1], "pallas", batched)
        hlo = _compile(run, chip(*lead, *fv.shape), chip(*reps.shape))
        assert "tpu_custom_call" in hlo, batched


@pytest.mark.parametrize("name", FABRICS)
def test_scheduled_slot_kernel_compiles(name, chip):
    _, _, ctx = _sched(name)
    core = ctx.slot_step_fn(K, "pallas").core
    n_in, n_out = ctx.ia_pad.size, ctx.oa_pad.size
    bits = schedule_fire.pattern_bits(ctx)
    t_full = ctx.slot_tables()[7]
    hlo = _compile(core, chip(*bits.shape), chip(*t_full.shape),
                   chip(SLOTS, n_in, 1024), chip(SLOTS, K), chip(SLOTS),
                   chip(SLOTS, ctx.A2), chip(SLOTS, ctx.A2),
                   chip(SLOTS, n_in), chip(SLOTS, n_out), chip(SLOTS, n_out))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("name", FABRICS)
def test_xla_slot_step_compiles(name, chip):
    _, eng = _engine(name, backend="xla")
    sp = dataflow_fire.FabricSpec(eng._block_tables())
    hlo = _compile(eng._slot_step(K), chip(SLOTS, sp.n_in, 1024),
                   chip(SLOTS, sp.n_in), *_state(sp, chip, SLOTS),
                   chip(SLOTS))
    assert "tpu_custom_call" not in hlo


@pytest.mark.parametrize("slots,n_in,L", [
    (512, 8, 1024), (256, 64, 256), (SLOTS, 8, 1024), (SLOTS, 8, 64)])
def test_admission_reset_compiles(slots, n_in, L, chip):
    """An admission round's reset at the chip benchmark's two cells and
    at serving size: a lane-aligned feed buffer is filled by the Pallas
    placement kernel, a shorter one by an XLA scatter."""
    from repro.core.engine import _slot_reset, _staged_size
    A2, n_out = 178, 8
    hlo = _compile(_slot_reset, chip(slots, n_in, L), chip(slots, n_in),
                   chip(slots, A2), chip(slots, A2), chip(slots, n_in),
                   chip(slots, n_out), chip(slots, n_out),
                   chip(_staged_size(slots, n_in, L)), chip(A2), chip(A2))
    assert ("tpu_custom_call" in hlo) == (L % 128 == 0)
