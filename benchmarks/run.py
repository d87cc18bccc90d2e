"""Benchmark driver — one section per paper table / report table.

  table1_*   paper Table 1 analogue (dataflow benchmarks: resources +
             engine cycles + compiled throughput)
  engine_*   block-fused/batched engine executor sweep (also serialized
             to BENCH_dataflow.json for cross-PR perf tracking)
  opt_*      graph-compiler optimization sweep: off vs spec vs full vs
             sched across backends x K x B (BENCH_opt.json; --opt runs
             it alone, --quick --opt is the CI smoke and
             --quick --sched the scheduled-vs-dynamic one)
  profile_*  §12 fabric-counter sweep (profiled engines; BENCH_profile
             .json feeds roofline.py's fabric section; --trace runs it
             alone, --quick --trace is the CI smoke)
  shard_*    §14 multi-fabric sharding sweep over P regions
             (BENCH_shard.json feeds roofline.py's shard section;
             --shard runs it alone, --quick --shard is the CI
             sharded-vs-solo bit-identity smoke over forced host
             devices)
  kernel_*   Pallas kernel micro-benchmarks vs jnp references
  train_*    end-to-end reduced-config train-step timings (per family)
  roofline_* aggregated dry-run roofline terms (if records exist)

Prints ``name,us_per_call,derived`` CSV.
"""
from __future__ import annotations

import json
import os
import sys
import time

if __name__ == "__main__" and "--shard" in sys.argv:
    # multi-fabric sharding (DESIGN.md §14) wants real host devices;
    # XLA only honors this flag if it is set before jax is imported
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

import jax
import numpy as np


def _train_steps():
    from repro.configs.base import get_arch
    from repro.data.pipeline import SyntheticLM
    from repro.optim import adamw
    from repro.train.loop import init_state, make_train_step

    for name in ("internlm2-1.8b", "kimi-k2-1t-a32b", "rwkv6-1.6b",
                 "zamba2-7b", "whisper-medium"):
        cfg = get_arch(name).reduced()
        src = SyntheticLM(vocab=cfg.vocab, seq_len=64, global_batch=4,
                          seed=0, frontend=cfg.frontend,
                          n_patches=cfg.n_patches,
                          frontend_dim=cfg.frontend_dim,
                          enc_seq=cfg.enc_seq)
        step = make_train_step(cfg, adamw.OptConfig(), donate=False)
        state = init_state(cfg, jax.random.key(0))
        b = src.batch_for_step(0)
        state, m = step(state, b)          # compile
        ts = []
        for i in range(1, 4):
            b = src.batch_for_step(i)
            t0 = time.perf_counter()
            state, m = step(state, b)
            float(m["loss"])
            ts.append(time.perf_counter() - t0)
        us = float(np.median(ts)) * 1e6
        toks = 4 * 64
        print(f"train_step_{name},{us:.0f},"
              f"tok_per_s={toks / us * 1e6:.0f};reduced_cfg;loss="
              f"{float(m['loss']):.3f}")


def dataflow_json(path: str | None = None) -> list[dict]:
    """Run the engine-backend sweep and write BENCH_dataflow.json (one
    record per bench/backend/B/K: us_per_call, cycles/s, tokens/s,
    dispatches) so the perf trajectory is machine-readable across PRs."""
    from benchmarks import table1_dataflow

    recs = table1_dataflow.backend_rows()
    path = path or os.path.join(os.path.dirname(__file__), "..",
                                "BENCH_dataflow.json")
    with open(path, "w") as f:
        json.dump(recs, f, indent=1)
    table1_dataflow.print_backend_csv(recs)
    return recs


def opt_json(path: str | None = None) -> list[dict]:
    """Run the --opt/--no-opt optimization sweep (off vs spec vs full
    across backends x K x B) and write BENCH_opt.json, so the
    graph-compiler speedup is tracked across PRs alongside
    BENCH_dataflow.json."""
    from benchmarks import table1_dataflow

    recs = table1_dataflow.opt_rows()
    payload = dict(records=recs, summary=table1_dataflow.opt_summary(recs))
    path = path or os.path.join(os.path.dirname(__file__), "..",
                                "BENCH_opt.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    table1_dataflow.print_opt_csv(recs)
    return recs


def profile_json(path: str | None = None, quick: bool = False,
                 benches=None, backends=("xla", "pallas", "reference"),
                 k_tokens: int = 8, block: int = 8) -> list[dict]:
    """``--trace``: run library benches with DESIGN.md §12 profiling on
    and write BENCH_profile.json — one record per bench x backend with
    the FabricProfile export (per-node fires/stalls, per-arc occupancy,
    fires-per-dispatch).  roofline.py's fabric section reads this file.

    Each record is cross-checked before it is written: profiling must
    not perturb results (outputs/fired/cycles bit-identical to an
    unprofiled engine) and the §12 partition invariant must hold."""
    from repro.core import library
    from repro.core.engine import DataflowEngine

    benches = benches or (("vector_sum", "gcd") if quick else
                          ("vector_sum", "fir", "fibonacci", "gcd",
                           "newton_sqrt", "bubble_sort"))
    recs = []
    for name in benches:
        bench = library.BENCHES[name]()
        if np.dtype(bench.dtype) != np.int32:
            continue
        feeds = library.random_feeds(name, bench, k_tokens,
                                     np.random.default_rng(42))
        for backend in backends:
            eng = DataflowEngine(bench.graph, backend=backend,
                                 block_cycles=block, profile=True)
            res = eng.run(feeds)
            prof = res.profile
            prof.check()
            base = DataflowEngine(bench.graph, backend=backend,
                                  block_cycles=block).run(feeds)
            assert base.outputs == res.outputs \
                and base.fired == res.fired \
                and base.cycles == res.cycles, \
                f"profiling perturbed {name}/{backend}"
            assert prof.fired == res.fired
            recs.append(dict(name=name, backend=backend, K=block,
                             k_tokens=k_tokens, profile=prof.to_json()))
            print(f"profile_{name}_{backend},0,{prof.summary()}")
    if not quick:
        path = path or os.path.join(os.path.dirname(__file__), "..",
                                    "BENCH_profile.json")
        with open(path, "w") as f:
            json.dump(recs, f, indent=1)
    return recs


def quick_opt() -> None:
    """CI smoke for the optimization sweep: 2 benches, tiny workloads,
    every level, no JSON (the committed BENCH_opt.json is a full-run
    artifact) — keeps the specialized kernels + rewrite passes
    exercised on every push."""
    from benchmarks import table1_dataflow
    recs = table1_dataflow.opt_rows(
        Bs=(1, 2), Ks=(4,), reps=1, k_tokens=4, fib_iters=8,
        benches=("fir", "fibonacci", "fir_traced", "gcd"))
    table1_dataflow.print_opt_csv(recs)


def quick_sched() -> None:
    """CI smoke for static firing schedules (DESIGN.md §13): scheduled
    vs dynamic rows on a control-free bench (fir: schedules engage,
    steady-state cadence reported) and a control-bearing one (gcd:
    scheduled compile falls back dynamically) across both device
    backends, plus a bit-identity cross-check against the dynamic
    engine.  No JSON — the committed BENCH_opt.json is a full-run
    artifact."""
    from benchmarks import table1_dataflow
    from repro.core import library
    from repro.core.compile import compile as _compile

    recs = table1_dataflow.opt_rows(
        Bs=(1, 2), Ks=(4,), reps=1, k_tokens=4, fib_iters=8,
        benches=("fir", "gcd"), levels=("full", "sched"))
    table1_dataflow.print_opt_csv(recs)
    sched = {r["name"]: r for r in recs if r["opt"] == "sched"
             and r["B"] == 1}
    assert sched["fir"]["scheduled"], "fir must compile a schedule"
    assert not sched["gcd"]["scheduled"], "gcd must fall back dynamic"
    for name in ("fir", "gcd"):
        bench = library.BENCHES[name]()
        k = 8 if name in library.SINGLE_SHOT else 4
        feeds = library.random_feeds(name, bench, k,
                                     np.random.default_rng(7))
        dyn = _compile(bench.graph, backend="xla", optimize="full",
                       block_cycles=4)(feeds)
        sch = _compile(bench.graph, backend="xla", optimize="sched",
                       block_cycles=4)(feeds)
        assert dyn.outputs == sch.outputs and dyn.cycles == sch.cycles \
            and dyn.fired == sch.fired, f"sched diverged on {name}"
        print(f"sched_check_{name},0,bit_identical=1")


def _lanes_graph(lanes: int = 4, depth: int = 24):
    """Embarrassingly-spatial fabric: `lanes` independent ADD/MUL
    chains sharing one const bus — the partitioner finds a zero-cut
    split, so sharding it measures pure per-region compute scaling
    (channel exchange cost ~0)."""
    from repro.core.graph import Graph, Op
    g = Graph(name=f"lanes_{lanes}x{depth}")
    g.const("c", 3)
    for ln in range(lanes):
        cur = f"in{ln}"
        for d in range(depth):
            nxt = f"l{ln}_{d}"
            g.add(Op.ADD if d % 2 == 0 else Op.MUL, [cur, "c"], [nxt])
            cur = nxt
    g.validate()
    return g


def _shard_benches():
    from repro.core import library
    vs = library.vector_sum_graph(64)
    pc = library.popcount_graph(16)
    rng = np.random.default_rng(11)
    lanes = _lanes_graph(4, 24)
    return [
        ("vector_sum_64", vs.graph,
         library.random_feeds("vector_sum", vs, 8, rng)),
        ("pop_count_16", pc.graph,
         library.random_feeds("pop_count", pc, 8, rng)),
        ("lanes_4x24", lanes,
         {f"in{ln}": rng.integers(0, 9, (8,)) for ln in range(4)}),
    ]


def shard_json(path: str | None = None, Ps=(1, 2, 4), block: int = 8,
               reps: int = 3) -> list[dict]:
    """``--shard``: the multi-fabric sharding sweep (DESIGN.md §14) over
    P regions on the large control-free benches, written to
    BENCH_shard.json.  Every sharded run is bit-identity-checked against
    the P=1 engine before its timing is recorded.

    Records carry the honest context a reader needs to interpret the
    wall clock: host core count and device count (forced host devices on
    one core time-slice a single CPU, so cycles/s cannot exceed P=1
    there — the *capacity* metrics, region balance and cut traffic, are
    the device-independent scaling story)."""
    from repro.core.engine import DataflowEngine
    from repro.core.partition import partition_graph

    recs = []
    ncpu = os.cpu_count() or 1
    ndev = len(jax.devices())
    for name, graph, feeds in _shard_benches():
        base = None
        for P in Ps:
            part = partition_graph(graph, P)
            eng = DataflowEngine(graph, block_cycles=block,
                                 partition=part)
            r = eng.run(feeds)
            if base is None:
                base = r
                base_us = None
            assert r.outputs == base.outputs and r.cycles == base.cycles \
                and r.fired == base.fired, f"shard diverged on {name} P={P}"
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                eng.run(feeds)
                ts.append(time.perf_counter() - t0)
            us = float(np.median(ts)) * 1e6
            if base_us is None:
                base_us = us
            w = part.region_weights(graph)
            cut = part.cut_arcs(graph)
            mf = eng._mf_ctx() if eng._part_on else None
            pushes_per_block = ch_hw = None
            if mf is not None:
                # measured cut-arc traffic: one profiled run (§12/§14
                # counters), tokens crossing channels per K-cycle block
                pr = DataflowEngine(graph, block_cycles=block,
                                    partition=part,
                                    profile=True).run(feeds)
                prof = pr.profile
                prof.check()
                pushes_per_block = 0.0 if not cut else round(
                    float(np.sum(prof.ch_pushes))
                    / max(pr.dispatches, 1), 3)
                ch_hw = int(np.max(prof.ch_hw)) if cut else 0
            rec = dict(
                name=name, P=P, K=block, us_per_call=round(us, 1),
                cycles=r.cycles,
                cycles_per_s=round(r.cycles / (us / 1e6), 1),
                speedup_vs_p1=round(base_us / us, 3),
                cut_arcs=len(cut),
                cut_tokens_per_block=pushes_per_block,
                channel_high_water=ch_hw,
                max_region_frac=round(max(w) / max(sum(w), 1), 4),
                region_weights=[int(x) for x in w],
                shard_map=bool(mf is not None and mf.use_shard_map),
                devices=ndev, host_cpus=ncpu)
            recs.append(rec)
            print(f"shard_{name}_P{P},{us:.1f},"
                  f"cycles_per_s={rec['cycles_per_s']};"
                  f"speedup_vs_p1={rec['speedup_vs_p1']};"
                  f"cut={rec['cut_arcs']};"
                  f"max_region_frac={rec['max_region_frac']};"
                  f"shard_map={int(rec['shard_map'])}")
    payload = dict(devices=ndev, host_cpus=ncpu, records=recs)
    path = path or os.path.join(os.path.dirname(__file__), "..",
                                "BENCH_shard.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return recs


def quick_shard() -> None:
    """CI smoke for multi-fabric sharding: in-process sharded-vs-solo
    bit-identity cross-check (every EngineResult field) on a control-free
    and a cyclic bench, under the forced 2+ host devices the --shard
    pre-import guard set up (so the shard_map path, not the vmap
    fallback, is what CI exercises).  No JSON — the committed
    BENCH_shard.json is a full-run artifact."""
    from repro.core import library
    from repro.core.engine import DataflowEngine

    ndev = len(jax.devices())
    for name, P, K in (("vector_sum", 2, 4), ("gcd", 2, 8)):
        bench = library.BENCHES[name]()
        k = 12 if name in library.SINGLE_SHOT else 4
        feeds = library.random_feeds(name, bench, k,
                                     np.random.default_rng(3))
        solo = DataflowEngine(bench.graph, block_cycles=K).run(feeds)
        eng = DataflowEngine(bench.graph, block_cycles=K, partition=P)
        shard = eng.run(feeds)
        assert shard.outputs == solo.outputs \
            and shard.counts == solo.counts \
            and shard.cycles == solo.cycles \
            and shard.fired == solo.fired, f"shard diverged on {name}"
        mf = eng._mf_ctx()
        print(f"shard_check_{name},0,bit_identical=1;P={P};"
              f"devices={ndev};shard_map={int(mf.use_shard_map)}")


def main() -> None:
    from benchmarks import table1_dataflow, kernels_bench, roofline
    table1_dataflow.main()
    dataflow_json()
    opt_json()
    profile_json()
    kernels_bench.main()
    _train_steps()
    roofline.main()


def quick() -> None:
    """CI smoke: the dataflow executor sweep at tiny K/B over 2 benches
    (serve_bench.py has its own --quick).  Catches benchmark-code rot
    without the full sweep's runtime; writes no JSON (the committed
    BENCH_*.json files are full-run artifacts)."""
    from benchmarks import table1_dataflow
    for r in table1_dataflow.rows(benches=("fibonacci", "vector_sum",
                                           "horner", "relu_chain",
                                           "gcd", "newton_sqrt")):
        print(f"table1_{r['name']},{r['compiled_us_per_token']},"
              f"nodes={r['nodes']};lat_cyc={r['latency_cycles']}")
    recs = table1_dataflow.backend_rows(
        Bs=(1, 2), block=4, reps=1, k_tokens=2,
        benches=("fibonacci", "vector_sum", "relu_chain", "gcd"))
    table1_dataflow.print_backend_csv(recs)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))   # `benchmarks` importable from CLI
    if "--shard" in sys.argv:
        if "--quick" in sys.argv:
            quick_shard()              # CI: shard_map bit-identity smoke
        else:
            shard_json()               # the §14 sharding sweep alone
    elif "--trace" in sys.argv:
        profile_json(quick="--quick" in sys.argv)  # the §12 sweep alone
    elif "--quick" in sys.argv:
        if "--sched" in sys.argv:
            quick_sched()
        elif "--opt" in sys.argv:
            quick_opt()
        else:
            quick()
    elif "--opt" in sys.argv:
        opt_json()                     # the opt sweep alone
    else:
        main()
