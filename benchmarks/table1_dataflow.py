"""Paper Table 1 analogue: per-benchmark fabric resources + speed.

Paper columns FF / LUT / Slices / Fmax map to (DESIGN.md §2):
  FF     -> arc register bits (16-bit data + 1-bit status per arc)
  LUT    -> summed operator datapath complexity weights
  Slices -> node count
  Fmax   -> engine throughput (cycles/token when streaming; the
            architecture-determined rate, like the paper's 613 MHz) and
            the compiled backend's wall-clock tokens/s on this host.

Besides the resource table, ``backend_rows`` sweeps the cycle-accurate
executors (DESIGN.md §3): the XLA engine at K ∈ {1, block} and the
fused Pallas block engine, each at batch sizes B ∈ {1, 8, 64} —
reporting us/call, cycles/s, tokens/s and device dispatches.
``benchmarks/run.py`` serializes these records to BENCH_dataflow.json
so the perf trajectory is tracked across PRs.

CSV: name,us_per_call,derived
"""
from __future__ import annotations

import os
import time

import numpy as np

from repro.core import library
from repro.core.compile import compile
from repro.core.engine import DataflowEngine


def _time(fn, *args, reps=5):
    fn(*args)   # warmup/compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6   # us


def rows(benches=None):
    rng = np.random.default_rng(0)
    out = []
    stream_k = 64
    for name, mk in library.BENCHES.items():
        if benches is not None and name not in benches:
            continue
        bench = mk()
        g = bench.graph
        dt = np.dtype(bench.dtype)
        r = g.resources()
        eng = DataflowEngine(g, dtype=dt)
        if name in library.SINGLE_SHOT:
            feeds1 = feeds_k = library.random_feeds(name, bench, 20, rng)
            n_stream = 1
        else:
            feeds_k = library.random_feeds(name, bench, stream_k, rng)
            feeds1 = {a: np.asarray(v)[:1] for a, v in feeds_k.items()}
            n_stream = stream_k
        # the unified compile() probes GraphTraits and picks the
        # executor: lockstep stream-vmapped SSA for control-free DAGs,
        # the trace-time-unrolled token-presence executor for cyclic /
        # control-bearing / init-bearing fabrics (loop benches)
        run = compile(g, dtype=dt)
        fk = feeds_k
        if run.traits.tokens_out_static:
            feeds_np = {k: np.asarray(v, dt) for k, v in feeds_k.items()}
            compiled_call = lambda: run(feeds_np)
            get_vals = lambda res: list(res.values())
        else:
            compiled_call = lambda: run(fk)
            get_vals = lambda res: list(res.outputs.values())

        lat = eng.run(feeds1).cycles
        thr = eng.run(feeds_k).cycles if n_stream > 1 else lat
        cyc_per_tok = (thr - lat) / max(n_stream - 1, 1) if n_stream > 1 \
            else lat
        us = _time(lambda: np.asarray(get_vals(compiled_call())[0]))
        out.append({
            "name": name, "nodes": r["nodes"], "arcs": r["arcs"],
            "ff_bits": r["ff_bits"], "lut_weight": r["lut_weight"],
            "latency_cycles": lat,
            "cycles_per_token": round(cyc_per_tok, 2),
            "compiled_us_per_stream": round(us, 1),
            "compiled_us_per_token": round(us / n_stream, 2),
        })
    return out


def backend_rows(Bs=(1, 8, 64), block=16, reps=3, k_tokens=8,
                 benches=None):
    """Executor sweep: one JSON-able record per (bench, backend, B, K).

    Backends:
      xla             — jnp cycle body in a while_loop, K cycles fused
                        per loop iteration (K=1 is the seed engine).
      pallas          — fused fire-block kernel, K cycles + environment
                        per dispatch; batched over slot lanes.

    benches: optional iterable of bench names to restrict the sweep
    (the --quick smoke path).
    """
    out = []
    for name, mk in library.BENCHES.items():
        if benches is not None and name not in benches:
            continue
        bench = mk()
        g = bench.graph
        dt = np.dtype(bench.dtype)
        k = 20 if name in library.SINGLE_SHOT else k_tokens
        feeds = library.random_feeds(name, bench, k,
                                     np.random.default_rng(0))
        tok1 = library.tokens_out(name, k)

        def record(backend, B, K, call, res):
            rs = res if isinstance(res, list) else [res]
            us = _time(call, reps=reps)
            cyc = sum(r.cycles for r in rs)
            out.append(dict(
                name=name, backend=backend, B=B, K=K,
                us_per_call=round(us, 1),
                cycles_per_s=round(cyc / us * 1e6),
                tokens_per_s=round(B * tok1 / us * 1e6),
                dispatches=rs[0].dispatches,
                cycles=rs[0].cycles))

        for be, K in (("xla", 1), ("xla", block), ("pallas", block)):
            if be == "pallas" and dt != np.int32:
                continue
            eng = DataflowEngine(g, dtype=dt, backend=be, block_cycles=K)
            for B in Bs:
                if B == 1:
                    call = lambda: eng.run(feeds)
                else:
                    fb = [library.random_feeds(
                        name, bench, k, np.random.default_rng(b))
                        for b in range(B)]
                    call = lambda: eng.run_batch(fb)
                record(be, B, K, call, call())
    return out


def _steady_info(eng, feeds):
    """Scheduled-engine extras: the locked steady-state period and its
    token cadence (None when the engine is dynamic or the plan quiesced
    before a period formed)."""
    if not getattr(eng, "_sched_on", False):
        return None
    from repro.core.engine import pack_feeds
    ctx = eng._sched_ctx()
    _, fl = pack_feeds(eng.p["input_arcs"], feeds, eng.token_shape,
                       ctx.np_dtype)
    plan = ctx.plan_for(tuple(int(x) for x in fl))
    plan.ensure(eng.max_cycles)
    s = plan.steady()
    if s is None:
        return None
    pc, pt = s
    return dict(period_cycles=pc, period_tokens=pt,
                steady_tokens_per_cycle=round(pt / pc, 4))


def opt_rows(Bs=(1, 8), Ks=(4, 16), reps=7, k_tokens=64, fib_iters=300,
             benches=None, backends=("xla", "pallas"),
             levels=(False, "spec", "full", "sched")):
    """--opt/--no-opt sweep (ISSUE 3 + 8): every optimization level
    across backends x K x B, one JSON-able record per configuration.

    Levels:
      off   — the graph exactly as authored, dense ~20-way ALU
              where-chain per cycle (the PR 1/2 engine).
      spec  — opcode-class-specialized plan only (DESIGN.md §8):
              bucketed fire bodies over only the opcodes present;
              bit-identical in every EngineResult field.
      full  — graph rewrite passes (constant folding, identity
              elimination, DCE) + the specialized plan; fabrics shrink,
              so simulated cycles may drop too.
      sched — "full" + static firing schedules (DESIGN.md §13): on
              control-free fabrics the per-cycle fire sets compile out
              of the run loop entirely (no ready-mask reduction) and
              the record gains period_cycles / period_tokens /
              steady_tokens_per_cycle; cyclic / control-bearing benches
              fall back to the dynamic engine (rows mirror "full").

    Streams are long (k_tokens tokens / fib_iters loop iterations) so
    per-cycle compute, not dispatch overhead, dominates; timings take
    the best of ``reps`` to shed scheduler noise.  cycles_per_s is the
    figure of merit: simulated fabric cycles per wall-clock second.
    """
    out = []
    for name, mk in library.BENCHES.items():
        if benches is not None and name not in benches:
            continue
        bench = mk()
        dt = np.dtype(bench.dtype)
        k = fib_iters if name in library.SINGLE_SHOT else k_tokens
        feeds = library.random_feeds(name, bench, k,
                                     np.random.default_rng(0))
        tok1 = library.tokens_out(name, k)
        for be in backends:
            if be == "pallas" and dt != np.int32:
                continue        # the pallas kernels are int32-only
            for K in Ks:
                for opt in levels:
                    run = compile(bench.graph, dtype=dt, backend=be,
                                  block_cycles=K, optimize=opt)
                    eng = run.engine
                    for B in Bs:
                        if B == 1:
                            call = lambda e=eng, f=feeds: e.run(f)
                        else:
                            fb = [library.random_feeds(
                                name, bench, k, np.random.default_rng(b))
                                for b in range(B)]
                            call = lambda e=eng, f=fb: e.run_batch(f)
                        res = call()    # warmup/compile
                        rs = res if isinstance(res, list) else [res]
                        ts = []
                        for _ in range(reps):
                            t0 = time.perf_counter()
                            call()
                            ts.append(time.perf_counter() - t0)
                        us = float(min(ts)) * 1e6
                        cyc = sum(r.cycles for r in rs)
                        rec = dict(
                            name=name, backend=be, B=B, K=K,
                            opt="off" if opt is False else opt,
                            nodes=len(run.graph.nodes),
                            us_per_call=round(us, 1),
                            cycles_per_s=round(cyc / us * 1e6),
                            tokens_per_s=round(B * tok1 / us * 1e6),
                            dispatches=rs[0].dispatches,
                            cycles=rs[0].cycles)
                        if opt == "sched":
                            rec["scheduled"] = bool(
                                getattr(eng, "_sched_on", False))
                            steady = _steady_info(eng, feeds)
                            if steady is not None:
                                rec.update(steady)
                        out.append(rec)
    return out


def opt_summary(recs, K=None, B=None):
    """Per-backend win count at the canonical (K, B) point — largest K,
    smallest B present in the records unless overridden: benches where
    the best opt-on cycles/s beats opt-off."""
    if not recs:
        return []
    K = max(r["K"] for r in recs) if K is None else K
    B = min(r["B"] for r in recs) if B is None else B
    rows = [r for r in recs if r["K"] == K and r["B"] == B]
    summary = []
    for be in sorted({r["backend"] for r in rows}):
        wins = []
        for name in sorted({r["name"] for r in rows}):
            cfg = {r["opt"]: r["cycles_per_s"] for r in rows
                   if r["backend"] == be and r["name"] == name}
            if not cfg or "off" not in cfg:
                continue
            best = max(v for o, v in cfg.items() if o != "off")
            if best > cfg["off"]:
                wins.append(f"{name}:{best / cfg['off']:.2f}x")
        summary.append(dict(backend=be, K=K, B=B, wins=len(wins),
                            total=len({r["name"] for r in rows}),
                            detail=wins))
    return summary


def print_opt_csv(recs):
    for r in recs:
        print(f"opt_{r['name']}_{r['backend']}_B{r['B']}_K{r['K']}_"
              f"{r['opt']},{r['us_per_call']},"
              f"cycles_per_s={r['cycles_per_s']};"
              f"tokens_per_s={r['tokens_per_s']};"
              f"nodes={r['nodes']};dispatches={r['dispatches']}")
    for s in opt_summary(recs):
        print(f"opt_summary_{s['backend']}_K{s['K']}_B{s['B']},0,"
              f"opt_beats_off_on={s['wins']}/{s['total']}:"
              f"{'+'.join(s['detail'])}")


def print_backend_csv(recs):
    """One CSV line per executor record (shared with benchmarks/run.py)."""
    for r in recs:
        print(f"engine_{r['name']}_{r['backend']}_B{r['B']}_K{r['K']},"
              f"{r['us_per_call']},"
              f"cycles_per_s={r['cycles_per_s']};"
              f"tokens_per_s={r['tokens_per_s']};"
              f"dispatches={r['dispatches']}")


def main(with_backends: bool = False):
    for r in rows():
        derived = (f"nodes={r['nodes']};arcs={r['arcs']};"
                   f"ff_bits={r['ff_bits']};lut={r['lut_weight']};"
                   f"lat_cyc={r['latency_cycles']};"
                   f"cyc_per_tok={r['cycles_per_token']}")
        print(f"table1_{r['name']},{r['compiled_us_per_token']},{derived}")
    if with_backends:
        print_backend_csv(backend_rows())


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import sys
    main(with_backends="--backends" in sys.argv)
