"""Bring-up smoke of the dataflow server on a TPU (not a benchmark).

One chip (no arguments): for the fir, bubble_sort, gcd and relu_chain
fabrics, serve seeded requests through ``DataflowServer`` on the
``xla`` and then the ``pallas`` backend at serving size, and serve fir
again on the static firing schedule (``schedule="auto"``).  It checks
that every request is ``ok``; that pallas, xla and the scheduled path
agree bit for bit; that a seeded sample spread over the stream lengths
matches ``run_reference``; that no server degraded or retried; and that
every pallas step compiled to a Mosaic kernel (``tpu_custom_call``).

Four chips (``--four-chips``): only the sharded path — a graph split
into 4 regions, one per chip, through ``DataflowEngine.run``/
``run_batch`` and ``DataflowServer``, compared with the solo one-chip
engine and a ``run_reference`` sample.

    python chip_smoke.py [--seed N] [--four-chips]

Every input is generated from ``--seed``.  The script runs in one
process and starts none.  Lines before the last report phases, compile
times and which path ran; none of them is a benchmark.  The last line
of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed check raises, so the script exits non-zero without it; so
it does where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# one chip: a multi-tenant server's slot count and traffic; stream
# lengths (loop fabrics: trip-count scales) uniform over 1..max_len;
# `sample` requests per fabric are checked against run_reference
ONE_CHIP = dict(slots=2048, requests=4096, max_len=1024, sample=64)
FABRICS = ("fir", "bubble_sort", "gcd", "relu_chain")
# four chips: fabrics that split with a real cut at P=4
FOUR_CHIPS = dict(slots=256, requests=512, max_len=256, sample=16)
SHARDED = ("bubble_sort", "pop_count")
P = 4
BLOCK = 16


class SmokeFailure(RuntimeError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(*a) -> None:
    print(*a, flush=True)


def require_tpu(jax):
    """(platform, kind, count) of the default devices; exits non-zero
    when they are not TPUs."""
    devs = jax.devices()
    dev = (devs[0].platform, devs[0].device_kind, len(devs))
    say(f"device: platform={dev[0]} kind={dev[1]} count={dev[2]}")
    if dev[0] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev[0]!r}",
              file=sys.stderr)
        sys.exit(2)
    return dev


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------
def make_requests(library, name, bench, n, max_len, rng):
    """n seeded feed dicts; stream lengths (loop fabrics: the trip-count
    scale of one initiation) uniform over 1..max_len."""
    lens = rng.integers(1, max_len + 1, n)
    return lens, [library.random_feeds(name, bench, int(k), rng)
                  for k in lens]


def same_result(np, r, q) -> bool:
    """EngineResults equal in every reported field."""
    return (r.cycles == q.cycles and r.fired == q.fired
            and r.counts == q.counts
            and all(np.asarray(r.outputs[a], np.int32).tobytes()
                    == np.asarray(q.outputs[a], np.int32).tobytes()
                    for a in q.counts))


def spread_sample(np, lens, k):
    """k request indices spread evenly over the sorted stream lengths."""
    order = np.argsort(lens, kind="stable")
    return order[np.linspace(0, len(order) - 1, k).round().astype(int)]


def serve(DataflowServer, graph, requests, *, slots, backend, **kw):
    """Serve a closed workload; returns (server, results by uid, secs)."""
    t0 = time.perf_counter()
    srv = DataflowServer(graph, slots=slots, block_cycles=BLOCK,
                         backend=backend, **kw)
    res = srv.run(requests)
    dt = time.perf_counter() - t0
    check(len(res) == len(requests),
          f"{graph.name}/{backend}: {len(res)} results for "
          f"{len(requests)} requests")
    bad = [r for r in res if r.status != "ok"]
    check(not bad, f"{graph.name}/{backend}: {len(bad)} requests not ok, "
          f"first {bad[:1]}")
    check(not srv.degraded and srv.backend == backend,
          f"{graph.name}/{backend}: server degraded to {srv.backend}")
    check(not srv.events, f"{graph.name}/{backend}: server events "
          f"{srv.events[:3]}")
    for r in res:
        m = r.metrics
        check(m.backend == backend and not m.degraded and m.retries == 0,
              f"{graph.name}/{backend}: uid {r.uid} served by "
              f"{m.backend} (degraded={m.degraded}, retries={m.retries})")
    return srv, res, dt


def check_mosaic(jnp, srv):
    """Every pallas step the server's engine ran lowers to a Mosaic
    kernel; returns the number of steps checked."""
    from repro.kernels.dataflow_fire import interpret_mode
    check(not interpret_mode(), "Pallas runs in interpret mode")
    eng, st = srv.engine, srv.state
    n = 0
    if eng._sched_on:
        from repro.kernels import schedule_fire
        ctx = eng._sched_ctx()
        bits = jnp.asarray(schedule_fire.pattern_bits(ctx))
        t_full = ctx.slot_tables()[7]
        for (nb, _), runner in ctx._slot_steps.items():
            B = st.slots
            hlo = runner.core.lower(
                bits, t_full, st.fv, jnp.zeros((B, nb), jnp.int32),
                jnp.zeros((B,), jnp.int32), st.full, st.val, st.ptr,
                st.out_last, st.out_count).compile().as_text()
            check("tpu_custom_call" in hlo,
                  f"scheduled slot step K={nb}: no Mosaic kernel")
            n += 1
    else:
        for (nb, batched), step in eng._steps.items():
            check(batched, "the server ran a single-stream step")
            hlo = step.lower(st.fv, st.fl, st.full, st.val, st.ptr,
                             st.out_last, st.out_count,
                             st.active_dev).compile().as_text()
            check("tpu_custom_call" in hlo,
                  f"pallas block step K={nb}: no Mosaic kernel")
            n += 1
    check(n > 0, "no pallas step ran")
    return n


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------
def one_chip(args, np, jnp):
    from repro.core import library
    from repro.core.engine import run_reference
    from repro.serve.dataflow_server import DataflowServer

    for i, name in enumerate(FABRICS):
        bench = library.BENCHES[name]()
        rng = np.random.default_rng([args.seed, i])
        lens, reqs = make_requests(library, name, bench, args.requests,
                                   args.max_len, rng)
        got = {}
        for be in ("xla", "pallas"):
            srv, res, dt = serve(DataflowServer, bench.graph, reqs,
                                 slots=args.slots, backend=be)
            got[be] = res
            say(f"  {name} {be}: {len(res)} requests ok in {dt:.1f} s "
                f"({srv.block} blocks, compile included; not a benchmark)")
            if be == "pallas":
                say(f"  {name} pallas: {check_mosaic(jnp, srv)} step(s) "
                    "compiled to tpu_custom_call")
        diff = [x.uid for x, y in zip(got["xla"], got["pallas"])
                if not same_result(np, x.engine, y.engine)]
        check(not diff, f"{name}: pallas != xla for uids {diff[:8]}")
        t0 = time.perf_counter()
        idx = spread_sample(np, lens, args.sample)
        for j in idx:
            want = run_reference(bench.graph, reqs[j])
            check(same_result(np, got["pallas"][j].engine, want),
                  f"{name}: request {j} (length {lens[j]}) differs from "
                  "run_reference")
        say(f"  {name}: pallas == xla on all {len(reqs)}; "
            f"{len(idx)} requests (lengths {lens[idx].min()}.."
            f"{lens[idx].max()}) == run_reference "
            f"({time.perf_counter() - t0:.1f} s)")
        if name == "fir":
            srv, res, dt = serve(DataflowServer, bench.graph, reqs,
                                 slots=args.slots, backend="pallas",
                                 schedule="auto")
            eng = srv.engine
            ctx = eng._sched_ctx() if eng._sched_on else None
            check(ctx is not None, "fir: the static schedule did not engage")
            check(eng.sched_bails == 0
                  and all(p.quiesced for p in ctx._plans.values()),
                  "fir: a schedule fell back to the dynamic engine")
            diff = [x.uid for x, y in zip(got["pallas"], res)
                    if not same_result(np, x.engine, y.engine)]
            check(not diff, f"fir scheduled != dynamic for uids {diff[:8]}")
            say(f"  fir pallas schedule='auto': {len(res)} requests ok in "
                f"{dt:.1f} s over {len(ctx._plans)} schedules, "
                f"{check_mosaic(jnp, srv)} step(s) compiled to "
                "tpu_custom_call; == dynamic (not a benchmark)")


# ---------------------------------------------------------------------------
# four chips: the sharded path only
# ---------------------------------------------------------------------------
def four_chips(args, np, jax):
    from repro.core import library
    from repro.core.engine import DataflowEngine, run_reference
    from repro.serve.dataflow_server import DataflowServer

    check(len(jax.devices()) >= P, f"--four-chips needs {P} devices")
    for i, name in enumerate(SHARDED):
        bench = library.BENCHES[name]()
        g = bench.graph
        rng = np.random.default_rng([args.seed, 100 + i])
        lens, reqs = make_requests(library, name, bench, args.requests,
                                   args.max_len, rng)
        solo = DataflowEngine(g, block_cycles=BLOCK)
        part = DataflowEngine(g, block_cycles=BLOCK, partition=P)
        mf = part._mf_ctx()
        check(mf.P == P and mf.use_shard_map,
              f"{name}: partition={P} did not engage shard_map")
        cut = len(part.partition.cut_arcs(g))
        t0 = time.perf_counter()
        for f in reqs[:4]:
            check(same_result(np, part.run(f), solo.run(f)),
                  f"{name}: sharded run() != solo")
        batch = reqs[:64]
        for x, y in zip(part.run_batch(batch), solo.run_batch(batch)):
            check(same_result(np, x, y), f"{name}: sharded run_batch != solo")
        say(f"  {name} P={P} (cut arcs: {cut}): run x4 + run_batch x"
            f"{len(batch)} == solo ({time.perf_counter() - t0:.1f} s)")
        srv_p, res_p, dt = serve(DataflowServer, g, reqs, slots=args.slots,
                                 backend="xla", partition=P)
        check(srv_p.engine._mf_ctx().use_shard_map,
              f"{name}: the sharded server runs on one device")
        devs = srv_p.state.full.sharding.device_set
        check(len(devs) == P,
              f"{name}: region state spans {len(devs)} devices, not {P}")
        _, res_s, dt_s = serve(DataflowServer, g, reqs, slots=args.slots,
                               backend="xla")
        diff = [x.uid for x, y in zip(res_p, res_s)
                if not same_result(np, x.engine, y.engine)]
        check(not diff, f"{name}: sharded server != solo for {diff[:8]}")
        for j in spread_sample(np, lens, args.sample):
            check(same_result(np, res_p[j].engine,
                              run_reference(g, reqs[j])),
                  f"{name}: sharded request {j} != run_reference")
        say(f"  {name} DataflowServer(partition={P}): {len(reqs)} requests "
            f"ok in {dt:.1f} s on {len(devs)} devices (solo server "
            f"{dt_s:.1f} s); == solo server; {args.sample} == "
            "run_reference (not a benchmark)")
    return P


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help=f"run only the {P}-region sharded path")
    args = ap.parse_args(argv)
    vars(args).update(FOUR_CHIPS if args.four_chips else ONE_CHIP)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache(ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np
    platform, kind, count = require_tpu(jax)
    say(f"compile cache: {cache}")
    say(f"sizes: slots={args.slots} requests={args.requests} "
        f"stream lengths 1..{args.max_len} block={BLOCK} "
        f"seed={args.seed}")
    t0 = time.perf_counter()
    if args.four_chips:
        count = four_chips(args, np, jax)
    else:
        one_chip(args, np, jnp)
    say(f"all phases passed in {time.perf_counter() - t0:.1f} s "
        "(not a benchmark)")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
