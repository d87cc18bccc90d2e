"""Continuous vs wave batching on mixed-length dataflow workloads.

The workload is a deterministic synthetic arrival trace over each
library bench: R requests in a fixed submission order whose stream
lengths mix many short requests with periodic long ones (the shape
that breaks wave batching — every wave of B inherits its slowest
member's residency, so the short requests idle in their slots).

Two servers, same engine, same arrival order:

  wave        — ``DataflowEngine.run_batch`` over successive groups of
                ``slots`` requests (the PR 1 API: a global barrier per
                group).
  continuous  — :class:`repro.serve.dataflow_server.DataflowServer`:
                per-slot quiescence detection + mid-flight refill from
                the queue, free slots clock-gated out of the fabric.

Each continuous row also reports serving-quality metrics (DESIGN.md
§11): per-request wall-latency p50/p99 (submit -> result, measured on
an instrumented step loop) and the queue's high-water mark.
``fault_rows()`` re-runs a subset through a seeded
:class:`~repro.serve.faults.FaultPlan` ("_faulted" rows) so the
overhead of the retry/watchdog/poison machinery is tracked next to the
clean numbers.

``main()`` sweeps every library bench x {xla, pallas} and writes
BENCH_serve.json (committed, so the requests/s trajectory is tracked
across PRs).  ``--quick`` runs 3 benches at tiny K/B with reps=1 as a
CI smoke step — it writes the same JSON schema so CI artifacts carry
the latency percentiles too.

CSV: name,us_per_call,derived  (one line per bench/backend/mode).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from repro.core import library
from repro.serve.dataflow_server import DataflowServer, cached_engine
from repro.serve.faults import FaultPlan


def workload(name: str, bench, R: int, long_len: int = 200,
             every: int = 4):
    """Deterministic mixed-length trace: request i is *long*
    (``long_len`` tokens / loop iterations) when i % every == 0, else
    short (1-3 tokens).  Values are seeded per-request, so the trace is
    reproducible across runs and modes."""
    lens = [long_len if i % every == 0 else 1 + i % 3 for i in range(R)]
    return [library.random_feeds(name, bench, k,
                                 np.random.default_rng(1_000 + i))
            for i, k in enumerate(lens)]


def _time(fn, reps: int):
    fn()                       # warmup: compile every block/reset shape
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _latency_probe(mk_server, feeds):
    """One instrumented serve of ``feeds``: submit everything, then
    step (never drain) so each result's arrival is timestamped.
    Returns (results, per-request wall latencies in seconds, server)."""
    srv = mk_server()
    t0 = time.perf_counter()
    submit_t = {}
    for f in feeds:
        uid = srv.submit(f)
        submit_t[uid] = time.perf_counter()
    res, lat = [], []
    while srv.pending:
        for r in srv.step():
            now = time.perf_counter()
            res.append(r)
            lat.append(now - submit_t.get(r.uid, t0))
    return res, lat, srv


def _pcts(lat):
    return (round(float(np.percentile(lat, 50)) * 1e3, 3),
            round(float(np.percentile(lat, 99)) * 1e3, 3))


def serve_rows(benches=None, backends=("xla", "pallas"), R: int = 16,
               slots: int = 4, block: int = 32, reps: int = 3,
               long_len: int = 200, every: int = 4):
    out = []
    for name, mk in library.BENCHES.items():
        if benches is not None and name not in benches:
            continue
        bench = mk()
        if np.dtype(bench.dtype) != np.int32:
            continue    # the resumable slot API is int32-only
        feeds = workload(name, bench, R, long_len=long_len, every=every)
        for backend in backends:
            eng = cached_engine(bench.graph, backend=backend,
                                block_cycles=block)

            def run_wave():
                res = []
                for i in range(0, R, slots):
                    res.extend(eng.run_batch(feeds[i:i + slots]))
                return res

            def run_cont(out=None):
                srv = DataflowServer(bench.graph, slots=slots,
                                     block_cycles=block, engine=eng)
                for f in feeds:
                    srv.submit(f)
                res = srv.drain()
                if out is not None:
                    out.append((res, srv))
                return res

            wave_res = run_wave()
            probe: list = []
            run_cont(out=probe)
            cont_res, srv = probe[0]
            # same work was done (sanity — results are property-tested
            # bit-identical in tests/test_dataflow_server.py)
            assert len(cont_res) == len(wave_res) == R
            wave_disp = sum(r.dispatches for r in wave_res[::slots])
            cont_disp = srv.block + srv.admission_rounds
            waits = [r.metrics.queue_wait_blocks for r in cont_res]
            wave_s = _time(run_wave, reps)
            cont_s = _time(run_cont, reps)
            # per-request wall latency, measured on a separate
            # instrumented pass (the timed passes above stay untouched)
            _, lat, probe_srv = _latency_probe(
                lambda: DataflowServer(bench.graph, slots=slots,
                                       block_cycles=block, engine=eng),
                feeds)
            p50, p99 = _pcts(lat)
            out.append(dict(
                name=name, backend=backend, R=R, slots=slots, K=block,
                long_len=long_len,
                wave_s=round(wave_s, 4), cont_s=round(cont_s, 4),
                wave_req_per_s=round(R / wave_s, 1),
                cont_req_per_s=round(R / cont_s, 1),
                speedup=round(wave_s / cont_s, 2),
                wave_dispatches=wave_disp, cont_dispatches=cont_disp,
                cont_p50_ms=p50, cont_p99_ms=p99,
                max_queue_depth=probe_srv.max_queue_depth,
                mean_queue_wait_blocks=round(float(np.mean(waits)), 2),
                mean_residency_cycles=round(float(np.mean(
                    [r.metrics.residency_cycles for r in cont_res])), 1)))
    return out


def fault_rows(benches=("vector_sum",), backend="xla", R: int = 16,
               slots: int = 4, block: int = 8,
               long_len: int = 64, every: int = 4):
    """"_faulted" rows: the same mixed-length trace served through a
    seeded FaultPlan (transient dispatch failures + wedges + poisoned
    feeds) — measuring what the fault-tolerance machinery costs and
    recording the disposition mix.  Every request must still be
    answered; the row asserts conservation before it is emitted."""
    out = []
    for name in benches:
        bench = library.BENCHES[name]()
        if np.dtype(bench.dtype) != np.int32:
            continue
        feeds = workload(name, bench, R, long_len=long_len, every=every)

        def mk():
            return DataflowServer(
                bench.graph, slots=slots, block_cycles=block,
                backend=backend, max_retries=3, wedge_timeout_blocks=4,
                faults=FaultPlan(seed=11, dispatch_fail_rate=0.05,
                                 transient_attempts=1,
                                 wedge_rate=0.1, poison_rate=0.1))

        _latency_probe(mk, feeds)          # warmup (compiles)
        t0 = time.perf_counter()
        res, lat, srv = _latency_probe(mk, feeds)
        total_s = time.perf_counter() - t0
        assert len(res) == R, "every request must be answered"
        p50, p99 = _pcts(lat)
        statuses: dict[str, int] = {}
        for r in res:
            statuses[r.status] = statuses.get(r.status, 0) + 1
        out.append(dict(
            name=f"{name}_faulted", backend=backend, R=R, slots=slots,
            K=block, long_len=long_len,
            cont_s=round(total_s, 4),
            cont_req_per_s=round(R / total_s, 1),
            cont_p50_ms=p50, cont_p99_ms=p99,
            max_queue_depth=srv.max_queue_depth,
            statuses=statuses, retries=len(
                [e for e in srv.events if e["kind"] == "dispatch-retry"])))
    return out


def export_observability(bench_name: str = "vector_sum",
                         backend: str = "xla", R: int = 8,
                         slots: int = 2, block: int = 4,
                         long_len: int = 8,
                         trace_path: str | None = None,
                         metrics_path: str | None = None) -> dict:
    """``--trace``: one fully instrumented serve (profile + trace +
    metrics all on); writes BENCH_serve_trace.json (Chrome trace-event
    JSON — load it in Perfetto / chrome://tracing) and
    BENCH_serve_metrics.json, then re-loads and validates both so a
    malformed export fails the CI smoke right here.

    Honours ``REPRO_FAULTS``: when the chaos job sets it (anything but
    "off"), the serve runs under a seeded FaultPlan and the export must
    contain fault-injection events."""
    from repro.obs import (MetricsRegistry, TraceRecorder, load_chrome,
                           validate_chrome, validate_snapshot)
    bench = library.BENCHES[bench_name]()
    feeds = workload(bench_name, bench, R, long_len=long_len, every=3)
    chaos = os.environ.get("REPRO_FAULTS", "").lower() not in ("", "off")
    plan = FaultPlan.scaled(seed=11, dispatch_fail_rate=0.1,
                            transient_attempts=1, wedge_rate=0.15,
                            poison_rate=0.15) if chaos else None
    tr, mr = TraceRecorder(), MetricsRegistry()
    srv = DataflowServer(bench.graph, slots=slots, block_cycles=block,
                         backend=backend, wedge_timeout_blocks=4,
                         faults=plan, profile=True, trace=tr, metrics=mr)
    for f in feeds:
        srv.submit(f)
    res = srv.drain()
    assert len(res) == R, "every request must be answered"
    profiled = [r for r in res
                if r.engine is not None and r.engine.profile is not None]
    for r in profiled:
        r.engine.profile.check()
    fires = sum(r.engine.profile.fired for r in profiled)
    root = os.path.join(os.path.dirname(__file__), "..")
    trace_path = trace_path or os.path.join(root, "BENCH_serve_trace.json")
    metrics_path = metrics_path or os.path.join(root,
                                                "BENCH_serve_metrics.json")
    tr.save(trace_path)
    mr.save(metrics_path)
    info = validate_chrome(load_chrome(trace_path))
    with open(metrics_path) as f:
        validate_snapshot(json.load(f))
    kinds = sorted({e.kind for e in tr.events})
    if plan is not None and plan.log:
        assert "fault" in kinds, \
            f"chaos run injected faults but the trace has none: {kinds}"
    print(f"serve_trace_{bench_name}_{backend},0,"
          f"events={info['events']};uids={info['uids']};"
          f"tracks={info['tracks']};fires={fires};"
          f"chaos={int(chaos)};kinds={'+'.join(kinds)}")
    return dict(trace=trace_path, metrics=metrics_path, kinds=kinds,
                fires=fires, **info)


def print_csv(recs):
    for r in recs:
        base = f"serve_{r['name']}_{r['backend']}"
        if "wave_s" in r:
            print(f"{base}_wave,{r['wave_s'] * 1e6:.0f},"
                  f"req_per_s={r['wave_req_per_s']};"
                  f"dispatches={r['wave_dispatches']}")
        tail = (f"speedup={r['speedup']};"
                f"wait_blocks={r['mean_queue_wait_blocks']}"
                if "speedup" in r else
                f"statuses={r['statuses']};retries={r['retries']}")
        print(f"{base}_cont,{r['cont_s'] * 1e6:.0f},"
              f"req_per_s={r['cont_req_per_s']};"
              f"p50_ms={r['cont_p50_ms']};p99_ms={r['cont_p99_ms']};"
              f"max_queue={r['max_queue_depth']};" + tail)


def _write(recs, path: str | None) -> None:
    path = path or os.path.join(os.path.dirname(__file__), "..",
                                "BENCH_serve.json")
    with open(path, "w") as f:
        json.dump(recs, f, indent=1)


def main(path: str | None = None) -> list[dict]:
    recs = serve_rows() + fault_rows()
    _write(recs, path)
    print_csv(recs)
    for backend in ("xla", "pallas"):
        rows = [r for r in recs if r["backend"] == backend
                and "speedup" in r]
        wins = [r["name"] for r in rows if r["speedup"] > 1.0]
        print(f"serve_summary_{backend},0,continuous_beats_wave_on="
              f"{len(wins)}/{len(rows)}:{'+'.join(wins)}")
    return recs


def quick(path: str | None = None) -> list[dict]:
    """CI smoke: 3 benches at tiny K/B, reps=1 — exercises the code
    paths (incl. the faulted row) and writes the full JSON schema, p50/
    p99 latency and queue high-water included, without reproducing the
    committed full-run speedups."""
    recs = serve_rows(benches=("vector_sum", "fibonacci", "gcd"),
                      backends=("xla", "pallas"), R=6, slots=2, block=4,
                      reps=1, long_len=8, every=3)
    recs += fault_rows(R=6, slots=2, block=4, long_len=8)
    _write(recs, path)
    print_csv(recs)
    return recs


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    quick() if "--quick" in sys.argv else main()
    if "--trace" in sys.argv:
        export_observability()
