"""Benchmark of the dataflow server on a TPU: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration, a traffic mix and
its metrics; each is found in files of its own (``cells.py``).  One
process: set-up (compile through the persistent cache in
``<checkout>/.jax_cache``, build the server, warm up the cell's own
shapes, draw the traffic from ``--seed``), then ``--seconds`` of
serving, then a drain, then the check of what the window produced
against the frozen reference (``reference.py``).

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from host spans around the server's slot API and
from the profiler's trace of the window.  The last line of standard
output is one JSON object; the last lines of standard error are the
numbers that decide ``correct``, each beside its limit.  It exits 2,
printing no result, where JAX finds no TPU or fewer chips than the cell
asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

import cells  # noqa: E402
import drive  # noqa: E402
import reference  # noqa: E402
import traffic as traffic_mod  # noqa: E402

CHECK_SAMPLE = 128      # answered requests compared with the reference
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def say(*a) -> None:
    print(*a, flush=True)


def require_tpu(jax, chips: int) -> dict:
    """The device record; exits 2 without a TPU or with too few chips."""
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"run.py: needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform!r} device(s)", file=sys.stderr)
        raise SystemExit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class Compiles:
    """Counts JAX's tracing, lowering and compiling events, and sums
    their seconds by kind, until ``close``."""

    def __init__(self):
        import jax
        self.n = 0
        self.seconds = dict.fromkeys(COMPILE_EVENTS, 0.0)
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event in self.seconds:
            self.n += 1
            self.seconds[event] += secs

    def split(self) -> dict:
        return {f"{e.rsplit('/', 1)[1].replace('_duration', '')}_s": s
                for e, s in self.seconds.items()}

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self._on)


def build_server(cell, graph):
    from repro.serve.dataflow_server import DataflowServer
    c = cell.config
    return DataflowServer(graph, slots=int(c["slots"]),
                          block_cycles=int(c["block_cycles"]),
                          backend=c["backend"],
                          max_cycles=int(c["max_cycles"]),
                          optimize=bool(c["optimize"]),
                          schedule=c["schedule"])


def serve_one(srv, arcs, length: int, uid: int = -1) -> int:
    """Submit one request of ``length`` zero tokens and turn the
    heartbeat until it is answered; returns the heartbeats it took."""
    from repro.serve.types import Request
    srv.submit(Request(uid=uid, feeds={a: np.zeros(length, np.int32)
                                       for a in arcs}))
    for beats in range(1, 100_000):
        done = srv.step()
        if done:
            if done[0].status != "ok":
                raise RuntimeError(f"request {uid}: {done[0].status}")
            return beats
    raise RuntimeError(f"request {uid} never finished")


def warm_up(srv, arcs, length: int) -> None:
    """Serve one request as long as the cell's longest, through the
    server's own entry points: the engine grows its feed buffer as the
    window would, and the admission reset and the K-cycle step compile
    at the shapes the window will use."""
    serve_one(srv, arcs, length)


def profile_options(jax):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def mosaic_missing(srv, arcs) -> int:
    """1 if a probe request, served after the drain under the profiler,
    runs no Mosaic kernel (``trace_reduce.KERNELS``) on the device, that
    is, the server's pallas path is not what ran.  Read from what
    executed, so it holds whatever operands the kernel takes."""
    import jax
    import trace_reduce
    from jax.profiler import TraceAnnotation
    d = tempfile.mkdtemp(prefix="bench_probe_")
    try:
        jax.profiler.start_trace(d, profiler_options=profile_options(jax))
        try:
            with TraceAnnotation("bench.window"):
                serve_one(srv, arcs, 2 * int(srv.engine.block_cycles),
                          uid=-2)
        finally:
            jax.profiler.stop_trace()
        kernels = trace_reduce.reduce_dir(d)["kernels"]
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return int(sum(k["count"] for k in kernels.values()) == 0)


def compare(fabric, traffic, arcs, log, dtype=np.int32) -> int:
    """Kept answers (``drive.Log.kept``) that differ from the reference's
    in any output value or count, their cycles or their firings."""
    kept = log.kept()
    uids = sorted(kept)
    want = reference.run(fabric, [traffic.feeds(log.pool[u], arcs)
                                  for u in uids], dtype)
    bad = 0
    for u, w in zip(uids, want):
        e = kept[u]
        got = ({a: int(np.asarray(v, np.int32)) for a, v in e.outputs.items()},
               {a: int(v) for a, v in e.counts.items()}, e.cycles, e.fired)
        exp = ({a: int(np.int32(v)) for a, v in w.outputs.items()},
               w.counts, w.cycles, w.fired)
        bad += got != exp
    return bad


@dataclasses.dataclass
class Served:
    """A run up to the drain: what the checks and the readers read."""
    seconds: float
    setup_s: float
    phases: dict
    compiles_in_window: int
    srv: object
    arcs: list
    traffic: object
    log: object
    spans: object
    device: dict
    trace: dict | None = None


def serve(cell, seed: int, seconds: float, trace: bool, device: dict,
          t_start: float = T_START) -> Served:
    """Set-up, the measured window and the drain; the server is left
    as the drain left it."""
    import jax
    from repro.core import asm

    phases = {"start_to_jax_s": time.perf_counter() - t_start}
    compiles = Compiles()
    try:
        t = time.perf_counter()
        graph = asm.parse(cell.netlist, name=cell.config["name"])
        srv = build_server(cell, graph)
        arcs = reference.parse(cell.netlist).input_arcs()
        phases["build_s"] = time.perf_counter() - t
        t = time.perf_counter()
        traffic = traffic_mod.generate(cell.mix, len(arcs),
                                       int(cell.config["slots"]), seed,
                                       seconds)
        phases["traffic_s"] = time.perf_counter() - t
        t = time.perf_counter()
        warm_up(srv, arcs, traffic.max_len)
        phases["warm_up_s"] = time.perf_counter() - t
        phases.update(compiles.split())
        spans = drive.Spans(trace)
        spans.wrap(srv.engine)
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace \
            else None
        if trace:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=profile_options(jax))
        log = drive.Log(keep=CHECK_SAMPLE, rng=random.Random(seed))
        n_before = compiles.n
        gc.collect()
        gc.freeze()        # set-up's objects leave the collector's walk
        setup_s = time.perf_counter() - t_start
        try:
            log = drive.window(srv, traffic, arcs, seconds, spans, log)
        finally:
            if trace:
                jax.profiler.stop_trace()
            spans.unwrap(srv.engine)
        in_window = compiles.n - n_before
    finally:
        compiles.close()
    drive.drain(srv, log)
    gc.unfreeze()
    stats = jax.devices()[0].memory_stats() or {}
    device = dict(device, memory_peak_bytes=int(
        stats.get("peak_bytes_in_use", 0)))
    served = Served(seconds, setup_s, phases, in_window, srv, arcs, traffic,
                    log, spans, device)
    if trace:
        import trace_reduce
        try:
            served.trace = trace_reduce.reduce_dir(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        served.device.update(busy_s=served.trace["busy_s"],
                             window_s=served.trace["window_s"])
    return served


def due(s: Served) -> list:
    """Requests the window owes an answer: in an open loop those due
    before it closed, in a backlog every one sent."""
    if s.traffic.mode == "open":
        return s.log.due_in_window(s.seconds)
    return sorted(s.log.due)


def server_checks(s: Served) -> dict:
    """Numbers read off the served run, each with the limit 0."""
    owed = due(s)
    answered = [u for u in owed if u in s.log.done]
    return {
        "unanswered": len(owed) - len(answered),
        "not_ok": sum(s.log.status[u] != "ok" for u in answered),
        "server_events": len(s.srv.events) + int(s.srv.degraded)
        + s.log.retries,
        "no_mosaic": mosaic_missing(s.srv, s.arcs),
    }


def report(s: Served) -> None:
    log, owed = s.log, due(s)
    answered = [u for u in owed if u in log.done]
    in_window = sum(log.done[u] <= log.window_s for u in answered)
    late = sorted(log.sent[u] - log.due[u] for u in owed) or [0.0]
    say(f"setup: {json.dumps(s.phases)} setup_s={s.setup_s}")
    say(f"window: {log.window_s} s, {log.heartbeats} heartbeats, "
        f"{len(owed)} requests due, {in_window} answered in the window, "
        f"{len(answered)} after the drain of "
        f"{log.drained_s - log.window_s} s; longest stream "
        f"{s.traffic.max_len} tokens; generator lateness p99 "
        f"{late[int(0.99 * (len(late) - 1))] * 1e3} ms, max "
        f"{late[-1] * 1e3} ms")
    say(f"compiles inside the window: {s.compiles_in_window}")


def measure(cell, seed: int, seconds: float, trace: bool,
            device: dict, t_start: float = T_START) -> dict:
    """Everything after the look for a chip; returns the result line."""
    s = serve(cell, seed, seconds, trace, device, t_start)
    report(s)
    checks = server_checks(s)
    graph = s.srv.graph
    fabric_sizes = {"arcs": len(graph.arcs), "inputs": len(s.arcs),
                    "outputs": len(graph.output_arcs())}
    s.srv = None                 # free the program's state first
    t = time.perf_counter()
    checks["mismatched"] = compare(reference.parse(cell.netlist), s.traffic,
                                   s.arcs, s.log)
    checks["none_compared"] = int(not s.log.kept())
    say(f"reference: {len(s.log.kept())} answers compared in "
        f"{time.perf_counter() - t} s, the longest {s.log.longest[0]} "
        "cycles")
    view = RunView(s.traffic.mode, seconds, s.setup_s, s.log,
                   s.traffic, s.spans, s.trace, fabric_sizes,
                   int(cell.config["slots"]),
                   int(cell.config["block_cycles"]),
                   _peaks(s.device["kind"]) if trace else None)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = cells.reader(m["name"], cell.root)(view)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    out = {"correct": not any(checks.values()),
           "attempted": len(due(s)),
           "failed": checks["unanswered"] + checks["not_ok"],
           "metrics": metrics, "device": s.device}
    if trace:
        out["breakdown"] = s.trace["breakdown"]
    out["checks"] = {k: {"value": v, "limit": 0} for k, v in checks.items()}
    return out


@dataclasses.dataclass
class RunView:
    """What a metric reader reads (``metrics/<name>.py``)."""
    mode: str
    seconds: float
    setup_s: float
    log: object
    traffic: object
    spans: object
    trace: dict | None
    fabric: dict          # arcs, inputs, outputs of the netlist
    slots: int
    block_cycles: int
    peaks: dict | None


def _peaks(kind: str):
    import roofline
    return roofline.peaks(kind)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    sys.path.insert(0, str(cells.ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache(cells.ROOT)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device = require_tpu(jax, cell.chips)
    say(f"device: {json.dumps(device)}; compile cache {cache}")
    out = measure(cell, args.seed, args.seconds, bool(args.trace), device)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
