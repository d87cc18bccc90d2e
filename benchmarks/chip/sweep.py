"""Sweeps that define a cell, on the chip: an open-loop cell's knee, or
the slot count at which a backlog cell's throughput stops growing.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
        --seconds <s> (--rates 1000,2000,... | --slots 1024,2048,...)

Serves the cell exactly as ``run.py`` does, once per value, in one
process, with no drain.  ``--rates`` (an open cell) prints per offered
rate (requests/s) the requests due, those still waiting for a slot and
those pending at the window's close, and the p50 and p99 due-to-answer
latency; a rate is sustained when nothing is left waiting for a slot at
the close, and the cell's mix then takes about four fifths of the
highest such rate.  ``--slots`` (a backlog cell) prints per slot count
the tokens per second answered in the window, the heartbeats and the
process's device memory peak so far (give the counts rising).  Tools
for defining a cell, not part of a run.  Exits 2 without a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time

import cells
import drive
import run
import stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    how = ap.add_mutually_exclusive_group(required=True)
    how.add_argument("--rates")
    how.add_argument("--slots")
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    sys.path.insert(0, str(cells.ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(cells.ROOT)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device = run.require_tpu(jax, cell.chips)
    drive.DRAIN_S = 0.0     # an overloaded rate would drain for minutes
    if args.slots:
        tokens = cells.reader("tokens_per_s", cell.root)
        for n in (int(x) for x in args.slots.split(",")):
            c = dataclasses.replace(cell, config=dict(cell.config, slots=n))
            s = run.serve(c, args.seed, args.seconds, False, device,
                          time.perf_counter())
            s.srv = None
            view = run.RunView(s.traffic.mode, args.seconds, s.setup_s,
                               s.log, s.traffic, s.spans, None, {}, n,
                               int(cell.config["block_cycles"]), None)
            run.say(json.dumps({
                "slots": n, "tokens_per_s": tokens(view),
                "heartbeats": s.log.heartbeats,
                "heartbeat_ms": s.log.window_s / s.log.heartbeats * 1e3,
                "memory_peak_bytes": s.device["memory_peak_bytes"]}))
            del s
            gc.collect()
        return 0
    for rate in (float(x) for x in args.rates.split(",")):
        c = dataclasses.replace(cell, mix=dict(cell.mix, rate_per_s=rate))
        s = run.serve(c, args.seed, args.seconds, False, device,
                      time.perf_counter())
        checks = run.server_checks(s)
        lat = drive.latencies_ms(s.log, args.seconds)
        run.say(json.dumps({
            "rate_per_s": rate, "due": len(lat),
            "heartbeats": s.log.heartbeats,
            "queued_at_close": s.log.queued_at_close,
            "pending_at_close": s.log.pending_at_close,
            "p50_ms": stats.percentile(lat, 50),
            "p99_ms": stats.percentile(lat, 99), **checks}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
