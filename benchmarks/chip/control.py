"""The control of a cell's correctness check, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \
        --seconds <s>

For each seed, in one process: serve the cell's traffic for ``--seconds``
exactly as ``run.py`` does, then compare the same sample of answers
twice: with the frozen reference in the token type the configuration
states (int32), which is the check every run makes, and with the same
reference computed in the next narrower type (int16), which stands in
for a program that narrowed its tokens.  The first must read 0
mismatched answers and the second must not, or the check cannot tell
a narrowed program from a sound one.  Prints one line per seed and a
JSON summary last.  Exits 2 without a TPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

import cells  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402


def readings(cell, seed: int, seconds: float, device: dict) -> dict:
    s = run.serve(cell, seed, seconds, False, device, time.perf_counter())
    checks = run.server_checks(s)
    s.srv = None
    fabric = reference.parse(cell.netlist)
    return {"seed": seed, "checked": len(s.log.kept()), **checks,
            "program_mismatched": run.compare(fabric, s.traffic, s.arcs,
                                              s.log, np.int32),
            "control_mismatched": run.compare(fabric, s.traffic, s.arcs,
                                              s.log, np.int16)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    sys.path.insert(0, str(cells.ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(cells.ROOT)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device = run.require_tpu(jax, cell.chips)
    rows = []
    for seed in (int(x) for x in args.seeds.split(",")):
        r = readings(cell, seed, args.seconds, device)
        run.say(json.dumps(r))
        rows.append(r)
    print(json.dumps({
        "workload": cell.name, "device": device,
        "program_max": max(r["program_mismatched"] for r in rows),
        "control_min": min(r["control_mismatched"] for r in rows),
        "checked_min": min(r["checked"] for r in rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
