"""Roofline table: aggregate the dry-run JSON records (launch/dryrun.py)
into the per-(arch x shape x mesh) table for EXPERIMENTS.md §Roofline,
plus the fabric-interior roofline from the §12 counter sweep
(BENCH_profile.json, written by ``run.py --trace``).

The fabric section compares each bench's *achieved* cadence against
the paper fabric's handshake bound: an arc's full/empty register pair
moves at most one token every 2 cycles, so per-arc occupancy is
bounded by 0.5 at steady state and a node can fire at most every
other cycle.  ``cadence_frac`` = hottest node's fires-per-cycle over
that 0.5 bound — the dataflow analogue of "fraction of peak FLOPs".

The scheduled section (``sched_rows``, from BENCH_opt.json's sched
records) plots each control-free bench's *scheduled* steady-state
output cadence — tokens per cycle of the locked period (DESIGN.md
§13) — against the same 0.5 tokens/cycle handshake bound and the
dynamic engine's measured output cadence, showing where software-
pipelined arc registers push throughput past the handshake cadence.

The sharding section (``shard_rows``, from BENCH_shard.json, written
by ``run.py --shard``) inspects the §14 multi-fabric speedup story the
same way: *per-region cadence* — the ideal speedup is bounded by the
hottest region's weight fraction (1/max_region_frac, the spatial
Amdahl term) — vs *channel-bound cadence* — each cut arc is a
register-pair channel moving at most one token every 2 cycles, so a
K-cycle block carries at most 0.5*K tokens per channel; measured
cut-arc traffic per block over that capacity says whether the fabric
is compute- or channel-limited at this partition.

CSV: name,us_per_call,derived  (us_per_call = dominant term in us)
"""
from __future__ import annotations

import glob
import json
import os

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..",
                          "experiments", "dryrun")

PROFILE_JSON = os.path.join(os.path.dirname(__file__), "..",
                            "BENCH_profile.json")

OPT_JSON = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_opt.json")

SHARD_JSON = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_shard.json")

# handshake cadence bound: 1 token per 2 cycles per arc (DESIGN.md §2)
CADENCE_BOUND = 0.5


def fabric_rows(path: str | None = None) -> list[dict]:
    """Fabric-interior roofline rows from the §12 profile sweep."""
    path = path or PROFILE_JSON
    if not os.path.exists(path):
        return []
    with open(path) as f:
        recs = json.load(f)
    rows = []
    for r in recs:
        p = r["profile"]
        cycles = max(p["cycles"], 1)
        hot = max(p["nodes"], key=lambda n: n["fires"],
                  default={"name": "-", "fires": 0})
        hot_rate = hot["fires"] / cycles
        occ = [a["busy"] / cycles for a in p["arcs"]]
        rows.append(dict(
            name=r["name"], backend=r["backend"],
            cycles=p["cycles"], fired=p["fired"],
            dispatches=p["dispatches"],
            fires_per_dispatch=round(p["fires_per_dispatch"], 1),
            utilization=round(p["utilization"], 4),
            hot_node=hot["name"],
            hot_fires_per_cycle=round(hot_rate, 4),
            cadence_frac=round(hot_rate / CADENCE_BOUND, 4),
            max_arc_occupancy=round(max(occ, default=0.0), 4),
            mean_arc_occupancy=round(
                sum(occ) / len(occ), 4) if occ else 0.0))
    return rows


def fabric_main(path: str | None = None) -> None:
    rows = fabric_rows(path)
    if not rows:
        print("roofline_fabric_no_records,0,run run.py --trace first")
        return
    for r in rows:
        print(f"roofline_fabric_{r['name']}_{r['backend']},0,"
              f"fires_per_dispatch={r['fires_per_dispatch']};"
              f"util={r['utilization']};"
              f"hot={r['hot_node']}@{r['hot_fires_per_cycle']}/cyc;"
              f"cadence_frac={r['cadence_frac']}"
              f"(bound={CADENCE_BOUND}/arc);"
              f"arc_occ_max={r['max_arc_occupancy']};"
              f"arc_occ_mean={r['mean_arc_occupancy']}")


def sched_rows(path: str | None = None) -> list[dict]:
    """Scheduled-cadence rows from BENCH_opt.json's sched records
    (largest K, B=1): the locked period's tokens/cycle vs the 0.5
    handshake bound vs the dynamic engine's measured output cadence
    (tokens/cycle of the matching opt="full" record)."""
    path = path or OPT_JSON
    if not os.path.exists(path):
        return []
    with open(path) as f:
        payload = json.load(f)
    recs = payload["records"] if isinstance(payload, dict) else payload
    if not recs:
        return []
    K = max(r["K"] for r in recs)
    rows = []
    for r in recs:
        if (r.get("opt") != "sched" or not r.get("scheduled")
                or r["B"] != 1 or r["K"] != K
                or "steady_tokens_per_cycle" not in r):
            continue
        dyn = next((d for d in recs
                    if d["name"] == r["name"]
                    and d["backend"] == r["backend"]
                    and d["B"] == 1 and d["K"] == K
                    and d["opt"] == "full"), None)
        steady = r["steady_tokens_per_cycle"]
        row = dict(name=r["name"], backend=r["backend"], K=K,
                   period_cycles=r["period_cycles"],
                   period_tokens=r["period_tokens"],
                   steady_tokens_per_cycle=steady,
                   bound_frac=round(steady / CADENCE_BOUND, 4))
        if dyn is not None:
            row["dynamic_tokens_per_cycle"] = round(
                dyn["tokens_per_s"] / max(dyn["cycles_per_s"], 1), 4)
            row["speedup_vs_dynamic"] = round(
                r["cycles_per_s"] / max(dyn["cycles_per_s"], 1), 2)
        rows.append(row)
    return rows


def sched_main(path: str | None = None) -> None:
    rows = sched_rows(path)
    if not rows:
        print("roofline_sched_no_records,0,run run.py --opt first")
        return
    for r in rows:
        dyn = r.get("dynamic_tokens_per_cycle", "-")
        spd = r.get("speedup_vs_dynamic", "-")
        print(f"roofline_sched_{r['name']}_{r['backend']},0,"
              f"steady={r['steady_tokens_per_cycle']}tok/cyc"
              f"(period={r['period_tokens']}tok/"
              f"{r['period_cycles']}cyc);"
              f"bound_frac={r['bound_frac']}"
              f"(handshake={CADENCE_BOUND}tok/cyc);"
              f"dynamic={dyn}tok/cyc;"
              f"speedup_vs_dynamic={spd}x")


def shard_rows(path: str | None = None) -> list[dict]:
    """Sharding roofline rows from BENCH_shard.json (P>1 records):
    measured speedup vs the per-region cadence bound (1/max_region_frac
    — the hottest region paces the lockstep global cycle) and cut-arc
    traffic per block vs the channel capacity (0.5*K tokens per channel
    per block, the handshake cadence over a K-cycle block)."""
    path = path or SHARD_JSON
    if not os.path.exists(path):
        return []
    with open(path) as f:
        payload = json.load(f)
    recs = payload["records"] if isinstance(payload, dict) else payload
    rows = []
    for r in recs:
        if r["P"] <= 1:
            continue
        ideal = 1.0 / max(r["max_region_frac"], 1e-9)
        cap = CADENCE_BOUND * r["K"] * r["cut_arcs"]
        traffic = r.get("cut_tokens_per_block") or 0.0
        rows.append(dict(
            name=r["name"], P=r["P"], K=r["K"],
            speedup_vs_p1=r["speedup_vs_p1"],
            region_bound_speedup=round(ideal, 3),
            region_cadence_frac=round(r["speedup_vs_p1"] / ideal, 4),
            cut_arcs=r["cut_arcs"],
            cut_tokens_per_block=traffic,
            channel_capacity_per_block=round(cap, 1),
            channel_bound_frac=round(traffic / cap, 4) if cap else 0.0,
            shard_map=r.get("shard_map", False),
            devices=r.get("devices"), host_cpus=r.get("host_cpus")))
    return rows


def shard_main(path: str | None = None) -> None:
    rows = shard_rows(path)
    if not rows:
        print("roofline_shard_no_records,0,run run.py --shard first")
        return
    for r in rows:
        print(f"roofline_shard_{r['name']}_P{r['P']},0,"
              f"speedup={r['speedup_vs_p1']}x"
              f"(region_bound={r['region_bound_speedup']}x);"
              f"region_cadence_frac={r['region_cadence_frac']};"
              f"cut_traffic={r['cut_tokens_per_block']}tok/blk"
              f"(cap={r['channel_capacity_per_block']});"
              f"channel_bound_frac={r['channel_bound_frac']};"
              f"devices={r['devices']};host_cpus={r['host_cpus']}")


def load(tag: str | None = None, mesh: str | None = None):
    recs = []
    for path in sorted(glob.glob(os.path.join(DRYRUN_DIR, "*.json"))):
        with open(path) as f:
            r = json.load(f)
        if tag and r.get("tag") != tag:
            continue
        if mesh and r.get("mesh") != mesh:
            continue
        recs.append(r)
    return recs


def table(recs):
    rows = []
    for r in recs:
        if r.get("status") != "ok":
            rows.append((r["arch"], r["shape"], r["mesh"], r["status"],
                         None))
            continue
        rows.append((r["arch"], r["shape"], r["mesh"], "ok",
                     r["roofline"]))
    return rows


def main():
    fabric_main()
    sched_main()
    shard_main()
    recs = load(tag="baseline", mesh="pod")
    if not recs:
        print("roofline_no_records,0,run launch/dryrun.py first")
        return
    for arch, shape, mesh, status, rf in table(recs):
        if rf is None:
            print(f"roofline_{arch}_{shape},0,{status}")
            continue
        dom_s = rf[f"{rf['dominant']}_s"]
        derived = (f"dominant={rf['dominant']};"
                   f"compute_s={rf['compute_s']:.3e};"
                   f"memory_s={rf['memory_s']:.3e};"
                   f"collective_s={rf['collective_s']:.3e};"
                   f"useful={rf['useful_flops_ratio']:.3f}")
        print(f"roofline_{arch}_{shape},{dom_s * 1e6:.1f},{derived}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    main()
