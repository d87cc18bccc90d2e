"""Fabric: share of node-cycles on active slots in which the node fired
(counter ``node_firings`` over the fabric's nodes times
``active_slot_cycles``), in %.  Program counter; reads ``None`` until
the run hands the readers the program's counters (``run.obs``) and the
fabric's node count (``run.fabric["nodes"]``)."""


def read(run):
    obs = getattr(run, "obs", None)
    nodes = (getattr(run, "fabric", None) or {}).get("nodes")
    cycles = obs.counter("active_slot_cycles") if obs is not None else 0
    if not cycles or not nodes:
        return None
    return 100.0 * obs.counter("node_firings") / (nodes * cycles)
