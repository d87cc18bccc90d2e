"""Kernel: device time of the ``dataflow_fire_block`` events in the
trace, over the slots x cycles the window's steps simulated, in ns."""


def read(run):
    k = (run.trace or {}).get("kernels", {}).get("dataflow_fire_block")
    if not k or not k["count"] or not run.spans.slot_cycles:
        return None
    return k["seconds"] / run.spans.slot_cycles * 1e9
