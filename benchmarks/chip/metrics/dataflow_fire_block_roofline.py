"""Kernel: the least time the chip's HBM bandwidth allows for the
logical bytes of the window's ``dataflow_fire_block`` calls, over their
device time in the trace, in %.  The bandwidth bound only: the v5e
publishes no int32 vector peak."""
import roofline


def read(run):
    k = (run.trace or {}).get("kernels", {}).get("dataflow_fire_block")
    if not k or not k["count"] or not run.peaks:
        return None
    f = run.fabric
    per_call = roofline.fire_block_bytes(f["arcs"], f["inputs"],
                                         f["outputs"], run.slots,
                                         run.block_cycles)
    least = k["count"] * per_call / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / k["seconds"]
