"""Scheduler admission: host time in ``DataflowEngine.reset_slots`` (pack
the admitted requests' feeds, stage the slot buffer, dispatch the fused
reset) over the window, per heartbeat, in ms.  Benchmark span."""


def read(run):
    beats = run.log.heartbeats
    if not beats or "reset_slots" not in run.spans.seconds:
        return None
    return sum(run.spans.seconds["reset_slots"]) / beats * 1e3
