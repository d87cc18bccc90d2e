"""Engine slot API: host time in ``DataflowEngine.step_block`` (the
K-cycle dispatch and its one host sync, which waits for the device)
over the window, per heartbeat, in ms.  Benchmark span."""


def read(run):
    beats = run.log.heartbeats
    if not beats or "step_block" not in run.spans.seconds:
        return None
    return sum(run.spans.seconds["step_block"]) / beats * 1e3
