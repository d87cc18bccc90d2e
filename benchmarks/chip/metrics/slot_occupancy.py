"""Scheduler: mean share of slots holding a request, read at each
heartbeat's step, in %."""


def read(run):
    occ = run.spans.occupancy
    return 100.0 * sum(occ) / len(occ) if occ else None
