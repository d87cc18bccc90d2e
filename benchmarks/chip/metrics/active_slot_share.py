"""Scheduler: share of the slot-cycles the window's steps simulated that
fell on slots holding a request (counters ``active_slot_cycles`` over
``slot_cycles``), in %.  Program counter."""


def read(run):
    obs = getattr(run, "obs", None)
    total = obs.counter("slot_cycles") if obs is not None else 0
    if not total:
        return None
    return 100.0 * obs.counter("active_slot_cycles") / total
