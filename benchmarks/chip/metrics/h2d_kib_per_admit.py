"""Scheduler admission: bytes the program hands the device in its
admission rounds (counter ``h2d_bytes{site=admit}``) over the requests
it admitted (``requests_admitted``, summed over tenants) in the window,
in KiB.  Program counter."""


def read(run):
    obs = getattr(run, "obs", None)
    rows = obs.counter("requests_admitted") if obs is not None else 0
    if not rows:
        return None
    return obs.counters.get("h2d_bytes{site=admit}", 0) / rows / 1024
