"""Engine slot API: bytes the program reads back from the device (counter
``d2h_bytes``: the step's fired counts and last progress, harvest's
output registers) per heartbeat of the window, in KiB.  Program
counter."""


def read(run):
    obs = getattr(run, "obs", None)
    if obs is None or not obs.heartbeats():
        return None
    return obs.counter("d2h_bytes") / obs.heartbeats() / 1024
