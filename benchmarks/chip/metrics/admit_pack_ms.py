"""Scheduler admission: host time in the program's span
``dataflow.admit.pack`` (packing each admitted request's feeds, then
allocating and filling the round's staging buffers) per heartbeat of the
window, in ms.  Program span."""
import program_spans


def read(run):
    return program_spans.per_heartbeat_ms(getattr(run, "obs", None),
                                          "dataflow.admit.pack")
