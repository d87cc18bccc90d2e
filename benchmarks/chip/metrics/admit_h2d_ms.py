"""Scheduler admission: host time in the program's span
``dataflow.admit.h2d`` (handing the round's staging buffers to the
device) per heartbeat of the window, in ms.  Program span."""
import program_spans


def read(run):
    return program_spans.per_heartbeat_ms(getattr(run, "obs", None),
                                          "dataflow.admit.h2d")
