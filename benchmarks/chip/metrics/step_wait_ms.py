"""Engine slot API: host time in the program's span ``dataflow.step.wait``
(the step's one readback of fired counts and last progress, which waits
for the device) per heartbeat of the window, in ms.  Program span."""
import program_spans


def read(run):
    return program_spans.per_heartbeat_ms(getattr(run, "obs", None),
                                          "dataflow.step.wait")
