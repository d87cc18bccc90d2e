"""Scheduler: self time of the program's span ``dataflow.heartbeat`` (the
heartbeat less its admit, step and harvest spans: expiry and exit sweeps,
free and quiesced slot scans, the block length) per heartbeat of the
window, in ms.  Program span."""
import program_spans


def read(run):
    return program_spans.per_heartbeat_ms(getattr(run, "obs", None),
                                          program_spans.HEARTBEAT,
                                          "self_ns")
