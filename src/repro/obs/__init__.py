"""repro.obs -- fabric observability (DESIGN.md §12).

Three layers, from device to host:

- ``profile``  : per-node / per-arc fabric counters (fire counts, stall
  attribution, arc occupancy) accumulated in device state by the block
  kernels and surfaced as a :class:`FabricProfile`.
- ``trace``    : :class:`TraceRecorder`, a block-clock event log of the
  slot-lifecycle state machine (DESIGN.md §11), exportable as Chrome
  trace-event JSON loadable in Perfetto.
- ``metrics``  : :class:`MetricsRegistry`, process-local counters /
  gauges / histograms with a JSON snapshot.
- ``probe``    : :class:`Probe`, one server's recorder and registry as
  the serving hot path sees them (spans and counters per heartbeat).

Nothing in this package imports jax: the engine hands over plain numpy
arrays, so obs stays importable from any host-side tool.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               validate_snapshot)
from repro.obs.probe import Probe
from repro.obs.profile import FabricProfile
from repro.obs.trace import (
    TraceInvariantError,
    TraceRecorder,
    load_chrome,
    validate_chrome,
)

__all__ = [
    "Counter",
    "FabricProfile",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "Probe",
    "TraceInvariantError",
    "TraceRecorder",
    "load_chrome",
    "validate_chrome",
    "validate_snapshot",
]
