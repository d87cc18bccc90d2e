"""Probe -- one server's recorder and registry, as its hot path sees them.

``DataflowServer`` builds one when it has a ``TraceRecorder`` or a
``MetricsRegistry`` and hands it to the engine's slot API call by call
(``reset_slots``, ``step_block``, ``harvest``), so an engine shared by
servers (``cached_engine``) never holds either.  Without both the server
keeps ``None``, and every site on the hot path costs one ``is None``
test: no clock is read, no annotation built, nothing recorded.  A slot
API call that raises closes the spans it opened (``mark``/``unwind``),
so a retried call's spans sit beside the failed one's, not inside them.

Counters the hot path keeps (DESIGN.md §12):

- ``h2d_bytes{site=admit}``: bytes of the host arrays an admission round
  hands the device;
- ``admit_splits``: extra reset dispatches of admission rounds whose
  tokens overflow the staging capacity;
- ``d2h_bytes{site=step|harvest}``: bytes read back from the device;
- ``retraces{what=feed_buffer|step}``: growths of the feed buffer, new
  jitted slot steps;
- ``slot_cycles`` / ``active_slot_cycles``: slots times cycles stepped,
  all slots and the active ones only.
"""
from __future__ import annotations


class Probe:
    __slots__ = ("trace", "metrics", "admitted", "active")

    def __init__(self, trace=None, metrics=None) -> None:
        self.trace = trace
        self.metrics = metrics
        # the open heartbeat's rows admitted and slots stepped
        self.admitted = 0
        self.active = 0

    def begin(self, name: str, block: int | None = None, **args) -> int:
        """Open a span (see ``TraceRecorder.begin``); -1 without a
        recorder."""
        if self.trace is None:
            return -1
        return self.trace.begin(name, block, **args)

    def end(self, i: int, **args) -> None:
        if i >= 0:
            self.trace.end(i, **args)

    def mark(self) -> int:
        """How many spans are open, for ``unwind``; -1 without a
        recorder."""
        return -1 if self.trace is None else self.trace.depth

    def unwind(self, mark: int) -> None:
        """Close the spans opened since ``mark``."""
        if mark >= 0:
            self.trace.unwind(mark)

    def count(self, name: str, n: int = 1, **labels) -> None:
        if self.metrics is not None:
            self.metrics.counter(name, **labels).inc(n)
