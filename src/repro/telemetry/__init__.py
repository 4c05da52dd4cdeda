"""Telemetry: structured observability for the train/serve system.

Three planes, one package (docs/ARCHITECTURE.md §8):

  * **in-step monitors** (`monitors.py`) — proposal-health scalars (ESS,
    entropy, max-weight fraction, EMPTY-row count, observed staleness)
    compiled into the master step as optional extra outputs; off is the
    identity code path (HLO-pinned), on never perturbs the trajectory;
  * **events** (`events.py`) — a schema-versioned JSONL sink for spans,
    counters, and per-step metrics records, host-side and buffered;
  * **spans** (`spans.py`) — named host phases: always a profiler
    annotation (on the device trace's clock), plus a wall-clock record
    when a sink is open; non-blocking, so instrumenting an async run
    never re-serializes the scoring/master overlap.

`Telemetry` is the facade the host drivers (`AsyncPipeline`,
`StreamedISSGD`, `ServeLoop`, `launch/train.py`) carry: sink + spans +
the periodic-counter cadence.  `Telemetry.null()` is the always-available
instance with nothing to write into; its spans still annotate, so
pipeline code has exactly one path whether telemetry is on or off.
"""
from __future__ import annotations

from typing import Callable, Optional

from repro.telemetry.events import SCHEMA_VERSION, EventSink, NullSink
from repro.telemetry.monitors import MONITOR_NAMES, MonitorSet
from repro.telemetry import spans as _spans

__all__ = ["EventSink", "NullSink", "MonitorSet", "MONITOR_NAMES",
           "SCHEMA_VERSION", "Telemetry"]


class Telemetry:
    """Facade handed to the host drivers: an event sink, spans, and the
    cadence at which periodic counters fire.  Every span is dispatch-only
    (the async overlap contract) and annotates the profiler's trace."""

    _null = None

    def __init__(self, sink, every: int = 10):
        if every < 1:
            raise ValueError(f"telemetry cadence must be >= 1, got {every}")
        self.sink = sink
        self.every = int(every)

    @classmethod
    def null(cls) -> "Telemetry":
        """The shared no-op instance (NullSink, nothing emitted)."""
        if cls._null is None:
            cls._null = cls(NullSink())
        return cls._null

    def __bool__(self) -> bool:
        return bool(self.sink)

    def timed(self, name: str, fn: Callable, *args,
              step: Optional[int] = None):
        """Run ``fn(*args)`` inside a span named `name` (see spans.timed)."""
        return _spans.timed(self.sink, name, fn, *args, step=step)

    def span(self, name: str, step: Optional[int] = None):
        """Context manager: span `name` around the block."""
        return _spans.span(self.sink, name, step=step)

    def step(self, step: int):
        """Context manager: the profiler's step annotation around one
        iteration of the trainer's loop."""
        return _spans.step_annotation(step)

    def counter(self, name: str, value, step: Optional[int] = None) -> None:
        """Emit one counter sample."""
        self.sink.counter(name, value, step=step)

    def emit(self, kind: str, step: Optional[int] = None, **fields) -> None:
        """Emit a raw record through the sink."""
        self.sink.emit(kind, step=step, **fields)

    def due(self, t: int) -> bool:
        """Whether periodic counters should fire at host step `t`."""
        return bool(self.sink) and t % self.every == 0
