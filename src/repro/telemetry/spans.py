"""Phase spans: a profiler annotation always, a JSONL record with a sink.

Every span opens a `jax.profiler.TraceAnnotation` named after its phase,
with the step as its `step` argument, so a profiler trace carries it on
the host plane (`/host:CPU`), on the same clock as the device's ops.
With no trace active an annotation costs about a microsecond.  When the
sink is truthy the span's host wall-clock (`perf_counter`) is also
written as one ``kind="span"`` record, which the adaptive controller and
`tools/metrics_report.py` read.  This module is the one place that builds
annotations; call sites go through `Telemetry.span`, `Telemetry.timed`
and `Telemetry.step`.

Span taxonomy (docs/ARCHITECTURE.md §8), the only names a call site uses:

  ``scoring.dispatch``  the async or streamed scoring pass's dispatch
  ``master.dispatch``   the async or streamed master step's dispatch
  ``sample.dispatch``   the streamed sampling step's dispatch
  ``store.publish``     the async store swap
  ``serve.tick``        one serving-loop tick between train steps
  ``stream.prefetch``, ``stream.fetch``, ``stream.gather``
                        the streamed data plane's host work
  ``train.dispatch``    `launch/train.py`: the fused step's dispatch
  ``train.callback``    `launch/train.py`: the caller's `on_step`
  ``train.log_sync``    `launch/train.py`: a logging step's `device_get`
  ``train.probe``       `launch/train.py`: fused mode's coverage probe

and one step annotation, ``train`` (`StepTraceAnnotation` with
``step_num``), around each iteration of the trainer's loop, which lines
host steps up with device steps in the profiler's step view.

Spans are non-blocking.  JAX dispatch is asynchronous, and the async
pipeline (core/async_pipeline.py) depends on the scoring and master
computations being in flight together; a timer that waited on each
phase's outputs would re-serialize exactly that overlap.  So a span
closes when the call returns, while the device work is still in flight:
a dispatch span much shorter than the phase's device time is the witness
that the next phase started concurrently (pinned in
tests/test_telemetry.py).  Device time per phase is read from a profiler
trace, where these spans share the device's clock.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Callable, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation


def _annotation(name: str, step: Optional[int] = None) -> TraceAnnotation:
    """The profiler annotation of span `name`, carrying `step`."""
    if step is None:
        return TraceAnnotation(name)
    return TraceAnnotation(name, step=step)


def step_annotation(step: int) -> StepTraceAnnotation:
    """The trainer loop's per-iteration step annotation."""
    return StepTraceAnnotation("train", step_num=step)


def span(sink, name: str, step: Optional[int] = None):
    """Context manager: annotate the block as span `name` and, when the
    sink is truthy, emit its host wall-clock as one ``kind="span"``
    record.  Purely host-side: whatever the block dispatched stays in
    flight.  With a falsy sink it is the bare annotation."""
    if not sink:
        return _annotation(name, step)
    return _recorded(sink, name, step)


@contextmanager
def _recorded(sink, name: str, step: Optional[int]):
    with _annotation(name, step):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            sink.span(name, time.perf_counter() - t0, step=step)


def timed(sink, name: str, fn: Callable, *args,
          step: Optional[int] = None):
    """Call ``fn(*args)`` inside span `name` and return its result; the
    span closes as soon as dispatch returns."""
    with _annotation(name, step):
        if not sink:
            return fn(*args)
        t0 = time.perf_counter()
        out = fn(*args)
        sink.span(name, time.perf_counter() - t0, step=step)
        return out
