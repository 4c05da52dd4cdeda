"""The trainer's own host spans in a profiler trace.

`repro.telemetry.spans` opens a `jax.profiler.TraceAnnotation` for every
span, so a trace's host plane holds one event per span, named exactly
after it (`train.dispatch`, `train.log_sync`, ...), on the clock of the
device's ops.  A trace of a program without these spans holds none, and
each reader then returns None.
"""
from __future__ import annotations

DISPATCH = "train.dispatch"
LOG_SYNC = "train.log_sync"


def intervals(ctx, name: str) -> list:
    """(start_ns, end_ns) of each host event named `name`, by start."""
    return sorted((start, start + dur) for _, n, start, dur
                  in ctx.events["host"] if n == name)


def us_per_step(ctx, name: str) -> float | None:
    """Summed length of the spans named `name` over the traced steps, in
    microseconds a step; None when the trace has no such span."""
    spans = intervals(ctx, name)
    if not spans:
        return None
    return sum(end - start for start, end in spans) / 1e3 / ctx.steps


def covered_ns(gaps: list, spans: list) -> float:
    """Nanoseconds of the gaps, each (start_ns, length_ns), that the union
    of the spans, each (start_ns, end_ns) and sorted by start, covers."""
    union = []
    for start, end in spans:
        if union and start <= union[-1][1]:
            union[-1][1] = max(union[-1][1], end)
        else:
            union.append([start, end])
    total, j = 0.0, 0
    for g_start, length in gaps:
        g_end = g_start + length
        while j < len(union) and union[j][1] <= g_start:
            j += 1
        k = j
        while k < len(union) and union[k][0] < g_end:
            total += min(union[k][1], g_end) - max(union[k][0], g_start)
            k += 1
    return total
