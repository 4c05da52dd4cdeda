"""Share of the first chip's idle time that falls inside the trainer's
dispatch of its step: of the gaps between the chip's op intervals
(bench/trace.py `idle_gaps`), the nanoseconds that a `train.dispatch`
span covers, over all their nanoseconds.  None when the program has no
such span or the chip was never idle between its ops."""
from bench import program_spans, trace


def read(ctx):
    spans = program_spans.intervals(ctx, program_spans.DISPATCH)
    if not spans or not ctx.device_ops:
        return None
    gaps = trace.idle_gaps(ctx.device_ops[0])
    idle = sum(length for _, length in gaps)
    if idle <= 0:
        return None
    return 100.0 * program_spans.covered_ns(gaps, spans) / idle
