"""Buffers the runtime allocates while the trainer dispatches its step, a
traced step: host events named `AllocateRawBuffer` (the PJRT runtime's
allocation of one output buffer) whose start falls inside a
`train.dispatch` span, over the traced steps.  An output that reuses a
donated input buffer allocates none.  None when the program has no such
span."""
import bisect

from bench import program_spans

ALLOCATE = "AllocateRawBuffer"


def read(ctx):
    spans = program_spans.intervals(ctx, program_spans.DISPATCH)
    if not spans:
        return None
    starts = [start for start, _ in spans]
    count = 0
    for _, name, start, _ in ctx.events["host"]:
        if name != ALLOCATE:
            continue
        k = bisect.bisect_right(starts, start) - 1
        if k >= 0 and start < spans[k][1]:
            count += 1
    return count / ctx.steps
