"""Host time the trainer spends dispatching its step, in microseconds a
traced step: the summed length of the `train.dispatch` spans (launch/
train.py's `jax.jit` step call, from entry to return, allocation of the
outputs included) over the traced steps.  None when the program has no
such span."""
from bench import program_spans


def read(ctx):
    return program_spans.us_per_step(ctx, program_spans.DISPATCH)
