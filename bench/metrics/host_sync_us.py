"""Host time the trainer spends in its log sync, in microseconds a traced
step: the summed length of the `train.log_sync` spans (launch/train.py's
one `jax.device_get` of a logging step, which waits for that step's
device work and its transfer) over the traced steps.  None when the
program has no such span."""
from bench import program_spans


def read(ctx):
    return program_spans.us_per_step(ctx, program_spans.LOG_SYNC)
