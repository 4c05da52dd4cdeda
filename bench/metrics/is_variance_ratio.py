"""The variance of the IS gradient estimator under the proposal the step
sampled from, over uniform sampling's: mean(trace_stale²) /
mean(trace_unif²) over the cell's `variance_steps`, a fixed range of steps
counted from init.  Read from the step's own monitors (√TrΣ over each
step's freshly scored slice, computed on the device)."""


def read(ctx):
    if not ctx.variance:
        return None
    stale = sum(s * s for s, _ in ctx.variance)
    unif = sum(u * u for _, u in ctx.variance)
    return stale / unif
