"""The whole step's share of the chips' peak, from the trace: the
operations the traced steps require (the configuration's cost model in
bench/costs/), over the device timeline's span, from the first operation
of the traced steps to the end of the last, times chips times the bf16
peak.  The span counts the device's idle gaps between the steps' ops, so
the share bounds every kernel's and falls when the host holds the chip
back."""


def read(ctx):
    if ctx.span_s <= 0:
        return None
    costs = ctx.load_module("costs", ctx.config["name"])
    flops = costs.step_flops(ctx.config, ctx.trainer_flags) * ctx.steps
    peak = ctx.peak("bf16_flops_per_s")
    return 100.0 * flops / (ctx.span_s * ctx.chips * peak)
