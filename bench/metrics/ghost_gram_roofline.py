"""Roofline share of the Gram ghost-norm kernel (`kernels/ghost_norm.py`,
Pallas name `ghost_norm`): the least time of its launches' work, each
launch the larger of its FLOPs over the bf16 peak and its bytes over the
HBM bandwidth (bench/costs/ghost_norm.py; the FLOPs bound it), over the
summed device time of its events in the trace.  The launches of a step are
the configuration's tapped linears (bench/costs/<config>.py `gram_taps`):
one row per (layer, scored sequence) for a layer's tap, one per scored
sequence for the head.  None when no `ghost_norm` ran."""

KERNEL = "ghost_norm"


def read(ctx):
    seconds, launches = ctx.kernel_seconds(KERNEL)
    if launches == 0:
        return None
    taps = ctx.load_module("costs", ctx.config["name"]).gram_taps(
        ctx.config, ctx.trainer_flags["score_batch"] // ctx.chips)
    costs = ctx.load_module("costs", KERNEL).cost(
        ctx.trainer_flags["seq"], taps)
    least = sum(max(c["flops"] / ctx.peak("bf16_flops_per_s"),
                    c["bytes"] / ctx.peak("hbm_bytes_per_s"))
                for c in costs)
    return 100.0 * least * (launches / len(taps)) / seconds
