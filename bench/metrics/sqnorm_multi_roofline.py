"""Roofline share of the multi-tap Prop.-1 kernel
(`kernels/per_example_sqnorm.py`, Pallas name `per_example_sqnorm_multi`):
the least time its work takes on the chip, the larger of its FLOPs over
the bf16 peak and its bytes over the HBM bandwidth (bench/costs/
per_example_sqnorm_multi.py; the bytes bound it, by far), over the summed
device time of its events in the trace.  Its taps are the MLP's linears:
inputs of widths dims[:-1], output cotangents of widths dims[1:], one row
per scored example on each chip."""

KERNEL = "per_example_sqnorm_multi"


def read(ctx):
    seconds, launches = ctx.kernel_seconds(KERNEL)
    if launches == 0:
        return None
    cfg = ctx.config
    dims = [cfg["input_dim"], *cfg["hidden"], cfg["num_classes"]]
    rows = ctx.trainer_flags["score_batch"] // ctx.chips
    cost = ctx.load_module("costs", KERNEL).cost(rows, dims[:-1], dims[1:])
    least = max(cost["flops"] / ctx.peak("bf16_flops_per_s"),
                cost["bytes"] / ctx.peak("hbm_bytes_per_s"))
    return 100.0 * least * launches / seconds
