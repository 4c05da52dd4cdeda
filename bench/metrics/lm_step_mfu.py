"""The whole LM step's share of the chips' peak, from the trace: the
formula of bench/metrics/step_mfu.py (the required FLOPs of the traced
steps by the configuration's cost model, here bench/costs/deepseek_7b.py,
over the device timeline's span times chips times the bf16 peak), read
through it, for the cells that train a language model."""


def read(ctx):
    return ctx.load_module("metrics", "step_mfu").read(ctx)
