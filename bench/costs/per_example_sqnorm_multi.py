"""Least work of the multi-tap Prop.-1 kernel, from its tap shapes.

For B rows and taps of widths (din_t, dout_t), the kernel must read every
tap's x (B x din_t) and d (B x dout_t) once and write one f32 per row and
tap; it squares and sums each element once (2 FLOPs) and forms B
products per tap.  Padding and stacking are the implementation's, not the
work's, so they are not counted.
"""


def cost(batch: int, din: list, dout: list, itemsize: int = 4) -> dict:
    elems = batch * (sum(din) + sum(dout))
    return {"flops": 2.0 * elems + 2.0 * batch * len(din),
            "bytes": float(itemsize * elems + 4 * batch * len(din))}
