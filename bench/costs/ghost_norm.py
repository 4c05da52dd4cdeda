"""Least work of the Gram ghost norm, ‖XᵀD‖²_F = ⟨XXᵀ, DDᵀ⟩, from its tap
shapes, whatever implements it.

For a launch of `rows` independent (S × din, S × dout) pairs: the
symmetric Gram matrices XXᵀ and DDᵀ take S(S+1)/2 dot products of din and
dout terms each, S(S+1)(din+dout) FLOPs a row (the elementwise product
and its sum, 2·S², are under 0.1% of it and left out); the bytes are X and
D read once and one f32 written a row.  Padding, blocking and the
kernel's re-reads of X and D per block are the implementation's, not the
work's, so they are not counted.
"""


def cost(seq: int, launches: list, itemsize: int = 4) -> list:
    """FLOPs and bytes of each launch: `launches` is a list of (rows, din,
    dout)."""
    return [{"flops": float(rows * seq * (seq + 1) * (din + dout)),
             "bytes": float(itemsize * rows * seq * (din + dout) + 4 * rows)}
            for rows, din, dout in launches]
