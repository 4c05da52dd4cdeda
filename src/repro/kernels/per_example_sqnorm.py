"""Pallas TPU kernel for paper Proposition 1 (rank-1 / fully-connected case).

Computes, per minibatch row n:
    out[n] = ||x[n,:]||² · ||d[n,:]||²  (+ ||d[n,:]||²  for the bias term)
without ever materializing per-example gradients — the paper's recipe for
making importance weights affordable (§3.3).

Tiling: grid (batch_blocks, feature_blocks).  The feature dimension is the
reduction; partial row sums live in VMEM scratch across the feature grid
steps (innermost), the product is emitted on the last feature block.
x and d may have different widths; the wrapper pads both to the common
feature-block grid with zeros (exact for sums of squares).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, d_ref, out_ref, xs_acc, ds_acc, *, nkx: int, nkd: int,
            with_bias: bool):
    k = pl.program_id(1)
    nk = max(nkx, nkd)

    @pl.when(k == 0)
    def _init():
        xs_acc[...] = jnp.zeros_like(xs_acc)
        ds_acc[...] = jnp.zeros_like(ds_acc)

    @pl.when(k < nkx)
    def _accum_x():
        xb = x_ref[...].astype(jnp.float32)
        xs_acc[...] += jnp.sum(xb * xb, axis=-1)

    @pl.when(k < nkd)
    def _accum_d():
        db = d_ref[...].astype(jnp.float32)
        ds_acc[...] += jnp.sum(db * db, axis=-1)

    @pl.when(k == nk - 1)
    def _emit():
        res = xs_acc[...] * ds_acc[...]
        if with_bias:
            res = res + ds_acc[...]
        out_ref[...] = res


def per_example_sqnorm(
    x: jax.Array,
    d: jax.Array,
    *,
    with_bias: bool = True,
    block_b: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """out[n] = ||x[n]||²·||d[n]||² (+||d[n]||²). x:(B,din) d:(B,dout) → f32[B]."""
    assert x.ndim == 2 and d.ndim == 2 and x.shape[0] == d.shape[0]
    b, din = x.shape
    dout = d.shape[1]

    bb = min(block_b, b)
    pad_b = (-b) % bb
    nkx = pl.cdiv(din, block_k)
    nkd = pl.cdiv(dout, block_k)
    nk = max(nkx, nkd)

    xp = jnp.pad(x, ((0, pad_b), (0, (-din) % block_k)))
    dp = jnp.pad(d, ((0, pad_b), (0, (-dout) % block_k)))

    grid = (pl.cdiv(b + pad_b, bb), nk)
    out = pl.pallas_call(
        functools.partial(_kernel, nkx=nkx, nkd=nkd, with_bias=with_bias),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, block_k), lambda i, k: (i, jnp.minimum(k, nkx - 1))),
            pl.BlockSpec((bb, block_k), lambda i, k: (i, jnp.minimum(k, nkd - 1))),
        ],
        out_specs=pl.BlockSpec((bb,), lambda i, k: (i,)),
        out_shape=jax.ShapeDtypeStruct((b + pad_b,), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bb,), jnp.float32),
            pltpu.VMEM((bb,), jnp.float32),
        ],
        interpret=interpret,
    )(xp, dp)
    return out[:b]


# ----------------------------------------------------------- fused multi-tap
def _multi_kernel(x_ref, d_ref, out_ref, xs_acc, ds_acc, *, nkx: int,
                  nkd: int, with_bias: bool):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        xs_acc[...] = jnp.zeros_like(xs_acc)
        ds_acc[...] = jnp.zeros_like(ds_acc)

    @pl.when(k < nkx)
    def _accum_x():
        xb = x_ref[0].astype(jnp.float32)
        xs_acc[...] += jnp.sum(xb * xb, axis=-1)

    @pl.when(k < nkd)
    def _accum_d():
        db = d_ref[0].astype(jnp.float32)
        ds_acc[...] += jnp.sum(db * db, axis=-1)

    # per-tap rows are STORED (same expression as the single-tap kernel),
    # not accumulated in-place: an in-kernel `out += xs·ds` lets the
    # compiler form an FMA (one rounding), which would break bitwise
    # parity with "sum of single-tap launches"; the wrapper chains the
    # tap adds outside, where no multiply is available to fuse.
    @pl.when(k == nk - 1)
    def _emit():
        res = xs_acc[...] * ds_acc[...]
        if with_bias:
            res = res + ds_acc[...]
        out_ref[...] = res.reshape(out_ref.shape)


def per_example_sqnorm_multi(
    xs: tuple,
    ds: tuple,
    *,
    with_bias: bool = True,
    block_b: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Sum of T rank-1 tap contributions in ONE grid sweep.

    ``out[n] = Σ_t ||xs[t][n]||²·||ds[t][n]||² (+||ds[t][n]||²)`` — the
    per-kernel-launch alternative to T separate `per_example_sqnorm` calls
    when the ghost scorer walks many tapped linears.  Taps are zero-padded
    to the widest tap's feature-block grid and stacked on a leading tap
    axis; the grid is (batch_blocks, taps, feature_blocks) — ONE sweep
    over all taps' operands instead of T kernel launches.  Zero padding
    is exact for sums of squares and the per-block reduction expressions
    match the single-tap kernel, so each tap's row of the (T, 1, B) kernel
    output is bitwise-equal to its single-tap launch; the wrapper then
    chains the tap adds in order, making the result BITWISE-identical to
    summing T single-tap launches (same block sizes) in the same order."""
    assert len(xs) == len(ds) and len(xs) >= 1
    b = xs[0].shape[0]
    assert all(x.ndim == 2 and x.shape[0] == b for x in xs)
    assert all(d.ndim == 2 and d.shape[0] == b for d in ds)
    n_taps = len(xs)

    bb = min(block_b, b)
    pad_b = (-b) % bb
    nkx = max(pl.cdiv(x.shape[1], block_k) for x in xs)
    nkd = max(pl.cdiv(d.shape[1], block_k) for d in ds)
    nk = max(nkx, nkd)
    kx, kd = nkx * block_k, nkd * block_k

    # upcast before stacking (exact) so heterogeneous tap dtypes coexist
    xstk = jnp.stack([
        jnp.pad(x.astype(jnp.float32), ((0, pad_b), (0, kx - x.shape[1])))
        for x in xs])
    dstk = jnp.stack([
        jnp.pad(d.astype(jnp.float32), ((0, pad_b), (0, kd - d.shape[1])))
        for d in ds])

    grid = (pl.cdiv(b + pad_b, bb), n_taps, nk)
    out = pl.pallas_call(
        functools.partial(_multi_kernel, nkx=nkx, nkd=nkd,
                          with_bias=with_bias),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bb, block_k),
                         lambda i, t, k: (t, i, jnp.minimum(k, nkx - 1))),
            pl.BlockSpec((1, bb, block_k),
                         lambda i, t, k: (t, i, jnp.minimum(k, nkd - 1))),
        ],
        # the unit middle dim keeps the block's last two dims (1, bb) legal
        # for Mosaic: a (1, bb) block over (T, B) is refused for T > 1
        out_specs=pl.BlockSpec((1, 1, bb), lambda i, t, k: (t, 0, i)),
        out_shape=jax.ShapeDtypeStruct((n_taps, 1, b + pad_b), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bb,), jnp.float32),
            pltpu.VMEM((bb,), jnp.float32),
        ],
        interpret=interpret,
    )(xstk, dstk)
    res = out[0, 0]
    for t in range(1, n_taps):
        res = res + out[t, 0]
    return res[:b]
