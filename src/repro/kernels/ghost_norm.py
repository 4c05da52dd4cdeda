"""Pallas TPU kernel for the ghost-norm extension (beyond-paper, see DESIGN §4).

For a linear layer shared across S sequence positions, the per-example
gradient is G_n = X_nᵀ D_n with X_n ∈ R^{S×din}, D_n ∈ R^{S×dout}, and

    ||G_n||²_F = <X_n X_nᵀ, D_n D_nᵀ>_F = Σ_{s,t} (x_s·x_t)(d_s·d_t).

The kernel never materializes G_n nor the full S×S Gram matrices in HBM:
it tiles the (s,t) plane into (bs×bs) blocks, accumulates the two block
Grams over feature-block grid steps on the MXU, multiplies them
elementwise, and reduces to one scalar per example.

Grid: (B, S_blocks_i, S_blocks_j, feature_blocks) — feature innermost so
the Gram accumulators stay resident in VMEM scratch.

`symmetric=True` exploits <A,B> symmetry in (i,j): blocks with j<i are
skipped (their MXU work is gated out) and off-diagonal contributions are
counted twice.  This halves the matmul FLOPs; it is the optimized variant
recorded in EXPERIMENTS.md §Perf (baseline = symmetric=False).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(xi_ref, xj_ref, di_ref, dj_ref, out_ref, a_acc, b_acc, *,
            nkx: int, nkd: int, symmetric: bool):
    i = pl.program_id(1)
    j = pl.program_id(2)
    k = pl.program_id(3)
    nk = max(nkx, nkd)

    @pl.when(jnp.logical_and(jnp.logical_and(i == 0, j == 0), k == 0))
    def _zero_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    live = jnp.logical_or(jnp.logical_not(symmetric), j >= i)

    @pl.when(jnp.logical_and(live, k == 0))
    def _init():
        a_acc[...] = jnp.zeros_like(a_acc)
        b_acc[...] = jnp.zeros_like(b_acc)

    @pl.when(jnp.logical_and(live, k < nkx))
    def _accum_a():
        xi = xi_ref[0].astype(jnp.float32)
        xj = xj_ref[0].astype(jnp.float32)
        a_acc[...] += jax.lax.dot_general(
            xi, xj, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(live, k < nkd))
    def _accum_b():
        di = di_ref[0].astype(jnp.float32)
        dj = dj_ref[0].astype(jnp.float32)
        b_acc[...] += jax.lax.dot_general(
            di, dj, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(live, k == nk - 1))
    def _emit():
        contrib = jnp.sum(a_acc[...] * b_acc[...])
        if symmetric:
            contrib = jnp.where(j > i, 2.0 * contrib, contrib)
        out_ref[...] += contrib


def ghost_norm(
    x: jax.Array,
    d: jax.Array,
    *,
    block_s: int = 128,
    block_k: int = 512,
    symmetric: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """||X_nᵀD_n||²_F per example. x:(B,S,din) d:(B,S,dout) → f32[B]."""
    assert x.ndim == 3 and d.ndim == 3
    assert x.shape[:2] == d.shape[:2]
    b, s, din = x.shape
    dout = d.shape[2]

    bs = min(block_s, s)
    pad_s = (-s) % bs
    nkx = pl.cdiv(din, block_k)
    nkd = pl.cdiv(dout, block_k)
    nk = max(nkx, nkd)

    # zero padding is exact: padded rows contribute zero inner products
    xp = jnp.pad(x, ((0, 0), (0, pad_s), (0, (-din) % block_k)))
    dp = jnp.pad(d, ((0, 0), (0, pad_s), (0, (-dout) % block_k)))
    ns = (s + pad_s) // bs

    grid = (b, ns, ns, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, nkx=nkx, nkd=nkd, symmetric=symmetric),
        grid=grid,
        name="ghost_norm",
        in_specs=[
            pl.BlockSpec((1, bs, block_k),
                         lambda bi, i, j, k: (bi, i, jnp.minimum(k, nkx - 1))),
            pl.BlockSpec((1, bs, block_k),
                         lambda bi, i, j, k: (bi, j, jnp.minimum(k, nkx - 1))),
            pl.BlockSpec((1, bs, block_k),
                         lambda bi, i, j, k: (bi, i, jnp.minimum(k, nkd - 1))),
            pl.BlockSpec((1, bs, block_k),
                         lambda bi, i, j, k: (bi, j, jnp.minimum(k, nkd - 1))),
        ],
        # one scalar per example; Mosaic refuses a rank-1 (1,) block
        out_specs=pl.BlockSpec((1, 1, 1), lambda bi, i, j, k: (bi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1, 1), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bs, bs), jnp.float32),
            pltpu.VMEM((bs, bs), jnp.float32),
        ],
        interpret=interpret,
    )(xp, xp, dp, dp)
    return out[:, 0, 0]
