"""Jitted public wrappers around the Pallas kernels.

Responsibilities:
  * interpret-mode selection: on the CPU backend the kernels run with
    interpret=True (Python emulation, used for validation); on TPU they
    lower to Mosaic; any other backend is an error.
  * padding to kernel-friendly shapes (done inside the kernel modules).
  * algorithm selection for the ghost norm: the blocked Gram kernel costs
    O(S²(din+dout)) while the direct per-example einsum costs
    O(S·din·dout); we pick per layer shape (mixed ghost-norm strategy).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode_attention
from repro.kernels.flash_attention_bwd import attn_score_sweep as _attn_score_sweep
from repro.kernels.ghost_norm import ghost_norm as _ghost_norm
from repro.kernels.per_example_sqnorm import per_example_sqnorm as _per_example_sqnorm
from repro.kernels.per_example_sqnorm import per_example_sqnorm_multi as _per_example_sqnorm_multi
from repro.kernels.selective_scan import selective_scan as _selective_scan


def _interpret() -> bool:
    """Pallas interpret mode on the CPU (validation), Mosaic on the TPU.
    Any other backend is refused rather than silently emulated."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels run on 'tpu' (Mosaic) or 'cpu' "
                       f"(interpret mode), not on backend {backend!r}")


# ------------------------------------------------------------------ Prop. 1
@functools.partial(jax.jit, static_argnames=("with_bias",))
def per_example_sqnorm(x, d, with_bias: bool = True):
    """Paper Prop. 1: (B,din),(B,dout) → f32[B] squared grad-norm."""
    return _per_example_sqnorm(x, d, with_bias=with_bias, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("with_bias",))
def per_example_sqnorm_multi(xs, ds, with_bias: bool = True):
    """Fused multi-tap Prop. 1: Σ_t ||xs[t]||²·||ds[t]||² in one sweep.

    Bitwise-identical to summing single-tap `per_example_sqnorm` launches
    over the taps in order (same block sizes, zero padding exact for sums
    of squares) — the kernel-launch batching the ghost scorer uses when it
    walks many rank-1 tapped linears."""
    return _per_example_sqnorm_multi(tuple(xs), tuple(ds),
                                     with_bias=with_bias,
                                     interpret=_interpret())


# --------------------------------------------------------------- ghost norm
def ghost_cost(s: int, din: int, dout: int) -> float:
    """FLOPs of the Gram path per example."""
    return float(s) * s * (din + dout)


def direct_cost(s: int, din: int, dout: int) -> float:
    """FLOPs of the materialized per-example gradient path."""
    return float(s) * din * dout


def uses_gram(s: int, din: int, dout: int) -> bool:
    """Whether `ghost_norm` takes the Gram kernel for a tap of `s`
    positions, `din` inputs and `dout` outputs: when its FLOPs are at most
    the direct path's (a tie goes to Gram).  The FLOP model targets the
    TPU; in interpret mode (CPU validation) the kernel is Python-emulated,
    so the choice is never Gram there."""
    return (not _interpret()
            and ghost_cost(s, din, dout) <= direct_cost(s, din, dout))


@functools.partial(jax.jit, static_argnames=("symmetric", "force"))
def ghost_norm(x, d, symmetric: bool = True, force: str | None = None):
    """||X_nᵀD_n||²_F per example, x:(B,S,din) d:(B,S,dout) → f32[B].

    Takes the path `uses_gram` picks unless `force` in {"gram", "direct"}
    pins it.
    """
    _, s, din = x.shape
    dout = d.shape[2]
    use_gram = uses_gram(s, din, dout)
    if force == "gram":
        use_gram = True
    elif force == "direct":
        use_gram = False
    if use_gram:
        return _ghost_norm(x, d, symmetric=symmetric, interpret=_interpret())
    return ref.ghost_norm_direct_ref(x, d)


# ----------------------------------------------------------- selective scan
@functools.partial(jax.jit, static_argnames=("chunk", "block_d"))
def selective_scan(u, delta, a, b, c, d, chunk: int = 128, block_d: int = 512):
    """Mamba-1 chunked selective scan, padding seq/channels as needed."""
    bsz, s, di = u.shape
    chunk = min(chunk, s)
    pad_s = (-s) % chunk
    bd = min(block_d, di)
    pad_d = (-di) % bd
    if pad_s or pad_d:
        u_p = jnp.pad(u, ((0, 0), (0, pad_s), (0, pad_d)))
        # pad delta with ones so exp(Δ·A) stays finite; padded channels are
        # discarded below anyway
        dl_p = jnp.pad(delta, ((0, 0), (0, pad_s), (0, pad_d)),
                       constant_values=1.0)
        a_p = jnp.pad(a, ((0, pad_d), (0, 0)))
        b_p = jnp.pad(b, ((0, 0), (0, pad_s), (0, 0)))
        c_p = jnp.pad(c, ((0, 0), (0, pad_s), (0, 0)))
        d_p = jnp.pad(d, ((0, pad_d),))
    else:
        u_p, dl_p, a_p, b_p, c_p, d_p = u, delta, a, b, c, d
    y = _selective_scan(u_p, dl_p, a_p, b_p, c_p, d_p,
                        chunk=chunk, block_d=bd, interpret=_interpret())
    return y[:, :s, :di]


# --------------------------------------------------------- decode attention
@functools.partial(jax.jit, static_argnames=("block_s",))
def decode_attention(q, k, v, lengths, block_s: int = 512):
    """Flash-decode GQA attention over a (possibly partial) KV cache."""
    return _decode_attention(q, k, v, lengths, block_s=block_s,
                             interpret=_interpret())


# ---------------------------------------------------------- flash attention
@functools.partial(jax.jit, static_argnames=("window", "block_q", "block_k"))
def flash_attention(q, k, v, window: int = 0, block_q: int = 256,
                    block_k: int = 256):
    """Causal GQA flash attention (forward; the prefill hot path)."""
    from repro.kernels.flash_attention import flash_attention as _fa
    return _fa(q, k, v, window=window, block_q=block_q, block_k=block_k,
               interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_q", "block_k"))
def attn_grad_sqnorm(dq, dk, dv, block_q: int = 256, block_k: int = 256):
    """(B,) per-example ||dQ||²+||dK||²+||dV||² via the separate-pass
    Pallas sweep (`attn_score_sweep`) — bitwise twin of the fused
    `with_scores` epilogue for f32 gradients."""
    return _attn_score_sweep(dq, dk, dv, block_q=block_q, block_k=block_k,
                             interpret=_interpret())


def make_flash_attention_trainable(window: int = 0, block_q: int = 256,
                                   block_k: int = 256,
                                   with_scores: bool = False):
    """Differentiable flash attention: forward + FlashAttention-2-style
    backward kernels wired through jax.custom_vjp.  Neither direction
    materializes the S×S attention matrix in HBM.

    With ``with_scores=True`` the returned op takes a fourth (B,) float32
    ``score_tap`` argument (ignored by the primal) whose cotangent is the
    fused per-example score ``||dQ_n||²+||dK_n||²+||dV_n||²`` emitted by
    the backward kernels' epilogues — pulling the vjp of a loss w.r.t. the
    tap yields the ghost score of the attention interface at near-zero
    extra cost (see core/scorer.py, strategy 'ghost' with attn_scores)."""
    from repro.kernels.flash_attention import flash_attention as _fa
    from repro.kernels.flash_attention_bwd import flash_attention_bwd as _fb

    if with_scores:
        @jax.custom_vjp
        def fa_s(q, k, v, score_tap):
            return _fa(q, k, v, window=window, block_q=block_q,
                       block_k=block_k, interpret=_interpret())

        def fwd_s(q, k, v, score_tap):
            o, lse = _fa(q, k, v, window=window, block_q=block_q,
                         block_k=block_k, interpret=_interpret(),
                         return_lse=True)
            return o, (q, k, v, o, lse)

        def bwd_s(res, do):
            q, k, v, o, lse = res
            dq, dk, dv, scores = _fb(q, k, v, o, lse, do, window=window,
                                     block_q=block_q, block_k=block_k,
                                     with_scores=True,
                                     interpret=_interpret())
            return dq, dk, dv, scores

        fa_s.defvjp(fwd_s, bwd_s)
        return fa_s

    @jax.custom_vjp
    def fa(q, k, v):
        return _fa(q, k, v, window=window, block_q=block_q, block_k=block_k,
                   interpret=_interpret())

    def fwd(q, k, v):
        o, lse = _fa(q, k, v, window=window, block_q=block_q,
                     block_k=block_k, interpret=_interpret(),
                     return_lse=True)
        return o, (q, k, v, o, lse)

    def bwd(res, do):
        q, k, v, o, lse = res
        return _fb(q, k, v, o, lse, do, window=window, block_q=block_q,
                   block_k=block_k, interpret=_interpret())

    fa.defvjp(fwd, bwd)
    return fa


def make_qkv_score_probe(block_q: int = 256, block_k: int = 256):
    """Identity op (q, k, v, score_tap) -> (q, k, v) whose backward runs
    the separate-pass score sweep on the gradient cotangents and returns
    it as the tap cotangent.  Composed before a plain trainable flash
    attention, this is the SEPARATE-pass twin of ``with_scores=True`` —
    same score, computed by re-reading dQ/dK/dV from HBM.  Exists so the
    fused path has a bitwise reference (and a benchmark baseline)."""

    @jax.custom_vjp
    def probe(q, k, v, score_tap):
        return q, k, v

    def fwd(q, k, v, score_tap):
        return (q, k, v), None

    def bwd(_, cts):
        dq, dk, dv = cts
        scores = _attn_score_sweep(dq, dk, dv, block_q=block_q,
                                   block_k=block_k, interpret=_interpret())
        return dq, dk, dv, scores

    probe.defvjp(fwd, bwd)
    return probe
