"""Per-example gradient-norm scoring — the paper's ω̃_n = ||g(x_n)||₂.

Strategies (config `score_strategy`):

  loss        ω̃_n = L(x_n).  Cheapest (forward only); a curriculum-style
              heuristic, not the optimal proposal.  Baseline for ablations.
  logit_grad  ω̃_n = ||∂L_n/∂logits||₂ in closed form from the forward pass
              (softmax CE ⇒ p − onehot).  Forward-only.  The "cheap
              approximation" family the paper's §6 anticipates; the standard
              EL2N-style proxy of the full gradient norm.
  ghost       EXACT ||∇_θ L_n||₂ over every tapped linear (paper Prop. 1 via
              the per_example_sqnorm kernel for rank-1 layers, plus our
              ghost-norm extension for sequence-shared layers).  One forward
              + one backward, no per-example gradient materialization.
  ghost_rev   same quantity, computed with a manual reverse scan over the
              layer periods: stores only the P period-boundary activations
              plus ONE period's records/cotangents at a time (vs ghost's
              all-layer records) — the memory-scalable exact scorer.
  full        vmap-of-grad oracle.  O(B·|θ|) memory — tests only.

All strategies return ω̃ ≥ 0 of shape (B,) in float32.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops

STRATEGIES = ("loss", "logit_grad", "ghost", "ghost_rev", "full")


# --------------------------------------------------------------- ghost core
def _contribution(x: jax.Array, dt: jax.Array, batch: int,
                  with_bias: bool, scanned: bool) -> jax.Array:
    """Squared per-example grad-norm contribution of one tapped linear.

    `scanned` declares whether the arrays carry a leading period axis (the
    scan-stacked records); never guessed from shapes — a (P, B*S, d)
    token-flattened record is shape-ambiguous with (B, S, d) when P == B.

    Shapes handled:
      not scanned: (B, d) rank-1 (paper Prop. 1) | (B, S, d) ghost ext.
      scanned:     (P, B, S, d) | (P, B*S, d) token-flattened (MoE router)
    """
    if not scanned:
        if x.ndim == 2:
            return ops.per_example_sqnorm(x, dt, with_bias=with_bias)
        return ops.ghost_norm(x, dt)
    if x.ndim == 3:  # (P, B*S, d) token-flattened inside scan
        p = x.shape[0]
        s = x.shape[1] // batch
        x = x.reshape(p, batch, s, x.shape[-1])
        dt = dt.reshape(p, batch, s, dt.shape[-1])
    # (P, B, S, d): every (period, example) row is an independent layer copy
    p, b = x.shape[:2]
    r = ops.ghost_norm(x.reshape(p * b, *x.shape[2:]),
                       dt.reshape(p * b, *dt.shape[2:]))
    return jnp.sum(r.reshape(p, b), axis=0)


def ghost_sq_norms(
    loss_with_taps: Callable,
    tap_shapes: dict,
    batch: int,
    scanned_names: Optional[set] = None,
    with_bias: bool = False,
    model_axes: tuple[str, ...] = (),
    sharded_names: Optional[set] = None,
) -> tuple[jax.Array, jax.Array]:
    """Exact per-example squared grad-norms via the tap trick.

    loss_with_taps(taps) -> (per_example_losses (B,), records dict) where
    records[name] is the INPUT of the linear whose output tap is taps[name].
    `scanned_names`: which records carry a leading period axis (default:
    every name except "unembed" — the transformer convention).

    With ``model_axes`` set (model-parallel params inside shard_map), the
    taps of column-sharded layers carry this device's dY column slice, so
    their contributions are partial sums over the model axis; the names in
    ``sharded_names`` are summed as-is, contributions of replicated layers
    (computed redundantly on every model device) are pre-divided by the
    model-axis size, and the total is psum-reduced over ``model_axes``
    into the exact per-example grad-norm — replicated, so every model
    replica writes identical proposal weights into the store.

    Two fused-kernel fast paths ride on the record walk:
      * names ending in ``.qkv_scores`` are SCORE taps — their cotangent
        already IS the finished (B,)/(P,B) per-example score emitted by
        the flash-attention backward epilogue (see models/attention.attn),
        so it is summed in directly (no Prop.-1 kernel call);
      * consecutive runs of rank-1 (2-D, unscanned) taps with the same
        model-axis scaling class are batched through
        `ops.per_example_sqnorm_multi` — one grid sweep instead of one
        kernel launch per tapped linear.

    Returns (sq_norms (B,), per_example_losses (B,)).
    """
    from repro.core.collectives import axis_info, psum
    taps0 = {k: jnp.zeros(v.shape, v.dtype) for k, v in tap_shapes.items()}

    def f(taps):
        losses, records = loss_with_taps(taps)
        return jnp.sum(losses), (losses, records)

    _, pull, (losses, records) = jax.vjp(f, taps0, has_aux=True)
    (dtaps,) = pull(jnp.ones((), jnp.float32))

    _, n_model = axis_info(tuple(model_axes))
    sq = jnp.zeros((batch,), jnp.float32)
    group_x: list = []
    group_d: list = []
    group_div = False

    def _flush(sq):
        nonlocal group_x, group_d, group_div
        if not group_x:
            return sq
        if len(group_x) == 1:
            contrib = ops.per_example_sqnorm(group_x[0], group_d[0],
                                             with_bias=with_bias)
        else:
            contrib = ops.per_example_sqnorm_multi(
                tuple(group_x), tuple(group_d), with_bias=with_bias)
        if group_div:
            contrib = contrib / n_model  # replicated layers: counted once
        group_x, group_d, group_div = [], [], False
        return sq + contrib

    for name, x in records.items():
        if name not in dtaps:
            continue
        scanned = (name in scanned_names) if scanned_names is not None \
            else (name != "unembed")
        dt = dtaps[name]
        divide = bool(model_axes) and name not in (sharded_names or ())
        if name.endswith(".qkv_scores"):
            sq = _flush(sq)
            contrib = dt.astype(jnp.float32)
            if scanned:  # (P, B) stacked over scan periods
                contrib = jnp.sum(contrib, axis=0)
            if divide:
                contrib = contrib / n_model
            sq = sq + contrib
            continue
        if not scanned and x.ndim == 2:  # rank-1 tap: groupable
            if group_x and group_div != divide:
                sq = _flush(sq)
            group_x.append(x)
            group_d.append(dt)
            group_div = divide
            continue
        sq = _flush(sq)
        contrib = _contribution(x, dt, batch, with_bias, scanned)
        if divide:
            contrib = contrib / n_model
        sq = sq + contrib
    sq = _flush(sq)
    return psum(sq, tuple(model_axes)), losses


def ghost_tap_paths(cfg, seq: int) -> dict:
    """``{"gram": n, "direct": n}``: how many of the LM ghost scorer's
    sequence-shared taps `ghost_norm` sends to the Gram kernel and how many
    to the direct einsum at `seq` positions (`ops.uses_gram`, on this
    backend).  The taps are `tap_structure`'s, each with the width of its
    recorded input, walked as `ghost_sq_norms` walks them; rank-1 (Prop. 1)
    and score taps are in neither count.  Unsharded shapes."""
    from repro.models.transformer import (init_transformer, per_example_loss,
                                          tap_structure)
    taps = tap_structure(cfg, 1, seq)
    params = jax.eval_shape(lambda: init_transformer(jax.random.key(0), cfg))
    tokens = jax.ShapeDtypeStruct((1, seq + 1), jnp.int32)
    _, aux = jax.eval_shape(
        lambda p, t, tok: per_example_loss(p, cfg, {"tokens": tok}, taps=t,
                                           collect=True),
        params, taps, tokens)
    out = {"gram": 0, "direct": 0}
    for name, x in aux.records.items():
        if name not in taps or name.endswith(".qkv_scores"):
            continue
        if name == "unembed" and x.ndim == 2:
            continue
        # batch 1: the positions are the second-last axis, also for a
        # token-flattened (P, B*S, d) record
        gram = ops.uses_gram(x.shape[-2], x.shape[-1], taps[name].shape[-1])
        out["gram" if gram else "direct"] += 1
    return out


# ----------------------------------------------------------- LM strategies
def make_lm_scorer(cfg, strategy: str, ssm_mode: str = "ref",
                   model_axes: tuple[str, ...] = (),
                   seq_shard: bool = False,
                   attn_impl: str = "ref",
                   attn_scores: Optional[str] = None) -> Callable:
    """Scorer for transformer LMs.  Returns fn(params, batch) -> ω̃ (B,).

    With ``model_axes`` set the returned scorer expects model-axis-sharded
    params inside shard_map (head/ffn/channel shards, see
    models/transformer.forward).  Gradient-norm strategies compute
    per-example partial squared norms from the local dY slices of the
    sharded layers (`sharded_tap_names` classifies which taps are partial
    vs replicated) and psum them over the model axes, so the proposal ω̃
    is exact and replicated across model devices; forward-only strategies
    (loss / logit_grad) read the gathered replicated logits and need no
    reduction.  ``seq_shard`` threads sequence parallelism through the
    forward.  The `full` vmap-of-grad oracle is single-device-only.

    ``attn_impl`` selects the attention path ("ref" chunked-jnp, "flash"
    trainable Pallas kernel).  ``attn_scores`` ("fused"/"separate",
    ghost/ghost_rev with attn_impl="flash" only) swaps each attention
    layer's wq/wk/wv ghost Gram terms for the flash-backward score tap
    ||dQ||²+||dK||²+||dV||² at the attention interface — an EL2N-style
    proxy of those three terms at near-zero extra cost ("fused" reads it
    from the backward kernel epilogue; "separate" re-reads the gradients
    from HBM, the bitwise-pinned reference).  The resulting ω̃ is NO
    LONGER the exact full-parameter grad-norm; all other layers' terms
    stay exact.
    """
    from repro.models.transformer import (per_example_loss,
                                          sharded_tap_names,
                                          tap_structure,
                                          tap_structure_from_params)
    model_axes = tuple(model_axes)
    if attn_scores is not None:
        if attn_scores not in ("fused", "separate"):
            raise ValueError(f"attn_scores must be 'fused', 'separate' or "
                             f"None, got {attn_scores!r}")
        if strategy not in ("ghost", "ghost_rev"):
            raise ValueError(
                f"attn_scores={attn_scores!r} modifies the ghost-tap walk; "
                f"it has no effect on strategy {strategy!r} — use 'ghost' "
                f"or 'ghost_rev'")
        if attn_impl != "flash":
            raise ValueError(
                f"attn_scores={attn_scores!r} needs the trainable flash "
                f"kernel (attn_impl='flash'), got attn_impl={attn_impl!r}")
        if cfg.attention == "mla":
            raise ValueError("attn_scores is a GQA flash-kernel feature; "
                             "attention='mla' has no flash backward")

    if strategy == "loss":
        def score(params, batch):
            losses, _ = per_example_loss(params, cfg, batch,
                                         ssm_mode=ssm_mode,
                                         model_axes=model_axes,
                                         seq_shard=seq_shard)
            return jnp.maximum(losses.astype(jnp.float32), 0.0)
        return score

    if strategy == "logit_grad":
        from repro.models.transformer import forward, lm_head_metrics

        def score(params, batch):
            tokens = batch["tokens"]
            embeds = batch.get("embeds")
            n_front = embeds.shape[1] if embeds is not None else 0
            h, _ = forward(params, cfg, tokens[:, :-1], embeds=embeds,
                           ssm_mode=ssm_mode, return_hidden=True,
                           model_axes=model_axes, seq_shard=seq_shard)
            # chunked head: never materializes (B,S,V) logits at once
            _, grad_norm = lm_head_metrics(params, cfg, h[:, n_front:],
                                           tokens[:, 1:],
                                           model_axes=model_axes)
            return grad_norm
        return score

    if strategy == "ghost":
        def score(params, batch):
            b, s = batch["tokens"].shape
            if model_axes:
                tap_shapes = tap_structure_from_params(
                    params, cfg, b, s - 1, model_axes=model_axes,
                    ssm_mode=ssm_mode, attn_impl=attn_impl,
                    attn_scores=attn_scores)
                sharded = sharded_tap_names(params, cfg,
                                            attn_scores=attn_scores)
            else:
                tap_shapes = tap_structure(cfg, b, s - 1,
                                           attn_impl=attn_impl,
                                           attn_scores=attn_scores)
                sharded = None
            # the unembed tap lives outside the scan: add it explicitly
            def loss_with_taps(taps):
                losses, aux = per_example_loss(
                    params, cfg, batch, taps=taps, collect=True,
                    ssm_mode=ssm_mode, model_axes=model_axes,
                    seq_shard=seq_shard, attn_impl=attn_impl,
                    attn_scores=attn_scores)
                return losses, aux.records
            sq, _ = ghost_sq_norms(loss_with_taps, tap_shapes, b,
                                   with_bias=False, model_axes=model_axes,
                                   sharded_names=sharded)
            return jnp.sqrt(sq)
        return score

    if strategy == "ghost_rev":
        return _make_ghost_rev_scorer(cfg, ssm_mode, model_axes=model_axes,
                                      seq_shard=seq_shard,
                                      attn_impl=attn_impl,
                                      attn_scores=attn_scores)

    if strategy == "full":
        if model_axes:
            raise ValueError(
                "strategy 'full' (the vmap-of-grad test oracle) does not "
                "support model-axis-sharded params; use 'ghost' or "
                "'ghost_rev', which psum partial per-example norms over "
                "the model axes")

        def score(params, batch):
            def loss_one(p, tokens):
                losses, _ = per_example_loss(
                    p, cfg, {"tokens": tokens[None]}, ssm_mode=ssm_mode)
                return losses[0]
            grads = jax.vmap(jax.grad(loss_one), in_axes=(None, 0))(
                params, batch["tokens"])
            leaves = jax.tree.leaves(grads)
            sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)),
                             axis=tuple(range(1, g.ndim))) for g in leaves)
            return jnp.sqrt(sq)
        return score

    raise ValueError(f"unknown strategy {strategy!r}")


# ----------------------------------------------- memory-scalable ghost_rev
def _make_ghost_rev_scorer(cfg, ssm_mode: str,
                           model_axes: tuple[str, ...] = (),
                           seq_shard: bool = False,
                           attn_impl: str = "ref",
                           attn_scores: Optional[str] = None):
    """Exact ghost scoring via a manual reverse scan over layer periods.

    Memory: P boundary activations + ONE period of records/cotangents,
    instead of `ghost`'s records+cotangents for every layer at once —
    the remat structure of training, applied to per-example scoring.

    With ``model_axes`` the per-period contributions follow the same
    partial/replicated classification as `ghost` (sharded_tap_names) and
    the accumulated squared norms psum over the model axes at the end.
    """
    import jax.numpy as jnp
    from repro.core.collectives import axis_info, psum
    from repro.models.layers import Tape, rmsnorm, unembed, embed
    from repro.models.transformer import (_apply_layer, sharded_tap_names,
                                          tap_structure,
                                          tap_structure_from_params)

    specs = cfg.layer_specs()
    model_axes = tuple(model_axes)

    def score(params, batch):
        _, n_model = axis_info(model_axes)
        sharded_names = sharded_tap_names(params, cfg,
                                          attn_scores=attn_scores) \
            if model_axes else set()
        tokens = batch["tokens"]
        embeds = batch.get("embeds")
        n_front = embeds.shape[1] if embeds is not None else 0
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        b, s_text = inputs.shape

        h0 = embed(params["embed"], inputs, cfg, model_axes=model_axes)
        if embeds is not None:
            h0 = jnp.concatenate([embeds.astype(h0.dtype), h0], axis=1)
        s = h0.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

        def period_fwd(h, pp, ptaps, collect):
            tape = Tape(taps=ptaps, records={} if collect else None)
            for i, spec in enumerate(specs):
                h, _ = _apply_layer(pp[f"l{i}"], h, cfg, spec, positions,
                                    tape, f"l{i}", ssm_mode,
                                    model_axes=model_axes,
                                    seq_shard=seq_shard,
                                    attn_impl=attn_impl,
                                    attn_scores=attn_scores)
            return h, tape.records

        # ---- phase A: forward, storing only period-boundary activations
        def f_a(h, pp):
            h2, _ = period_fwd(h, pp, None, False)
            return h2, h  # ys = this period's INPUT boundary

        h_final, boundaries = jax.lax.scan(f_a, h0, params["layers"])

        # ---- head: per-example loss cotangent + unembed ghost term
        def head_losses(h):
            hn = rmsnorm(params["final_norm"], h, cfg.norm_eps)
            logits = unembed(params["embed"], hn, cfg,
                             model_axes=model_axes)[:, n_front:]
            lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            nll = -jnp.take_along_axis(lp, targets[..., None], -1)[..., 0]
            return jnp.sum(jnp.mean(nll, axis=-1)), (hn, lp)

        (_, (hn, lp)), head_vjp = jax.vjp(head_losses, h_final, has_aux=False)
        dh_final, = head_vjp((jnp.ones(()), (jnp.zeros_like(hn),
                                             jnp.zeros_like(lp))))
        # closed-form dL/dlogits for the unembed ghost contribution —
        # computed from the GATHERED full-vocab logits, so under model
        # parallelism it is replicated and counted once (÷ n_model)
        p_soft = jnp.exp(lp)
        onehot = jax.nn.one_hot(targets, cfg.vocab_size, dtype=jnp.float32)
        dlogits = (p_soft - onehot) / s_text
        sq = ops.ghost_norm(hn[:, n_front:], dlogits) / n_model

        # per-period tap template (strip the leading period axis + unembed)
        full_taps = (tap_structure_from_params(
                         params, cfg, b, s_text + n_front,
                         model_axes=model_axes, ssm_mode=ssm_mode,
                         attn_impl=attn_impl, attn_scores=attn_scores)
                     if model_axes else
                     tap_structure(cfg, b, s_text + n_front,
                                   attn_impl=attn_impl,
                                   attn_scores=attn_scores))
        period_taps = {
            k: jnp.zeros(v.shape[1:], v.dtype)
            for k, v in full_taps.items() if k != "unembed"
        }

        # ---- phase B: reverse scan, one period of cotangents at a time
        def f_b(carry, xs):
            dh, acc = carry
            pp, h_in = xs
            (h_out, records), vjp = jax.vjp(
                lambda h, t: period_fwd(h, pp, t, True), h_in, period_taps)
            zero_rec = jax.tree.map(jnp.zeros_like, records)
            dh_prev, dtaps = vjp((dh, zero_rec))
            contrib = jnp.zeros((b,), jnp.float32)
            for name, x in records.items():
                if name not in dtaps:
                    continue
                dt = dtaps[name]
                if name.endswith(".qkv_scores"):
                    # score tap: the cotangent IS the finished (B,) score
                    c = dt.astype(jnp.float32)
                elif x.ndim == 2 and x.shape[0] != b:  # token-flat (T,d)
                    x = x.reshape(b, -1, x.shape[-1])
                    dt = dt.reshape(b, -1, dt.shape[-1])
                    c = _contribution(x, dt, b, False, scanned=False)
                else:
                    c = _contribution(x, dt, b, False, scanned=False)
                if model_axes and name not in sharded_names:
                    c = c / n_model  # replicated layer: counted once
                contrib = contrib + c
            return (dh_prev, acc + contrib), None

        (_, sq_layers), _ = jax.lax.scan(
            f_b, (dh_final, sq), (params["layers"], boundaries),
            reverse=True)
        return jnp.sqrt(psum(sq_layers, model_axes))

    return score


# ---------------------------------------------------------- MLP strategies
def make_mlp_scorer(cfg, strategy: str,
                    model_axes: tuple[str, ...] = ()) -> Callable:
    """Scorer for the paper's MLP classifier (faithful Prop.-1 path).

    With ``model_axes`` the returned scorer expects model-axis-sharded
    params (column shards, inside shard_map).  Gradient-norm strategies
    compute per-example partial squared norms from the local shards and
    psum them over the model axes, so the proposal ω̃ is exact and
    replicated across model devices; forward-only strategies (loss /
    logit_grad) read the gathered replicated logits and need no reduction.
    """
    from repro.models.mlp import layer_is_sharded, mlp_forward, per_example_loss
    from repro.models.layers import Tape
    from repro.core.collectives import axis_info, psum
    model_axes = tuple(model_axes)
    n_layers = len(cfg.hidden) + 1

    if strategy == "loss":
        def score(params, batch):
            return jnp.maximum(
                per_example_loss(params, batch, cfg, model_axes=model_axes),
                0.0)
        return score

    if strategy == "logit_grad":
        def score(params, batch):
            logits = mlp_forward(params, batch["x"], cfg,
                                 model_axes=model_axes)
            p = jax.nn.softmax(logits.astype(jnp.float32), -1)
            py = jnp.take_along_axis(p, batch["y"][:, None], -1)[:, 0]
            sq = jnp.sum(jnp.square(p), -1) - 2.0 * py + 1.0
            return jnp.sqrt(sq)
        return score

    if strategy == "ghost":
        def score(params, batch):
            b = batch["x"].shape[0]
            sharded = {f"fc{i}" for i in range(n_layers)
                       if model_axes and layer_is_sharded(params, cfg, i)}
            # discover tap shapes with one abstract trace
            shapes: dict = {}
            def probe(x):
                t = Tape(tap_shapes=shapes)
                return per_example_loss(params, {"x": x, "y": batch["y"]},
                                        cfg, tape=t, model_axes=model_axes)
            jax.eval_shape(probe, batch["x"])

            def loss_with_taps(taps):
                t = Tape(taps=taps, records={})
                losses = per_example_loss(params, batch, cfg, tape=t,
                                          model_axes=model_axes)
                return losses, t.records
            sq, _ = ghost_sq_norms(loss_with_taps, shapes, b,
                                   scanned_names=set(), with_bias=True,
                                   model_axes=model_axes,
                                   sharded_names=sharded)
            return jnp.sqrt(sq)
        return score

    if strategy == "full":
        def score(params, batch):
            def loss_one(p, x, y):
                return per_example_loss(p, {"x": x[None], "y": y[None]}, cfg,
                                        model_axes=model_axes)[0]
            grads = jax.vmap(jax.grad(loss_one), in_axes=(None, 0, 0))(
                params, batch["x"], batch["y"])
            _, n_model = axis_info(model_axes)

            def leaf_sq(i, g):
                s = jnp.sum(jnp.square(g.astype(jnp.float32)),
                            axis=tuple(range(1, g.ndim)))
                if model_axes and not layer_is_sharded(params, cfg, i):
                    s = s / n_model  # replicated layer: counted once
                return s

            sq = sum(leaf_sq(i, g)
                     for i in range(n_layers)
                     for g in jax.tree.leaves(grads[f"fc{i}"]))
            return jnp.sqrt(psum(sq, model_axes))
        return score

    raise ValueError(f"unknown strategy {strategy!r}")
