"""ISSGD — the paper's distributed importance-sampling SGD (section 4).

One SPMD train step fuses the paper's three actors (DESIGN.md §2):

  workers   → a scoring pass over a round-robin slice of the dataset,
              evaluated with *stale* parameters θ_stale (refreshed every
              `refresh_every` steps — the paper's parameter-push period);
  database  → the WeightStore (sharded ω̃ + scored_at arrays);
  master    → proposal read (B.1 staleness filter + B.3 smoothing),
              multinomial sampling, IS-scaled unbiased loss (§4.1),
              gradient step.

Modes:
  relaxed   the paper's practical algorithm (stale weights, fire-and-forget)
  exact     the §4.1 oracle: rescore the *whole* dataset with fresh params
            every step (synchronization barriers of fig. 1 enforced)
  uniform   plain SGD baseline (scoring still runs for monitoring parity,
            like the paper's background worker for the SGD runs)
  fused     beyond-paper (the paper's §6 "combine with ASGD" suggestion):
            no separate scoring pass — the training forward itself emits
            the per-example scores for the minibatch it trains on, and the
            store is refreshed for those examples at ~zero extra cost.
            Coverage of unsampled examples comes from an optional probe
            step (make_score_step) the launcher runs every K steps.

Distribution (core/distributed.py wires this under shard_map):

The step body is written against `axes`, a tuple of mesh axis names over
which the dataset, the WeightStore, and the scoring fan-out are sharded.
`cfg.score_shards` (W) fixes a *logical* decomposition of the table into W
contiguous scoring shards, independent of the device count: each device
owns W/num_devices of them, scores a round-robin slice of each per step,
and sampling is hierarchical (block totals → within-block resolve; see
core/sampler.py).  Because W — not the mesh — defines the decomposition,
running with axes=() on one device is bitwise the same algorithm, which is
what the sharded-equivalence tests pin down.  The full f32[N] table is
never gathered: the master only ever touches B sampled rows (one-owner
masked psums) and W block totals.

The step body is factored into two reusable halves — `make_scoring_pass`
(the workers) and `make_master_pass` (the master) — so that the fused step
built here (their lag-0 composition over one store) and the async pipeline
of core/async_pipeline.py (the two halves dispatched concurrently through
a double-buffered store) are literally the same code.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import variance
from repro.core.collectives import axis_info, gather_rows, psum
from repro.core.importance import (ISConfig, effective_sample_size,
                                   is_loss_scale)
from repro.core.sampler import two_stage_sample
from repro.core.weight_store import (WeightStore, init_store, read_proposal,
                                     write_scores, write_scores_global)
from repro.data.pipeline import gather_batch
from repro.optim import Optimizer, global_norm


@dataclasses.dataclass(frozen=True)
class ISSGDConfig:
    """Step-shape knobs: batch sizes, refresh cadence, mode, smoothing,
    and the mesh-free logical scoring decomposition W."""
    batch_size: int = 64
    score_batch_size: int = 256        # examples rescored per step ("workers")
    refresh_every: int = 8             # θ_stale refresh period (param pushes)
    mode: str = "relaxed"              # relaxed | exact | uniform | fused
    is_cfg: ISConfig = ISConfig()
    grad_clip: float = 0.0
    score_shards: int = 1              # W: logical scoring shards (mesh-free)
    # --- billion-example sampling structures (ISSUE 10) ------------------
    # stage-1 source: "dense" recomputes block masses in-draw; "tree"
    # routes them through core/mass_index.py (bitwise-equal draws)
    index: str = "dense"               # dense | tree
    # storage dtype of the weight table: f32 | bf16 | int8 (+ per-chunk
    # scale); non-f32 reads dequantize, so the sampled distribution IS
    # the quantized proposal
    table_dtype: str = "f32"
    # TTL decay of stale scores toward the uniform floor, in steps
    # (weight_store.decay_proposal); 0 disables (HLO-identical off path)
    score_ttl: int = 0
    # chunk granularity for the index / int8 scales / TTL decay; 0 →
    # one chunk per logical scoring shard (n_w)
    index_chunk_size: int = 0


class TrainState(NamedTuple):
    """Everything a step carries: master + worker params, the store, the
    step counter, and the PRNG key stream."""
    params: Any
    opt_state: Any
    stale_params: Any                  # the workers' parameter copy
    store: WeightStore
    step: jax.Array
    rng: jax.Array


class StepMetrics(NamedTuple):
    """Per-step monitors (paper fig. 4 traces + sampling diagnostics)."""
    loss: jax.Array
    grad_norm: jax.Array
    # √Tr(Σ(q)) monitors over the freshly scored slice (paper fig. 4)
    trace_ideal: jax.Array
    trace_stale: jax.Array
    trace_unif: jax.Array
    ess_frac: jax.Array                # ESS of proposal / N
    mean_weight: jax.Array
    sample_indices: jax.Array          # which examples were trained on


def init_train_state(params, optimizer: Optimizer, num_examples: int,
                     seed: int = 0, table_dtype: str = "f32",
                     index_chunk_size: int = 0) -> TrainState:
    """Fresh TrainState: stale params start as a copy of θ₀ in buffers
    of their own (the trainer donates the params, and keeps θ_stale), the
    store unscored (uniform proposal until the first sweep).
    ``table_dtype``/``index_chunk_size`` select the store representation
    (see ``weight_store.init_store``)."""
    return TrainState(
        params=params,
        opt_state=optimizer.init(params),
        stale_params=jax.tree.map(jnp.copy, params),
        store=init_store(num_examples, table_dtype=table_dtype,
                         chunk_size=index_chunk_size),
        step=jnp.zeros((), jnp.int32),
        rng=jax.random.key(seed),
    )


def read_sampling_proposal(store: WeightStore, step, cfg: ISSGDConfig,
                           n_w: int) -> jax.Array:
    """The proposal the master actually draws from: ``read_proposal``
    (B.1 filter + B.3 smoothing + EMPTY mask, dequantizing non-f32
    tables) followed by the optional per-chunk TTL decay toward the
    uniform floor.  ``score_ttl=0`` takes the identity code path —
    byte-identical HLO to a build that never heard of decay (gated in
    tests/test_mass_index.py).  Shard-local: the streamed sample_step
    calls the same function so host and device replay the same draw."""
    proposal = read_proposal(store, step, cfg.is_cfg)
    if cfg.score_ttl > 0:
        from repro.core.weight_store import decay_proposal
        cs = cfg.index_chunk_size or n_w
        proposal = decay_proposal(proposal, store.scored_at, step,
                                  cfg.score_ttl, cfg.is_cfg, cs)
    return proposal


def stage1_block_sums(proposal: jax.Array, w_loc: int,
                      cfg: ISSGDConfig) -> jax.Array | None:
    """Stage-1 masses for ``two_stage_sample``: None in dense mode (the
    draw recomputes them — the default, HLO-gated path); in tree mode
    the per-block masses come from the mass index's canonical reduction,
    which is bitwise the in-draw reduction, so tree draws ≡ dense
    draws (the ISSUE 10 acceptance pin)."""
    if cfg.index == "dense":
        return None
    if cfg.index != "tree":
        raise ValueError(f"unknown index {cfg.index!r}")
    from repro.core.mass_index import block_masses
    return block_masses(proposal, w_loc)


def _resolve_shards(cfg: ISSGDConfig, num_examples: int, sb: int,
                    n_local: int, n_dev: int) -> tuple[int, int, int]:
    """(w_loc, n_w, sb_w): per-device logical shards, shard length, and
    per-shard scoring slice — validated against the static shapes."""
    w = max(cfg.score_shards, 1)
    if w % n_dev:
        raise ValueError(f"score_shards={w} must be divisible by the "
                         f"device count {n_dev}")
    if num_examples % w:
        raise ValueError(f"num_examples={num_examples} not divisible by "
                         f"score_shards={w}")
    if sb % w:
        raise ValueError(f"score_batch_size={sb} not divisible by "
                         f"score_shards={w}")
    if n_local * n_dev != num_examples:
        raise ValueError(f"store shard of {n_local} rows × {n_dev} devices "
                         f"≠ num_examples={num_examples}")
    w_loc = w // n_dev
    return w_loc, n_local // w_loc, sb // w


def _spec_touches(spec, axes: tuple[str, ...]) -> bool:
    """Whether a PartitionSpec shards any dim over one of `axes`."""
    names: set = set()
    for entry in tuple(spec):
        if isinstance(entry, (tuple, list)):
            names.update(entry)
        elif entry is not None:
            names.add(entry)
    return bool(names & set(axes))


def _grad_global_norm(grads, model_axes: tuple[str, ...],
                      param_pspecs) -> jax.Array:
    """The true global grad norm when params (hence grads) may be
    model-axis-sharded: leaves sharded over `model_axes` contribute their
    local partial square-sum, replicated leaves (computed redundantly on
    every model device) are pre-divided by the axis size, and the total is
    psum-reduced before the sqrt.  With model_axes=() this is arithmetic-
    identical to `optim.global_norm`."""
    from jax.sharding import PartitionSpec as P
    from repro.core.collectives import axis_info
    if not model_axes:
        return global_norm(grads)
    if param_pspecs is None:
        raise ValueError("model_axes set but no param_pspecs: the grad "
                         "norm cannot tell sharded from replicated leaves")
    _, n_model = axis_info(model_axes)

    def leaf(g, spec):
        s = jnp.sum(jnp.square(g.astype(jnp.float32)))
        return s if _spec_touches(spec, model_axes) else s / n_model

    sq = sum(jax.tree.leaves(jax.tree.map(
        leaf, grads, param_pspecs, is_leaf=lambda x: isinstance(x, P))))
    return jnp.sqrt(psum(sq, model_axes))


def _score_slice(step: jax.Array, w_loc: int, n_w: int, sb_w: int) -> jax.Array:
    """Local indices of this step's round-robin scoring slice: each of the
    device's `w_loc` logical shards contributes `sb_w` examples."""
    base = (step * sb_w + jnp.arange(sb_w)) % n_w            # (sb_w,)
    return (jnp.arange(w_loc)[:, None] * n_w + base[None, :]).reshape(-1)


def scoring_layout(cfg: ISSGDConfig, num_examples: int,
                   n_dev: int = 1) -> tuple[int, int, int]:
    """Static (w_loc, n_w, sb_w) scoring layout for an n_dev-device run —
    the host-side streaming scheduler (data/streaming.py) uses this plus
    `_score_slice`'s formula to pre-fetch exactly the rows each device's
    scoring pass will touch, without tracing anything."""
    if num_examples % n_dev:
        raise ValueError(f"num_examples={num_examples} not divisible by "
                         f"{n_dev} devices")
    sb = num_examples if cfg.mode == "exact" else cfg.score_batch_size
    return _resolve_shards(cfg, num_examples, sb, num_examples // n_dev,
                           n_dev)


def make_scoring_pass(
    scorer: Callable,               # (params, batch) -> (B,) ω̃ (grad norms)
    cfg: ISSGDConfig,
    num_examples: int,
    constrain_batch: Optional[Callable] = None,
    axes: tuple[str, ...] = (),
    streaming: bool = False,
) -> Callable:
    """The workers' scoring fan-out as a reusable body.

    Returns ``scoring_pass(score_params, store, step, data) ->
    (store, fresh_scores, stale_slice)``: rescore this step's round-robin
    slice with `score_params` and push into `store`; `stale_slice` is the
    proposal over the slice *before* the write (the eq. 9 monitor input).
    Shard-local end to end (zero collectives) — in the async pipeline this
    is the computation that overlaps the master update.

    With ``streaming=True`` the ``data`` argument is the *pre-gathered*
    scoring slice itself (this device's sb_w·w_loc rows, host-streamed by
    data/streaming.py) rather than the device-resident dataset: the body
    never sees an example-count-sized array, which is the no-full-dataset
    guarantee the streamed HLO gate pins.  The store write still lands at
    the same round-robin indices, so the two variants are bitwise equal.
    """
    is_cfg = cfg.is_cfg
    n = num_examples
    sb = n if cfg.mode == "exact" else cfg.score_batch_size
    if constrain_batch is None:
        constrain_batch = lambda b: b
    axes = tuple(axes)

    def scoring_pass(score_params, store: WeightStore, step, data):
        with jax.named_scope("issgd.score"):
            _, n_dev = axis_info(axes)
            n_local = store.weights.shape[0]
            w_loc, n_w, sb_w = _resolve_shards(cfg, n, sb, n_local, n_dev)
            score_idx = _score_slice(step, w_loc, n_w, sb_w)
            score_batch = constrain_batch(
                data if streaming else gather_batch(data, score_idx))
            fresh_scores = scorer(score_params, score_batch)
            # stale view of the slice BEFORE the write (for eq. 9 monitor)
            pre_proposal = read_proposal(store, step, is_cfg)
            stale_slice = pre_proposal[score_idx]
            # reserved serving-capacity rows (scored_at == EMPTY) stay
            # inert: their scores are forced to 0 and their EMPTY stamp
            # survives the write, so un-ingested rows never gain proposal
            # mass.  With no reserved rows in the slice this is the
            # identity dataflow.
            from repro.core.weight_store import EMPTY
            live = store.scored_at[score_idx] > EMPTY
            fresh_scores = jnp.where(live, fresh_scores,
                                     jnp.zeros_like(fresh_scores))
            stamp = jnp.where(live,
                              jnp.broadcast_to(jnp.asarray(step, jnp.int32),
                                               live.shape),
                              jnp.asarray(EMPTY, jnp.int32))
            new_store = write_scores(store, score_idx, fresh_scores, stamp)
            return new_store, fresh_scores, stale_slice

    return scoring_pass


def make_master_pass(
    per_example_loss: Callable,     # (params, batch) -> (B,) losses
    optimizer: Optimizer,
    cfg: ISSGDConfig,
    num_examples: int,
    aux_loss: Optional[Callable] = None,   # (params, batch) -> scalar extra
    fused_score: Optional[Callable] = None,  # (params, batch) ->
    # (losses (B,), scores (B,)); required for mode="fused" — the training
    # forward emits its own importance scores (paper §6 direction)
    constrain_batch: Optional[Callable] = None,  # batch -> batch with
    # sharding constraints; jit-partitioned launchers (dryrun) pass one so
    # the gathered minibatch is batch-sharded over the data axes
    axes: tuple[str, ...] = (),     # mesh axes the example dim is sharded
    # over when the step runs inside shard_map; () = single-device
    model_axes: tuple[str, ...] = (),   # mesh axes the params are tensor-
    # sharded over; per_example_loss/fused_score must then be model-axis-
    # aware (they see local column shards and gather activations), and
    # `param_pspecs` (the tree from dist.sharding.param_pspecs) is
    # required so the grad norm can tell sharded from replicated leaves
    param_pspecs=None,
    monitors=None,                  # telemetry.MonitorSet: compile the
    # enabled proposal-health monitors into the step as ONE extra output
    # (a {name: scalar} dict).  None / empty set is the identity code
    # path — the program is HLO-identical to a monitor-free build, and
    # enabling monitors never changes the trajectory (both pinned in
    # tests/test_telemetry.py)
    streaming: bool = False,        # `data` is the pre-gathered replicated
    # minibatch (B rows) instead of the resident dataset; the sampled
    # indices are still drawn in-program from the store, and the host
    # driver (data/streaming.py) resolves them against its window — the
    # draw is deterministic given (store, step, rng), so both sides agree
    gated: bool = False,            # the controller's uniform↔IS gate: the
    # body takes one extra trailing device-bool `use_is` and selects the
    # sampling branch with jnp.where, so the host can flip modes without
    # a recompile.  gated=False is the identity code path (HLO-identical
    # to a build that never heard of the gate); a closed gate is bitwise
    # the uniform-mode program (both pinned in tests/test_controller.py).
    # Requires mode="relaxed" — the gate *is* the relaxed↔uniform switch.
) -> Callable:
    """The master's half of the step as a reusable body.

    Returns ``master_pass(params, opt_state, stale_params, store, step,
    k_sample, data, fresh_scores=None, stale_slice=None) -> (params,
    opt_state, stale_params, store, metrics)``: proposal read (B.1 + B.3)
    → two-stage sample → IS-scaled unbiased update (§4.1) → parameter
    push.  `store` is whatever proposal source the caller hands it: the
    freshly written store in the fused-step composition, or the lagged
    ``read_buf`` in the async pipeline.  `fresh_scores`/`stale_slice` feed
    the fig-4 trace monitors; when None (async — the monitors ride with
    the scoring step instead) the traces come back NaN.

    With a non-empty ``monitors`` set the return tuple grows one trailing
    element: the ``{name: scalar}`` proposal-health dict of
    telemetry/monitors.py, computed from the same proposal the sampler
    drew from (in async mode that is ``read_buf`` — the observed
    staleness monitor reads the lag right off its scored_at stamps).

    With ``gated=True`` the body takes one extra trailing ``use_is``
    device-bool (LAST in the signature, after the optional score args):
    both the uniform draw and the IS draw are computed from the same
    ``k_sample`` and selected elementwise, so a closed gate reproduces
    the uniform-mode trajectory bit-for-bit and an open gate the relaxed
    one — the controller (core/controller.py) owns the scalar.
    """
    is_cfg = cfg.is_cfg
    n = num_examples
    sb = n if cfg.mode == "exact" else cfg.score_batch_size
    if cfg.mode == "fused" and fused_score is None:
        raise ValueError("mode='fused' requires fused_score")
    if gated and cfg.mode != "relaxed":
        raise ValueError(f"gated=True switches relaxed↔uniform in-program; "
                         f"it requires mode='relaxed', got {cfg.mode!r}")
    if constrain_batch is None:
        constrain_batch = lambda b: b
    axes = tuple(axes)
    model_axes = tuple(model_axes)
    monitors = monitors or None

    def master_pass(params, opt_state, stale_params, store: WeightStore,
                    step, k_sample, data,
                    fresh_scores=None, stale_slice=None, use_is=None):
        if gated and use_is is None:
            raise ValueError("gated master_pass needs the use_is scalar")
        _, n_dev = axis_info(axes)
        n_local = store.weights.shape[0]
        w_loc, n_w, sb_w = _resolve_shards(cfg, n, sb, n_local, n_dev)

        # ---- 2. master reads the proposal (B.1 + B.3 + optional TTL
        # decay, dequantized for non-f32 tables), shard-local -----------------
        with jax.named_scope("issgd.proposal"):
            proposal = read_sampling_proposal(store, step, cfg, n_w)
            sum_w = psum(jnp.sum(proposal), axes)
            mean_weight = sum_w / n
        if monitors:
            from repro.telemetry.monitors import proposal_monitors
            # over the proposal actually sampled from, BEFORE this step's
            # writes (in async mode `store` is the lagged read_buf, so the
            # staleness monitor observes exactly L(t))
            with jax.named_scope("issgd.monitors"):
                mon = proposal_monitors(store, proposal, step, axes, n,
                                        monitors, sum_w=sum_w)

        # ---- 3. compose the minibatch (two-stage sample + one-owner gather) --
        with jax.named_scope("issgd.sample"):
            if cfg.mode == "uniform":
                idx = jax.random.randint(k_sample, (cfg.batch_size,), 0, n)
                scales = jnp.ones((cfg.batch_size,), jnp.float32)
            elif gated:
                # both draws from the same k_sample (pure functions of the
                # key), selected by the controller's gate: a closed gate IS
                # the uniform branch above, bit-for-bit
                idx_u = jax.random.randint(k_sample, (cfg.batch_size,), 0,
                                           n)
                idx_is = two_stage_sample(
                    k_sample, proposal, cfg.batch_size, axes=axes,
                    shards_per_device=w_loc,
                    block_sums=stage1_block_sums(proposal, w_loc, cfg))
                idx = jnp.where(use_is, idx_is, idx_u)
                sampled_w = gather_rows(proposal, idx, axes)
                scales = jnp.where(use_is,
                                   is_loss_scale(sampled_w, mean_weight),
                                   jnp.ones((cfg.batch_size,), jnp.float32))
            else:
                idx = two_stage_sample(k_sample, proposal, cfg.batch_size,
                                       axes=axes, shards_per_device=w_loc,
                                       block_sums=stage1_block_sums(
                                           proposal, w_loc, cfg))
                sampled_w = gather_rows(proposal, idx, axes)
                scales = is_loss_scale(sampled_w, mean_weight)
            batch = constrain_batch(data if streaming
                                    else gather_rows(data, idx, axes))

        # ---- 4. unbiased IS-scaled update (§4.1) ----------------------------
        # The gathered minibatch is replicated; every device computes the
        # identical master update (the paper's single master, SPMD-style) —
        # the parallelism win is the scoring fan-out above, which is the
        # dominant cost (score_batch_size ≫ batch_size).
        with jax.named_scope("issgd.update"):
            def loss_fn(params):
                if cfg.mode == "fused":
                    losses, scores = fused_score(params, batch)
                    scores = jax.lax.stop_gradient(scores)
                else:
                    losses, scores = per_example_loss(params, batch), None
                loss = jnp.mean(losses * scales)
                if aux_loss is not None:
                    loss = loss + aux_loss(params, batch)
                return loss, scores

            (loss, batch_scores), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            if cfg.mode == "fused":
                # zero-cost refresh for the examples just trained on.
                # NOTE: the fig-4 monitors below are then computed on an
                # importance-SAMPLED slice rather than a uniform one, so
                # trace_stale is biased upward (high-weight examples are
                # over-represented); use the probe step's uniform slices
                # for faithful monitoring in fused mode.
                fresh_scores = batch_scores
                stale_slice = sampled_w  # proposal at idx, already gathered
                store = write_scores_global(store, idx, batch_scores, step,
                                            axes)
            gnorm = _grad_global_norm(grads, model_axes, param_pspecs)
            if cfg.grad_clip > 0:
                from repro.optim import clip_by_global_norm
                # clip against the model-axis-aware norm computed above
                grads, _ = clip_by_global_norm(grads, cfg.grad_clip,
                                               norm=gnorm)
            new_params, opt_state = optimizer.update(grads, opt_state,
                                                     params, step)

        # ---- 5. parameter push to the workers every K steps ------------------
        with jax.named_scope("issgd.push"):
            if cfg.mode == "exact":
                stale_params = new_params
            else:
                push = (step + 1) % cfg.refresh_every == 0
                stale_params = jax.tree.map(
                    lambda new, old: jnp.where(push, new, old),
                    new_params, stale_params)

        # ---- 6. paper fig. 4 monitors over the scored slice ------------------
        # ||g_TRUE||² upper bound (B.2): the minibatch gradient norm
        with jax.named_scope("issgd.monitors"):
            if cfg.mode == "fused":
                # replicated minibatch slice: no psum (it would double-count)
                traces = variance.trace_sigma_all(fresh_scores, stale_slice)
            elif fresh_scores is None:
                # async pipeline: the scoring step owns the trace monitors
                nan = jnp.full((), jnp.nan, jnp.float32)
                traces = variance.TraceSigma(ideal=nan, stale=nan, unif=nan)
            else:
                traces = variance.trace_sigma_all_dist(
                    fresh_scores, stale_slice, axes, n_total=sb)
            sum_w2 = psum(jnp.sum(jnp.square(proposal)), axes)
            ess = effective_sample_size(proposal, s1=sum_w, s2=sum_w2) / n

        metrics = StepMetrics(
            loss=loss, grad_norm=gnorm,
            trace_ideal=jnp.sqrt(jnp.maximum(traces.ideal, 0.0)),
            trace_stale=jnp.sqrt(jnp.maximum(traces.stale, 0.0)),
            trace_unif=jnp.sqrt(jnp.maximum(traces.unif, 0.0)),
            ess_frac=ess, mean_weight=mean_weight,
            sample_indices=idx,
        )
        if monitors:
            return new_params, opt_state, stale_params, store, metrics, mon
        return new_params, opt_state, stale_params, store, metrics

    return master_pass


def make_train_step(
    per_example_loss: Callable,     # (params, batch) -> (B,) losses
    scorer: Callable,               # (params, batch) -> (B,) ω̃ (grad norms)
    optimizer: Optimizer,
    cfg: ISSGDConfig,
    num_examples: int,
    aux_loss: Optional[Callable] = None,
    fused_score: Optional[Callable] = None,
    constrain_batch: Optional[Callable] = None,
    axes: tuple[str, ...] = (),
    model_axes: tuple[str, ...] = (),
    param_pspecs=None,
    monitors=None,
    gated: bool = False,
) -> Callable:
    """Build the fused ISSGD step: (state, dataset_arrays) -> (state, metrics).

    This is the synchronous composition ``master_pass ∘ scoring_pass`` over
    a single-buffer store: step t's master samples from a proposal that
    already includes step t's scoring writes (lag 0).  The async pipeline
    (core/async_pipeline.py) runs the same two bodies concurrently through
    a double-buffered store instead.

    With a non-empty ``monitors`` (telemetry.MonitorSet) the step returns
    ``(state, metrics, monitor_dict)`` instead — the proposal-health
    scalars ride the compiled step as extra outputs; without it the
    program is untouched (HLO-identical, tests/test_telemetry.py).

    With ``gated=True`` (mode="relaxed" only) the step signature becomes
    ``(state, data, use_is)``: the trailing device-bool selects the
    sampling branch in-program (see ``make_master_pass``), so the
    adaptive controller can flip uniform↔IS without recompiling.
    ``gated=False`` is the identity code path.
    """
    axes = tuple(axes)
    monitors = monitors or None
    scoring = (None if cfg.mode == "fused" else
               make_scoring_pass(scorer, cfg, num_examples,
                                 constrain_batch, axes))
    master = make_master_pass(per_example_loss, optimizer, cfg, num_examples,
                              aux_loss=aux_loss, fused_score=fused_score,
                              constrain_batch=constrain_batch, axes=axes,
                              model_axes=model_axes,
                              param_pspecs=param_pspecs, monitors=monitors,
                              gated=gated)

    def _train_step(state: TrainState, data: dict, use_is=None):
        rng, k_sample = jax.random.split(state.rng)
        step = state.step

        # ---- 1. scoring fan-out (the "workers"), shard-local -----------------
        if cfg.mode == "fused":
            store = state.store   # scores arrive from the train fwd instead
            fresh_scores = stale_slice = None
        else:
            score_params = (state.params if cfg.mode == "exact"
                            else state.stale_params)
            store, fresh_scores, stale_slice = scoring(
                score_params, state.store, step, data)

        # ---- 2-6. the master's half ------------------------------------------
        params, opt_state, stale_params, store, metrics, *mon = master(
            state.params, state.opt_state, state.stale_params, store, step,
            k_sample, data, fresh_scores, stale_slice, use_is)
        new_state = TrainState(params, opt_state, stale_params, store,
                               step + 1, rng)
        if monitors:
            return new_state, metrics, mon[0]
        return new_state, metrics

    if gated:
        def train_step(state: TrainState, data: dict, use_is):
            return _train_step(state, data, use_is)
    else:
        def train_step(state: TrainState, data: dict):
            return _train_step(state, data)

    train_step.with_monitors = bool(monitors)
    train_step.gated = bool(gated)
    return train_step


def make_score_step(
    scorer: Callable,
    cfg: ISSGDConfig,
    num_examples: int,
    constrain_batch: Optional[Callable] = None,
    axes: tuple[str, ...] = (),
) -> Callable:
    """Standalone probe/scoring step: rescore a round-robin slice with the
    workers' stale params and push to the store.  Used (a) by the fused
    mode to keep coverage of unsampled examples, and (b) to amortize
    scoring over K train steps (the B.1 staleness/throughput trade).
    Shard-local end to end: no collectives at all."""
    scoring = make_scoring_pass(scorer, cfg, num_examples,
                                constrain_batch, axes)

    def score_step(state: TrainState, data: dict) -> TrainState:
        store, _, _ = scoring(state.stale_params, state.store,
                              state.step, data)
        return state._replace(store=store)

    return score_step
