"""ISSGD training launcher.

On real hardware this runs the full distributed ISSGD loop on the
production mesh; on CPU it runs reduced configs end-to-end (the same code
path, smaller mesh), e.g.:

  PYTHONPATH=src python -m repro.launch.train --arch glm4-9b --smoke \
      --steps 50 --batch 8 --seq 64 --strategy logit_grad
  PYTHONPATH=src python -m repro.launch.train --arch mlp_svhn --steps 300

Sharded execution (`core/distributed.py`): `--mesh N` runs the step under
shard_map on an N-device data mesh — dataset, WeightStore, and the scoring
fan-out sharded over the data axis, hierarchical two-stage sampling, no
full-table gathers.  With JAX_PLATFORMS=cpu, N host devices are forced via
XLA_FLAGS automatically, so the whole path works without a pod:

  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.train \
      --arch mlp_svhn --smoke --mesh 4

Streaming data plane (`data/streaming.py`): `--stream` keeps the dataset
host-resident in chunked form and feeds the devices a bounded,
proposal-aware window plus per-step host fetches — same-seed bitwise
identical to the resident run, so it composes with `--mesh` and
`--async-scoring` freely:

  PYTHONPATH=src python -m repro.launch.train --arch mlp_svhn --smoke \
      --mesh 4 --stream --window-chunks 4 --chunk-size 64

Model parallelism: `--model-parallel M` adds a trailing `model` axis to
the mesh and tensor-shards params + optimizer state through the
logical→mesh rules of `repro/dist/sharding.py` — composes with every mode
(relaxed/fused/async/streamed).  Per-example grad-norm scores are
psum-reduced over the model axis, so the proposal is exact and a dp×mp
run is same-seed equivalent to the dp-only run:

  PYTHONPATH=src python -m repro.launch.train --arch mlp_svhn --smoke \
      --mesh 2 --model-parallel 2

Transformer archs run the same shard_map data plane with a model-axis-
aware forward (head-sharded attention, ffn-sharded MLP/MoE, channel-
parallel mamba, vocab-parallel embed/unembed) and sequence-parallel
RMSNorm segments (disable with --no-sequence-parallel):

  PYTHONPATH=src python -m repro.launch.train --arch glm4-9b --smoke \
      --mesh 2 --model-parallel 2 --seq 32 --strategy ghost
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
import typing


def _force_host_devices(n: int) -> None:
    """Force n host devices when ``JAX_PLATFORMS`` names ``cpu``.  Must run
    before the jax backend initializes (importing jax alone does not
    initialize it).  Anywhere else the mesh takes the real devices, so a
    TPU that failed to start is an error, not a run on host devices."""
    if n <= 1:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return  # caller already chose a device count
    if "cpu" not in os.environ.get("JAX_PLATFORMS", "").split(","):
        return
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}").strip()


# importing jax does NOT initialize the backend; _force_host_devices (called
# first thing in main) can still adjust XLA_FLAGS before any device exists.
import jax
import jax.numpy as jnp

from repro.core.importance import ISConfig
from repro.core.issgd import ISSGDConfig, init_train_state, make_train_step
from repro.core.scorer import (ghost_tap_paths, make_lm_scorer,
                               make_mlp_scorer)
from repro.core.strategies import PROPOSALS, make_proposal
from repro.data import make_svhn_like, make_token_dataset
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import sgd


def _proposal_name(args) -> str:
    """The resolved proposal strategy: --proposal-strategy, falling back
    to the architecture-native --strategy when unset."""
    return args.proposal_strategy or args.strategy


def _profile_options() -> "jax.profiler.ProfileOptions":
    """`--profile-dir`'s capture options: host annotations and runtime
    events, no Python tracer (it would slow the very host loop the trace
    shows) and no HLO protos, as the benchmark traces its steps."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def _jit_donating_state(raw_step):
    """``jax.jit(raw_step)`` for a fused step ``raw_step(state, *rest)``
    that donates every buffer of ``state`` except ``stale_params``: XLA
    writes the new params, optimizer state, store, step and rng into the
    buffers they replace, so the runtime allocates fresh outputs only for
    θ_stale and the step's metrics.  θ_stale is the workers' snapshot
    between pushes, so it stays the caller's to keep (``main``'s
    ``on_step``).  The executable keeps ``raw_step``'s name."""
    def run(state, stale, *rest):
        return raw_step(state._replace(stale_params=stale), *rest)

    run.__name__ = run.__qualname__ = raw_step.__name__
    jitted = jax.jit(run, donate_argnums=0)

    def step(state, *rest):
        return jitted(state._replace(stale_params=None), state.stale_params,
                      *rest)
    return step


def _parse_field(kind, key: str, text: str):
    """`text` as a value of the config field `key`, declared `kind`."""
    if typing.get_origin(kind) is typing.Literal:
        if text not in typing.get_args(kind):
            raise ValueError(f"{key} must be one of "
                             f"{list(typing.get_args(kind))}, got {text!r}")
        return text
    if kind is bool:
        if text.lower() not in ("true", "false", "1", "0"):
            raise ValueError(f"{key} is a bool (true/false), got {text!r}")
        return text.lower() in ("true", "1")
    try:
        return kind(text)
    except ValueError:
        raise ValueError(f"{key} is {kind.__name__}, got {text!r}") from None


def resolve_config(args):
    """The configuration the flags name: ``--arch`` (its smoke variant
    with ``--smoke``), then each ``--config-override KEY=VALUE`` replacing
    one field of the transformer's ``ModelConfig`` by `dataclasses.replace`,
    the value parsed by the field's declared type.  The one place the
    trainer turns flags into a configuration.  Raises ValueError for an
    unknown key, a value of the wrong type, or overrides of the MLP."""
    overrides = args.config_override
    if args.arch == "mlp_svhn":
        if overrides:
            raise ValueError("--config-override applies to the transformer "
                             "archs' ModelConfig, not to --arch mlp_svhn")
        from repro.configs.mlp_svhn import CONFIG, smoke
        return smoke() if args.smoke else CONFIG
    from repro.configs import get_config, get_smoke_config
    from repro.models.config import ModelConfig
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    kinds = typing.get_type_hints(ModelConfig)     # field -> declared type
    changes = {}
    for item in overrides:
        key, sep, text = item.partition("=")
        if not sep:
            raise ValueError(f"--config-override takes KEY=VALUE, got "
                             f"{item!r}")
        if key not in kinds:
            raise ValueError(f"--config-override: ModelConfig has no field "
                             f"{key!r}")
        changes[key] = _parse_field(kinds[key], key, text)
    return dataclasses.replace(cfg, **changes) if changes else cfg


def build_mlp(args, model_axes=()):
    from repro.models.mlp import (init_mlp_classifier, mlp_specs,
                                  per_example_loss)
    cfg = resolve_config(args)
    train, _ = make_svhn_like(jax.random.key(args.seed), n=args.examples,
                              dim=cfg.input_dim)
    params = init_mlp_classifier(jax.random.key(args.seed + 1), cfg)
    pel = lambda p, b: per_example_loss(p, b, cfg, model_axes=model_axes)
    scorer = make_proposal(make_mlp_scorer, cfg, _proposal_name(args),
                           model_axes=model_axes)
    return params, train, pel, scorer, mlp_specs(cfg)


def build_lm(args, tel, model_axes=(), seq_shard=False):
    """The transformer's data, weights, loss and scorer.  With the ghost
    scorer, `tel` gets the counters ``scorer.gram_taps`` and
    ``scorer.direct_taps``: how many of its sequence-shared taps take the
    Gram kernel and how many the direct einsum (`ghost_tap_paths`)."""
    from repro.models.transformer import (init_transformer, per_example_loss,
                                          transformer_specs)
    cfg = resolve_config(args)
    train = make_token_dataset(jax.random.key(args.seed), n=args.examples,
                               seq=args.seq + 1, vocab=cfg.vocab_size)
    params = init_transformer(jax.random.key(args.seed + 1), cfg)
    pel = lambda p, b: per_example_loss(p, cfg, b, model_axes=model_axes,
                                        seq_shard=seq_shard)[0]
    scorer = make_proposal(make_lm_scorer, cfg, _proposal_name(args),
                           model_axes=model_axes, seq_shard=seq_shard)
    if _proposal_name(args) == "ghost":
        paths = ghost_tap_paths(cfg, args.seq)
        tel.counter("scorer.gram_taps", paths["gram"])
        tel.counter("scorer.direct_taps", paths["direct"])
    return params, train, pel, scorer, transformer_specs(cfg)


def validate_flags(ap, args, mp: int) -> None:
    """Fail fast, with the config field to fix, instead of inside shard_map.

    Rules (also in --help):
      * --model-parallel M with a transformer arch must divide num_heads
        and num_kv_heads (attention shards whole heads), d_inner for SSM
        stacks (the scan is channel-parallel), and MLA's num_heads; dims
        that merely fail elementwise divisibility (d_ff, vocab) fall back
        to replication with a warning instead.
      * --async-scoring needs --mode relaxed|uniform (fused/exact have no
        separate scoring pass to overlap).
      * --stream excludes --mode exact (the oracle rescores the resident
        dataset each step).
      * --strategy full is a single-device test oracle: no --model-parallel.
      * --config-override names fields of a transformer arch's ModelConfig.
    """
    if args.async_scoring and args.mode not in ("relaxed", "uniform"):
        ap.error("--async-scoring requires --mode relaxed|uniform (fused "
                 "scores ride the train forward and exact has no separate "
                 "pass to overlap)")
    if args.adaptive_is and args.mode != "relaxed":
        ap.error("--adaptive-is requires --mode relaxed (the controller "
                 "gates the relaxed sampler between uniform and IS; the "
                 "other modes have no gate to drive)")
    if args.stream and args.mode == "exact":
        ap.error("--stream does not support --mode exact (the oracle "
                 "rescores the full dataset each step; keep it resident)")
    if args.serve_loop:
        if not args.stream:
            ap.error("--serve-loop requires --stream (served traffic is "
                     "ingested as chunks of the host-resident store)")
        if args.arch == "mlp_svhn":
            ap.error("--serve-loop needs a token arch (the decode service "
                     "generates tokens); pick a transformer --arch")
        if args.mode not in ("relaxed", "fused"):
            ap.error("--serve-loop requires --mode relaxed|fused (uniform "
                     "sampling draws reserved-capacity rows before they "
                     "are ingested; exact is excluded by --stream)")
    if args.table_dtype == "int8":
        if args.stream or args.serve_loop:
            ap.error("--table-dtype int8 does not compose with --stream/"
                     "--serve-loop yet (the streamed serving ingest "
                     "assumes a float table); use f32 or bf16 there")
        n_local = args.examples // max(args.mesh, 1)
        cs = args.index_chunk_size
        if cs <= 0 or n_local % cs:
            ap.error(f"--table-dtype int8 needs --index-chunk-size > 0 "
                     f"dividing the per-shard rows ({n_local}); got {cs} "
                     f"(per-chunk scales may not straddle shards)")
    if args.index_chunk_size > 0 and \
            (args.examples // max(args.mesh, 1)) % args.index_chunk_size:
        ap.error(f"--index-chunk-size {args.index_chunk_size} must divide "
                 f"the per-shard rows "
                 f"({args.examples // max(args.mesh, 1)})")
    try:
        cfg = resolve_config(args)
    except ValueError as e:
        ap.error(str(e))
    if mp <= 1:
        return
    if _proposal_name(args) == "full":
        ap.error("--strategy full is the vmap-of-grad test oracle and does "
                 "not support --model-parallel; use ghost or ghost_rev")
    if args.arch == "mlp_svhn":
        return  # uneven hidden dims fall back to replication with a warning
    has_attn = any(s.mixer == "attn" for s in cfg.layer_specs())
    has_ssm = any(s.mixer == "mamba" for s in cfg.layer_specs())
    if has_attn and cfg.num_heads % mp:
        ap.error(f"--model-parallel {mp} does not divide num_heads="
                 f"{cfg.num_heads} of {cfg.name} (attention shards whole "
                 f"heads); pick a degree dividing num_heads or change the "
                 f"config's num_heads")
    if has_attn and cfg.attention == "gqa" and cfg.num_kv_heads % mp:
        ap.error(f"--model-parallel {mp} does not divide num_kv_heads="
                 f"{cfg.num_kv_heads} of {cfg.name} (K/V shard whole "
                 f"heads); pick a degree dividing num_kv_heads or change "
                 f"the config's num_kv_heads")
    if has_ssm and cfg.resolved_d_inner % mp:
        ap.error(f"--model-parallel {mp} does not divide d_inner="
                 f"{cfg.resolved_d_inner} of {cfg.name} (the selective "
                 f"scan is channel-parallel); pick a degree dividing "
                 f"d_inner (config field d_inner, default 2*d_model)")


_FLAG_RULES = """\
flag composition rules (validated up front; see also README and
docs/ARCHITECTURE.md):
  --mesh N            composes with everything; total devices = N * M
  --model-parallel M  composes with every mode and arch; for transformer
                      archs M must divide num_heads and num_kv_heads
                      (whole-head attention shards) and d_inner for SSM
                      stacks (channel-parallel scan); d_ff / vocab dims
                      that M does not divide fall back to replication
                      with a warning naming the parameter
  --async-scoring     requires --mode relaxed|uniform (fused scores ride
                      the train forward; exact has no pass to overlap)
  --stream            composes with --mesh/--model-parallel/--async-scoring
                      and --mode relaxed|uniform|fused; not --mode exact
                      (the oracle rescores the resident dataset)
  --sequence-parallel transformer + --model-parallel only; auto-skips
                      when M does not divide the sequence length
  --strategy full     single-device test oracle; not --model-parallel
  --adaptive-is       requires --mode relaxed (the controller flips the
                      relaxed sampler's uniform/IS gate from live
                      telemetry; composes with --mesh/--async-scoring/
                      --stream/--model-parallel)
  --index tree        composes with everything (draws are bitwise-equal
                      to the dense default; stage-1 masses come from
                      core/mass_index.py)
  --table-dtype       bf16 composes with everything; int8 needs
                      --index-chunk-size dividing the per-shard rows and
                      does not compose with --stream/--serve-loop
  --score-ttl K       composes with everything (per-chunk decay of stale
                      scores toward the uniform floor; 0 = off, the
                      HLO-identical default)
  --config-override   transformer archs only; KEY must be a ModelConfig
                      field, VALUE parses by its type (after --smoke)
"""


def main(argv=None, on_step=None):
    """Parse ``argv`` (default ``sys.argv[1:]``), train, and return the
    final ``(state, data)``; ``on_step(i, state, metrics)``, when given,
    sees every step's outputs (``chip_smoke.py`` drives the trainer so).

    The fused step donates its input state, all but ``stale_params``: the
    arrays of a step's ``state`` other than ``stale_params`` are valid
    until the next step is dispatched, so a caller that keeps one must
    copy it; ``stale_params`` arrays are the caller's to keep."""
    ap = argparse.ArgumentParser(
        epilog=_FLAG_RULES,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--arch", default="mlp_svhn")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--config-override", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="replace one field of the transformer arch's "
                    "ModelConfig (after --smoke), parsed by the field's "
                    "type; repeatable, e.g. --config-override "
                    "num_layers=2 --config-override dtype=float32")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--score-batch", type=int, default=256)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--examples", type=int, default=4096)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--mode", default="relaxed",
                    choices=["relaxed", "exact", "uniform", "fused"])
    ap.add_argument("--probe-every", type=int, default=8,
                    help="fused mode: run a coverage probe every K steps")
    ap.add_argument("--strategy", default="ghost",
                    choices=["loss", "logit_grad", "ghost", "ghost_rev", "full"])
    ap.add_argument("--proposal-strategy", default="",
                    choices=[""] + list(PROPOSALS),
                    help="proposal strategy from the zoo "
                    "(core/strategies.py): any --strategy name plus "
                    "upper_bound (K&F sqrt(2L) forward-only bound), "
                    "bandit_mixed (convex loss+logit_grad mixture), and "
                    "null (zero scores = uniform proposal); empty falls "
                    "back to --strategy")
    ap.add_argument("--adaptive-is", action="store_true",
                    help="run the adaptive IS controller "
                    "(core/controller.py): the sampler starts uniform and "
                    "switches to IS only when the observed trace ratio "
                    "says it pays; with --async-scoring the swap cadence "
                    "adapts to the dispatch-time ratio too (requires "
                    "--mode relaxed)")
    ap.add_argument("--adapt-every", type=int, default=25,
                    help="controller decision cadence in steps")
    ap.add_argument("--smoothing", type=float, default=1.0)
    ap.add_argument("--refresh-every", type=int, default=8)
    ap.add_argument("--staleness-threshold", type=int, default=0)
    ap.add_argument("--index", default="dense", choices=["dense", "tree"],
                    help="stage-1 mass source for the two-stage draw: "
                    "'tree' routes per-block masses through the chunk "
                    "mass index (core/mass_index.py) — bitwise-equal "
                    "draws, O(log N) write propagation at scale; 'dense' "
                    "recomputes them in-draw (default)")
    ap.add_argument("--table-dtype", default="f32",
                    choices=["f32", "bf16", "int8"],
                    help="weight-table storage: bf16 halves it, int8 (+ "
                    "per-chunk scale, needs --index-chunk-size) quarters "
                    "it; the proposal distortion is bounded and tested "
                    "(tests/test_sampler_stats.py)")
    ap.add_argument("--score-ttl", type=int, default=0,
                    help="decay scores toward the uniform floor with a "
                    "half-life of K steps per chunk age "
                    "(weight_store.decay_proposal); 0 = off "
                    "(HLO-identical default)")
    ap.add_argument("--index-chunk-size", type=int, default=0,
                    help="chunk granularity for the mass index / int8 "
                    "scales / TTL decay (0 = one chunk per logical "
                    "scoring shard)")
    ap.add_argument("--mesh", type=int, default=0,
                    help="run the sharded step on an N-device data mesh "
                    "(0 = single-device path); with JAX_PLATFORMS=cpu, N "
                    "host devices are forced automatically")
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="tensor-shard params + optimizer state over a "
                    "trailing M-device model axis (composes with --mesh/"
                    "--async-scoring/--stream and every arch; total "
                    "devices = mesh * M; transformer archs need M to "
                    "divide num_heads/num_kv_heads/d_inner — see the "
                    "rules below)")
    ap.add_argument("--sequence-parallel", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="transformer + --model-parallel: run the RMSNorm "
                    "segments sequence-parallel (on by default when M > 1 "
                    "and M divides the sequence length; "
                    "--no-sequence-parallel keeps them replicated; both "
                    "are exact)")
    ap.add_argument("--save-checkpoint", default="",
                    help="save the final TrainState here (sharded runs "
                    "use the gather-free per-shard npz layout)")
    ap.add_argument("--restore-checkpoint", default="",
                    help="restore a TrainState before training (old "
                    "replicated and new per-shard checkpoints both work)")
    ap.add_argument("--score-shards", type=int, default=0,
                    help="logical scoring shards W (0 = auto: mesh size, "
                    "or 1 single-device)")
    ap.add_argument("--async-scoring", action="store_true",
                    help="overlap the scoring fan-out with the master "
                    "update via the double-buffered WeightStore "
                    "(core/async_pipeline.py; mode relaxed|uniform)")
    ap.add_argument("--swap-every", type=int, default=1,
                    help="async: publish write_buf -> read_buf every K "
                    "steps (the proposal lag is L in [1, K])")
    ap.add_argument("--no-trace-monitors", action="store_true",
                    help="async: skip the fig-4 trace monitors in the "
                    "scoring step (keeps it strictly collective-free; "
                    "traces log as nan)")
    ap.add_argument("--stream", action="store_true",
                    help="host-resident chunked dataset + proposal-aware "
                    "device window (data/streaming.py); bitwise-identical "
                    "to the resident run, composes with --mesh and "
                    "--async-scoring")
    ap.add_argument("--chunk-size", type=int, default=0,
                    help="examples per host chunk (0 = auto: an eighth of "
                    "each shard's example range)")
    ap.add_argument("--window-chunks", type=int, default=4,
                    help="device-resident hot chunks per shard")
    ap.add_argument("--prefetch-every", type=int, default=1,
                    help="stage a fresh proposal-ranked window every K "
                    "steps")
    ap.add_argument("--serve-loop", action="store_true",
                    help="close the train/serve loop: run a continuous-"
                    "batching decode tick each train step against "
                    "published param snapshots, and ingest finished "
                    "requests back into the store as scorable examples "
                    "(requires --stream and a token arch)")
    ap.add_argument("--serve-slots", type=int, default=2,
                    help="serve loop: concurrent decode slots")
    ap.add_argument("--serve-prompt-len", type=int, default=4,
                    help="serve loop: synthetic-traffic prompt length")
    ap.add_argument("--serve-max-new", type=int, default=4,
                    help="serve loop: tokens generated per request")
    ap.add_argument("--serve-rate", type=int, default=1,
                    help="serve loop: new requests per serve tick")
    ap.add_argument("--serve-every", type=int, default=1,
                    help="serve loop: run a serve tick every K train steps")
    ap.add_argument("--serve-publish-every", type=int, default=0,
                    help="serve loop: snapshot train params for serving "
                    "every K serve ticks (0 = --swap-every, extending the "
                    "async staleness discipline to decode)")
    ap.add_argument("--serve-decode-steps", type=int, default=2,
                    help="serve loop: lock-step decodes per serve tick")
    ap.add_argument("--serve-reserve-chunks", type=int, default=2,
                    help="serve loop: zero chunks appended up front as "
                    "traffic capacity (reserved rows are proposal-"
                    "invisible until ingested)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default="")
    ap.add_argument("--metrics-jsonl", default="",
                    help="write schema-versioned telemetry events (spans, "
                    "counters, per-step metrics, monitors) to this JSONL "
                    "file; tools/metrics_report.py renders a run summary "
                    "from it")
    ap.add_argument("--metrics-every", type=int, default=0,
                    help="telemetry cadence in steps for periodic counters "
                    "and metrics records (0 = --log-every)")
    ap.add_argument("--monitors", default="none",
                    help="proposal-health monitors compiled into the step "
                    "as extra outputs: 'all', 'none', or a comma list of "
                    "ess,entropy,max_weight_frac,empty_rows,staleness; "
                    "off is HLO-identical to a monitor-free build and on "
                    "never changes the trajectory")
    ap.add_argument("--profile-dir", default="",
                    help="capture a jax.profiler trace of the window given "
                    "by --profile-steps into this directory")
    ap.add_argument("--profile-steps", default="2:2",
                    help="profiler capture window as START:COUNT train "
                    "steps (default 2:2 — skip compile, grab two steps)")
    args = ap.parse_args(argv)
    mp = max(args.model_parallel, 1)
    dp = max(args.mesh, 1)
    use_mesh = args.mesh > 0 or mp > 1
    validate_flags(ap, args, mp)
    _force_host_devices(dp * mp if use_mesh else args.mesh)
    enable_compile_cache()
    devices = jax.devices()
    print(f"devices: {devices[0].platform} {devices[0].device_kind} "
          f"x{len(devices)}", flush=True)
    if use_mesh and len(devices) < dp * mp:
        ap.error(f"--mesh {dp} x --model-parallel {mp} needs {dp * mp} "
                 f"devices, found {len(devices)} {devices[0].platform} "
                 f"device(s); on the CPU set JAX_PLATFORMS=cpu to force "
                 f"host devices")
    model_axes = ("model",) if mp > 1 else ()
    seq_shard = mp > 1 and (args.sequence_parallel is None
                            or args.sequence_parallel)

    from repro.telemetry import EventSink, MonitorSet, NullSink, Telemetry
    try:
        mon_set = MonitorSet.parse(args.monitors)
    except ValueError as e:
        ap.error(f"--monitors: {e}")
    try:
        prof_start, prof_count = map(int, args.profile_steps.split(":"))
    except ValueError:
        ap.error(f"--profile-steps must be START:COUNT, got "
                 f"{args.profile_steps!r}")
    if args.metrics_jsonl:
        sink = EventSink(args.metrics_jsonl,
                         run={"arch": args.arch, "mode": args.mode,
                              "steps": args.steps, "mesh": args.mesh,
                              "model_parallel": mp,
                              "async_scoring": args.async_scoring,
                              "stream": args.stream,
                              "serve_loop": args.serve_loop,
                              "swap_every": args.swap_every,
                              "monitors": list(mon_set.names),
                              "proposal_strategy": _proposal_name(args),
                              "adaptive_is": args.adaptive_is,
                              "seed": args.seed})
    else:
        sink = NullSink()
    ctl = None
    if args.adaptive_is:
        from repro.core.controller import ControllerConfig, ProposalController
        ctl = ProposalController(
            ControllerConfig(adapt_every=args.adapt_every,
                             adapt_swap=args.async_scoring),
            swap_every=args.swap_every)
        # the tap is truthy even over a NullSink, so the metrics/span
        # records the controller feeds on keep flowing file or no file
        sink = ctl.attach(sink)
    tel = Telemetry(sink, every=args.metrics_every or args.log_every)

    if args.arch == "mlp_svhn":
        params, train, pel, scorer, param_specs = build_mlp(args, model_axes)
    else:
        params, train, pel, scorer, param_specs = build_lm(
            args, tel, model_axes, seq_shard=seq_shard)
    pspec_kw = (dict(param_specs=param_specs, params_template=params)
                if mp > 1 else {})

    fused_score = None
    if args.mode == "fused":
        _cfg = resolve_config(args)
        if args.arch == "mlp_svhn":
            from repro.models.mlp import per_example_loss_and_score
            fused_score = lambda p, b: per_example_loss_and_score(
                p, b, _cfg, model_axes=model_axes)
        else:
            from repro.models.transformer import per_example_loss_and_score
            fused_score = lambda p, b: per_example_loss_and_score(
                p, _cfg, b, model_axes=model_axes, seq_shard=seq_shard)

    opt = sgd(args.lr)
    tcfg = ISSGDConfig(
        batch_size=args.batch, score_batch_size=args.score_batch,
        refresh_every=args.refresh_every, mode=args.mode,
        is_cfg=ISConfig(smoothing=args.smoothing,
                        staleness_threshold=args.staleness_threshold),
        score_shards=max(args.score_shards, 1),
        index=args.index, table_dtype=args.table_dtype,
        score_ttl=args.score_ttl,
        index_chunk_size=args.index_chunk_size)
    state = init_train_state(params, opt, train.size, seed=args.seed,
                             table_dtype=args.table_dtype,
                             index_chunk_size=args.index_chunk_size)
    data = train.arrays
    probe = None
    pipe = None
    plane = None
    mesh = None
    serve = None
    if args.stream:
        import numpy as np
        from repro.data.store import ChunkedExampleStore
        from repro.data.streaming import (StreamedISSGD, StreamingDataPlane,
                                          make_streamed_steps)
        n_examples = train.size
        n_shards = dp    # data shards; the model axis never splits examples
        if n_examples % n_shards:
            ap.error(f"--examples {n_examples} not divisible by --mesh "
                     f"{n_shards}")
        n_local = n_examples // n_shards
        csize = args.chunk_size
        if not csize:
            # auto: the largest divisor of the per-shard example count
            # that is at most an eighth of it (always exists; 1 divides)
            csize = next(c for c in range(max(n_local // 8, 1), 0, -1)
                         if n_local % c == 0)
        store = ChunkedExampleStore.from_arrays(data, csize)
        n_live = n_examples
        if args.serve_loop:
            # reserve traffic capacity BEFORE any sharded layout: shard
            # chunk ranges are contiguous slices of num_chunks, so the
            # tail must exist up front (store.append_chunk docs)
            for _ in range(max(args.serve_reserve_chunks, 1)):
                store.append_chunk()
            n_examples = store.num_examples
            if store.num_chunks % n_shards:
                ap.error(f"--serve-reserve-chunks {args.serve_reserve_chunks}"
                         f" leaves num_chunks={store.num_chunks} not "
                         f"divisible by --mesh {n_shards}")
            from repro.core.weight_store import init_store, reserve_tail
            state = state._replace(
                store=reserve_tail(
                    init_store(n_examples, table_dtype=args.table_dtype,
                               chunk_size=args.index_chunk_size), n_live))
        wc = max(1, min(args.window_chunks, store.num_chunks // n_shards))
        # the step programs never take the dataset; drop the monolithic
        # device arrays now that the host store holds the examples —
        # the sharding specs only need per-key ndim/dtype
        template = {k: np.empty((0,) + store.row_shape(k), store.dtype(k))
                    for k in store.keys}
        train = data = None
        if args.async_scoring:
            from repro.core.weight_store import to_buffered
            state = state._replace(store=to_buffered(state.store))
        if use_mesh:
            from repro.core import distributed as dist
            from repro.launch.mesh import make_debug_mesh
            mesh = make_debug_mesh(dp, model=mp)
            s_step, smp_step, m_step, tcfg = dist.make_sharded_streamed_steps(
                pel, scorer, opt, tcfg, n_examples, mesh, template,
                chunk_size=csize, fused_score=fused_score,
                async_mode=args.async_scoring,
                monitor_traces=not args.no_trace_monitors,
                monitors=mon_set, gated=args.adaptive_is, **pspec_kw)
        else:
            s_step, smp_step, m_step = make_streamed_steps(
                pel, scorer, opt, tcfg, n_examples, csize,
                fused_score=fused_score, async_mode=args.async_scoring,
                monitor_traces=not args.no_trace_monitors,
                monitors=mon_set, gated=args.adaptive_is)
        plane = StreamingDataPlane(store, wc, mesh=mesh)
        pipe = StreamedISSGD(plane, s_step, smp_step, m_step, tcfg,
                             n_examples, async_mode=args.async_scoring,
                             swap_every=args.swap_every,
                             prefetch_every=args.prefetch_every,
                             telemetry=tel, controller=ctl)
        if args.mode == "fused":
            probe = pipe.probe
        if args.serve_loop:
            from repro.serving import (ContinuousBatcher, ServeLoop,
                                       TrafficIngest, make_synthetic_traffic)
            scfg = resolve_config(args)
            serve_max_len = args.serve_prompt_len + args.serve_max_new
            b_pp = None
            if mp > 1:
                from repro.dist.sharding import param_pspecs as _make_pp
                b_pp = _make_pp(param_specs, params, mesh)
            batcher = ContinuousBatcher(
                params, scfg, num_slots=args.serve_slots,
                max_len=serve_max_len, mesh=mesh, param_pspecs=b_pp)
            ingest = TrafficIngest(store, seq_len=args.seq + 1,
                                   start_row=n_live,
                                   capacity_rows=n_examples - n_live)
            traffic = make_synthetic_traffic(
                scfg.vocab_size, args.serve_prompt_len,
                rate=args.serve_rate, max_new_tokens=args.serve_max_new,
                seed=args.seed + 7)
            serve = ServeLoop(
                batcher, ingest, traffic,
                publish_every=args.serve_publish_every or args.swap_every,
                serve_every=args.serve_every,
                decode_steps=args.serve_decode_steps, telemetry=tel)
            pipe.serve_tick = serve.on_train_step
            print(f"serve-loop: {args.serve_slots} slots, max_len "
                  f"{serve_max_len}, {n_examples - n_live} reserved rows",
                  flush=True)
        print(f"streaming: {store.num_chunks} chunks x {csize} rows "
              f"host-resident, window {wc} chunks/shard x {n_shards} "
              f"shard(s)"
              + (f", async swap every {args.swap_every}"
                 if args.async_scoring else ""), flush=True)
    elif args.async_scoring:
        from repro.core.async_pipeline import AsyncPipeline, make_async_steps
        from repro.core.weight_store import to_buffered
        state = state._replace(store=to_buffered(state.store))
        if use_mesh:
            from repro.core import distributed as dist
            from repro.launch.mesh import make_debug_mesh
            mesh = make_debug_mesh(dp, model=mp)
            print(f"mesh: {tuple(mesh.shape.values())} over "
                  f"{jax.device_count()} devices (async, swap every "
                  f"{args.swap_every})", flush=True)
            s_step, m_step, tcfg = dist.make_sharded_async_steps(
                pel, scorer, opt, tcfg, train.size, mesh, data,
                monitor_traces=not args.no_trace_monitors,
                monitors=mon_set, gated=args.adaptive_is, **pspec_kw)
            data = dist.shard_dataset(data, mesh)
        else:
            print(f"async scoring, swap every {args.swap_every}", flush=True)
            s_step, m_step = make_async_steps(
                pel, scorer, opt, tcfg, train.size,
                monitor_traces=not args.no_trace_monitors,
                monitors=mon_set, gated=args.adaptive_is)
        pipe = AsyncPipeline(s_step, m_step, args.swap_every, telemetry=tel,
                             controller=ctl)
    elif use_mesh:
        from repro.core import distributed as dist
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(dp, model=mp)
        print(f"mesh: {tuple(mesh.shape.values())} over "
              f"{jax.device_count()} devices", flush=True)
        raw_step, tcfg = dist.make_sharded_train_step(
            pel, scorer, opt, tcfg, train.size, mesh, data,
            fused_score=fused_score, monitors=mon_set,
            gated=args.adaptive_is, **pspec_kw)
        step_monitors = raw_step.with_monitors  # jax.jit drops attributes
        step_gated = raw_step.gated
        step = _jit_donating_state(raw_step)
        if args.mode == "fused":
            probe = jax.jit(dist.make_sharded_score_step(
                scorer, tcfg, train.size, mesh, data, optimizer=opt,
                **pspec_kw))
        data = dist.shard_dataset(data, mesh)
    else:
        raw_step = make_train_step(pel, scorer, opt, tcfg, train.size,
                                   fused_score=fused_score, monitors=mon_set,
                                   gated=args.adaptive_is)
        step_monitors = raw_step.with_monitors  # jax.jit drops attributes
        step_gated = raw_step.gated
        step = _jit_donating_state(raw_step)
        if args.mode == "fused":
            from repro.core.issgd import make_score_step
            probe = jax.jit(make_score_step(scorer, tcfg, train.size))

    if args.restore_checkpoint:
        from repro.checkpoint import restore_checkpoint
        # restore BEFORE placement: leaves come back as host numpy, so
        # the single shard_train_state below moves each (model-)shard
        # straight to its device — the full tensors never hit a device
        state, ck_step = restore_checkpoint(args.restore_checkpoint, state)
        print(f"restored {args.restore_checkpoint} (step {ck_step})",
              flush=True)
    if mesh is not None:
        from repro.core import distributed as dist
        state = dist.shard_train_state(
            state, mesh, param_specs=pspec_kw.get("param_specs"))

    history = []
    t0 = time.time()
    profiling = False
    for i in range(args.steps):
        if args.profile_dir and i == prof_start:
            jax.profiler.start_trace(args.profile_dir,
                                     profiler_options=_profile_options())
            profiling = True
            sink.emit("profile", step=i, action="start",
                      dir=args.profile_dir)
        with tel.step(i):
            mon = None
            if pipe is not None:
                state, m = pipe.step(state, data)
                mon = pipe.last_monitors
            else:
                sargs = ((state, data, ctl.gate()) if step_gated
                         else (state, data))
                out = tel.timed("train.dispatch", step, *sargs, step=i)
                if step_monitors:
                    state, m, mon = out
                else:
                    state, m = out
            if on_step is not None:
                with tel.span("train.callback", step=i):
                    on_step(i, state, m)
            if serve is not None:
                # finished traffic lands in the store between steps, once the
                # tick's training dispatches have retired (donation safety)
                state = serve.ingest_into(state)
            if probe is not None and i % args.probe_every == 0:
                state = tel.timed("train.probe", probe, state, data, step=i)
            log_now = i % args.log_every == 0 or i == args.steps - 1
            emit_now = bool(sink) and (tel.due(i) or i == args.steps - 1)
            if log_now or emit_now:
                # ONE forced transfer for everything this step logs —
                # per-field float() calls would each block the dispatch
                # queue separately
                vals, mon_vals = tel.timed(
                    "train.log_sync", jax.device_get,
                    ((m.loss, m.grad_norm, m.trace_ideal, m.trace_stale,
                      m.trace_unif, m.ess_frac), mon), step=i)
                rec = {"step": i, "loss": float(vals[0]),
                       "grad_norm": float(vals[1]),
                       "trace_ideal": float(vals[2]),
                       "trace_stale": float(vals[3]),
                       "trace_unif": float(vals[4]),
                       "ess_frac": float(vals[5]),
                       "elapsed_s": round(time.time() - t0, 2)}
                if plane is not None:
                    rec["stream_hit_rate"] = round(plane.stats.hit_rate, 4)
                if serve is not None:
                    rec["served_rows"] = int(serve.ingest.ingested)
                if log_now:
                    history.append(rec)
                    print(f"step {i:5d} loss {rec['loss']:.4f} "
                          f"√TrΣ ideal/stale/unif = "
                          f"{rec['trace_ideal']:.3f}/"
                          f"{rec['trace_stale']:.3f}/"
                          f"{rec['trace_unif']:.3f} "
                          f"ess {rec['ess_frac']:.3f}", flush=True)
                if emit_now:
                    sink.emit("metrics", step=i,
                              **{k: v for k, v in rec.items() if k != "step"})
                    if mon_vals is not None:
                        sink.emit("monitors", step=i,
                                  **{k: v for k, v in mon_vals.items()})
            if ctl is not None:
                # after the step's metrics have been folded into the window
                d = ctl.maybe_decide(i)
                if d is not None:
                    if pipe is not None:
                        pipe.swap_every = d.swap_every
                    print(f"controller: step {i} use_is={d.use_is} "
                          f"swap_every={d.swap_every} reason={d.reason}",
                          flush=True)
        if profiling and i == prof_start + prof_count - 1:
            # retire the window's dispatches before closing the trace
            jax.block_until_ready(state.params)
            jax.profiler.stop_trace()
            profiling = False
            sink.emit("profile", step=i, action="stop")
    if profiling:   # window ran past the end of the run
        jax.block_until_ready(state.params)
        jax.profiler.stop_trace()
        sink.emit("profile", step=args.steps - 1, action="stop")
    if serve is not None:
        print(f"serve-loop: ingested {serve.ingest.ingested} rows "
              f"({serve.ingest.dropped} dropped, "
              f"{len(serve.batcher.finished)} requests finished)",
              flush=True)
    if plane is not None:
        s = plane.stats
        print(f"streaming stats: window hit rate {s.hit_rate:.3f} "
              f"({s.hits} hits / {s.misses} misses), "
              f"{s.streamed_rows} scoring rows streamed, "
              f"{s.swaps} window swaps", flush=True)
    if args.save_checkpoint:
        from repro.checkpoint import save_checkpoint
        # sharded runs save gather-free: per-shard entries + manifest
        save_checkpoint(args.save_checkpoint, state, step=int(state.step),
                        gather=mesh is None)
        print(f"saved checkpoint to {args.save_checkpoint}", flush=True)
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(history, f, indent=2)
    end = {"steps": args.steps, "elapsed_s": round(time.time() - t0, 2)}
    if history:
        end["final_loss"] = history[-1]["loss"]
    if plane is not None:
        s = plane.stats
        end.update(stream_hit_rate=round(s.hit_rate, 4),
                   stream_window_swaps=s.swaps)
    if serve is not None:
        end.update(served_rows=int(serve.ingest.ingested),
                   served_dropped=int(serve.ingest.dropped))
    sink.emit("run_end", step=args.steps - 1, **end)
    sink.close()
    return state, data


if __name__ == "__main__":
    main()
