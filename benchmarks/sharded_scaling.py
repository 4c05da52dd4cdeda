"""Sharded-ISSGD scaling: scoring throughput and step time vs mesh shape.

Sweeps a dp×mp grid: pure data-parallel points scale the scoring fan-out
(the paper's workers), model-parallel points tensor-shard params +
optimizer state over a trailing `model` axis (activation gathers + score
psums buy per-device parameter memory).  Each mesh shape runs in a fresh
subprocess because the XLA host-device count is fixed at first backend
init.  The child times (a) the standalone scoring fan-out and (b) the
full sharded train step, on the shared benchmark MLP setup.

On CPU the forced host devices share the same cores, so absolute speedups
are not the claim — the recorded numbers pin down the *overhead* of the
sharded path (collective cost per step) and become real scaling curves on
a pod.  Standalone:

  PYTHONPATH=src python -m benchmarks.sharded_scaling --devices 1,2,4
  PYTHONPATH=src python -m benchmarks.sharded_scaling --devices 1,2 --mp 1,2
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap

_CHILD = """
    import json, time
    import jax
    from repro.core.importance import ISConfig
    from repro.core.issgd import ISSGDConfig, init_train_state
    from repro.core import distributed as dist
    from repro.core.scorer import make_mlp_scorer
    from repro.data import make_svhn_like
    from repro.launch.mesh import make_debug_mesh
    from repro.models.mlp import MLPConfig, init_mlp_classifier, mlp_specs
    from repro.models.mlp import per_example_loss as mlp_pel
    from repro.optim import sgd

    DP, MP = {dp}, {mp}
    STEPS = {steps}
    cfg = MLPConfig(input_dim={dim}, hidden=(256, 256), num_classes=10)
    train, _ = make_svhn_like(jax.random.key(0), n={n}, dim=cfg.input_dim)
    params = init_mlp_classifier(jax.random.key(1), cfg)
    opt = sgd(0.02)
    tcfg = ISSGDConfig(batch_size=64, score_batch_size={sb},
                       mode="relaxed", is_cfg=ISConfig(smoothing=1.0),
                       score_shards={w})
    mesh = make_debug_mesh(DP, model=MP)
    maxes = ("model",) if MP > 1 else ()
    pel = lambda p, b: mlp_pel(p, b, cfg, model_axes=maxes)
    scorer = make_mlp_scorer(cfg, "ghost", model_axes=maxes)
    pk = (dict(param_specs=mlp_specs(cfg), params_template=params)
          if MP > 1 else dict())
    step, tcfg = dist.make_sharded_train_step(
        pel, scorer, opt, tcfg, train.size, mesh, train.arrays, **pk)
    step = jax.jit(step)
    score = jax.jit(dist.make_sharded_score_step(
        scorer, tcfg, train.size, mesh, train.arrays, optimizer=opt, **pk))
    state = dist.shard_train_state(
        init_train_state(params, opt, train.size), mesh,
        param_specs=pk.get("param_specs"))
    data = dist.shard_dataset(train.arrays, mesh)

    def timed(fn, s):
        s2 = fn(s, data)                       # compile + warm
        jax.block_until_ready(s2)
        t0 = time.perf_counter()
        for _ in range(STEPS):
            s = fn(s, data)
        jax.block_until_ready(s)
        return (time.perf_counter() - t0) / STEPS, s

    dt_score, state = timed(score, state)
    dt_step, state = timed(lambda s, d: step(s, d)[0], state)
    pbytes = sum(x.addressable_shards[0].data.nbytes
                 for x in jax.tree.leaves(state.params))
    print(json.dumps({{
        "devices": DP * MP,
        "dp": DP, "mp": MP,
        "score_ms": dt_score * 1e3,
        "score_examples_per_s": {sb} / dt_score,
        "step_ms": dt_step * 1e3,
        "param_bytes_per_device": pbytes,
    }}))
"""


_TCHILD = """
    import json, time
    import jax, jax.numpy as jnp
    from repro.core.importance import ISConfig
    from repro.core.issgd import ISSGDConfig, init_train_state
    from repro.core import distributed as dist
    from repro.core.scorer import make_lm_scorer
    from repro.data import make_token_dataset
    from repro.launch.mesh import make_debug_mesh
    from repro.models.config import ModelConfig
    from repro.models.transformer import init_transformer, transformer_specs
    from repro.models.transformer import per_example_loss as lm_pel
    from repro.optim import sgd

    DP, MP = {dp}, {mp}
    STEPS = {steps}
    SEQ = 32
    cfg = ModelConfig(name="bench", arch_type="dense", num_layers=2,
                      d_model=128, num_heads=8, num_kv_heads=2, d_ff=256,
                      vocab_size=256, dtype="float32", remat=False)
    train = make_token_dataset(jax.random.key(0), n={n}, seq=SEQ + 1,
                               vocab=cfg.vocab_size)
    params = init_transformer(jax.random.key(1), cfg)
    opt = sgd(0.02)
    mesh = make_debug_mesh(DP, model=MP)
    maxes = ("model",) if MP > 1 else ()
    pk = (dict(param_specs=transformer_specs(cfg), params_template=params)
          if MP > 1 else dict())

    def build(seq_shard):
        pel = lambda p, b: lm_pel(p, cfg, b, model_axes=maxes,
                                  seq_shard=seq_shard)[0]
        scorer = make_lm_scorer(cfg, "ghost", model_axes=maxes,
                                seq_shard=seq_shard)
        tcfg = ISSGDConfig(batch_size=16, score_batch_size={sb},
                           mode="relaxed", is_cfg=ISConfig(smoothing=1.0),
                           score_shards={w})
        step, tcfg = dist.make_sharded_train_step(
            pel, scorer, opt, tcfg, train.size, mesh, train.arrays, **pk)
        return jax.jit(step)

    state0 = dist.shard_train_state(
        init_train_state(params, opt, train.size), mesh,
        param_specs=pk.get("param_specs"))
    data = dist.shard_dataset(train.arrays, mesh)

    def timed(fn, s):
        s2, _ = fn(s, data)                    # compile + warm
        jax.block_until_ready(s2)
        t0 = time.perf_counter()
        for _ in range(STEPS):
            s, _ = fn(s, data)
        jax.block_until_ready(s)
        return (time.perf_counter() - t0) / STEPS, s

    dt_sp, state = timed(build(True), state0)
    dt_nosp, state = timed(build(False), state)
    pbytes = sum(x.addressable_shards[0].data.nbytes
                 for x in jax.tree.leaves(state.params))
    # per-device norm-segment activation bytes (analytic: the RMSNorm
    # input slice per sub-layer), with/without sequence parallelism
    rows_dev = {sb} // DP
    norm_full = rows_dev * SEQ * cfg.d_model * 4
    norm_sp = norm_full // MP if MP > 1 and SEQ % MP == 0 else norm_full
    print(json.dumps({{
        "devices": DP * MP,
        "dp": DP, "mp": MP, "arch": "transformer",
        "step_ms": dt_sp * 1e3,
        "step_ms_no_seq_parallel": dt_nosp * 1e3,
        "param_bytes_per_device": pbytes,
        "norm_segment_bytes_per_device": norm_sp,
        "norm_segment_bytes_no_seq_parallel": norm_full,
    }}))
"""


def _run_child(dp: int, mp: int, *, n: int, dim: int, sb: int, w: int,
               steps: int, arch: str = "mlp") -> dict:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    nd = dp * mp
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={nd}",
               PYTHONPATH=os.path.join(repo, "src"))
    template = _TCHILD if arch == "transformer" else _CHILD
    code = textwrap.dedent(template).format(dp=dp, mp=mp, n=n, dim=dim,
                                            sb=sb, w=w, steps=steps)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=repo, timeout=560)
    if r.returncode != 0:
        raise RuntimeError(f"dp={dp} mp={mp} failed:\n{r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def sharded_scaling(device_counts=(1, 2, 4), n: int = 4096, dim: int = 96,
                    sb: int = 512, steps: int = 10, mp_counts=(1,),
                    arch: str = "mlp"):
    """Benchmark-harness entry: (rows, summary) over the dp×mp grid.

    ``arch="transformer"`` swaps in the dense-transformer child (ghost
    scoring through the model-axis-aware forward) and reports the
    sequence-parallel step time next to the replicated-norm one, plus
    the per-device norm-segment activation bytes both ways."""
    from benchmarks.common import require_forced_host_cpu
    require_forced_host_cpu("sharded_scaling")
    w = max(device_counts)  # same logical decomposition at every size
    rows = []
    for mp in mp_counts:
        for dp in device_counts:
            rows.append(_run_child(dp, mp, n=n, dim=dim, sb=sb, w=w,
                                   steps=steps, arch=arch))
    def _tag(r):
        return (f"{r['dp']}dev" if r["mp"] == 1
                else f"{r['dp']}x{r['mp']}dev")

    summary = {}
    base = min(rows, key=lambda r: (r["mp"], r["dp"]))
    for r in rows:
        tag = _tag(r)
        summary[f"step_ms/{tag}"] = r["step_ms"]
        if "score_examples_per_s" in r:
            summary[f"score_throughput/{tag}"] = r["score_examples_per_s"]
        summary[f"speedup_vs_{_tag(base)}/{tag}"] = (
            base["step_ms"] / r["step_ms"])
        if "step_ms_no_seq_parallel" in r:
            summary[f"step_ms_no_seq_parallel/{tag}"] = (
                r["step_ms_no_seq_parallel"])
            summary[f"norm_segment_bytes/{tag}"] = (
                r["norm_segment_bytes_per_device"])
            summary[f"norm_segment_bytes_no_sp/{tag}"] = (
                r["norm_segment_bytes_no_seq_parallel"])
        if r["mp"] > 1:
            summary[f"param_bytes_per_device/{tag}"] = (
                r["param_bytes_per_device"])
    return rows, summary


def transformer_scaling(device_counts=(1, 2), mp_counts=(1, 2),
                        sb: int = 128, steps: int = 5):
    """Registry entry for the transformer sweep (see benchmarks/run.py)."""
    return sharded_scaling(device_counts, n=1024, sb=sb, steps=steps,
                           mp_counts=mp_counts, arch="transformer")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", default="1,2,4",
                    help="comma-separated data-parallel sizes")
    ap.add_argument("--mp", default="1",
                    help="comma-separated model-parallel sizes (grid with "
                    "--devices; total devices per point = dp*mp)")
    ap.add_argument("--arch", default="mlp",
                    choices=["mlp", "transformer"],
                    help="benchmark model: the paper MLP, or the dense "
                    "transformer through the model-axis-aware forward "
                    "(reports seq-parallel vs replicated-norm step time "
                    "and per-device norm-segment activation bytes)")
    ap.add_argument("--examples", type=int, default=None,
                    help="dataset rows (default: 4096 mlp / 1024 "
                    "transformer — token rows are ~33x larger)")
    ap.add_argument("--score-batch", type=int, default=None,
                    help="rows rescored per step (default: 512 mlp / "
                    "128 transformer)")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    counts = tuple(int(x) for x in args.devices.split(","))
    mps = tuple(int(x) for x in args.mp.split(","))
    if args.examples is None:
        args.examples = 1024 if args.arch == "transformer" else 4096
    if args.score_batch is None:
        args.score_batch = 128 if args.arch == "transformer" else 512
    rows, summary = sharded_scaling(counts, n=args.examples,
                                    sb=args.score_batch, steps=args.steps,
                                    mp_counts=mps, arch=args.arch)
    for r in rows:
        print(r)
    print(json.dumps(summary, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f, indent=2)


if __name__ == "__main__":
    main()
