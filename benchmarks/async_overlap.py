"""Async-overlap benchmark: sync (fused) vs async (double-buffered) step
time across mesh sizes.

Each mesh size runs in a fresh subprocess because the XLA host-device count
is fixed at first backend init.  The child times (a) the fused step of
core/distributed.py, where the scoring fan-out serializes with the master
update, and (b) the async pipeline of core/async_pipeline.py, where the two
are dispatched as independent computations through the double-buffered
WeightStore (swap every K steps).

On CPU the forced host devices share the same cores and XLA executes the
two dispatched programs back to back, so the recorded numbers bound the
*overhead* of the split (extra dispatch + the swap copy) rather than
demonstrating the overlap win — the curves become real on a pod (ROADMAP
caveat).  Standalone:

  PYTHONPATH=src python -m benchmarks.async_overlap --mesh 1,2,4,8

Harness entry (`python -m benchmarks.run --only async_overlap
--bench-json BENCH.json`) emits the same rows as BENCH JSON.
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
    from repro.core.async_pipeline import AsyncPipeline, init_async_state
    from repro.core.scorer import make_mlp_scorer
    from repro.data import make_svhn_like
    from repro.models.mlp import MLPConfig, init_mlp_classifier
    from repro.models.mlp import per_example_loss as mlp_pel
    from repro.optim import sgd

    ND = {nd}
    STEPS = {steps}
    SWAP = {swap}
    cfg = MLPConfig(input_dim={dim}, hidden=(256, 256), num_classes=10)
    train, _ = make_svhn_like(jax.random.key(0), n={n}, dim=cfg.input_dim)
    params = init_mlp_classifier(jax.random.key(1), cfg)
    opt = sgd(0.02)
    tcfg = ISSGDConfig(batch_size=64, score_batch_size={sb},
                       mode="relaxed", is_cfg=ISConfig(smoothing=1.0),
                       score_shards={w})
    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh(ND)
    pel = lambda p, b: mlp_pel(p, b, cfg)
    scorer = make_mlp_scorer(cfg, "ghost")
    data = dist.shard_dataset(train.arrays, mesh)

    # --- sync: the fused step (scoring serializes with the update) -------
    step, tcfg = dist.make_sharded_train_step(
        pel, scorer, opt, tcfg, train.size, mesh, train.arrays)
    step = jax.jit(step)
    state = dist.shard_train_state(
        init_train_state(params, opt, train.size), mesh)
    s2 = step(state, data)                     # compile + warm
    jax.block_until_ready(s2)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        state, _m = step(state, data)
    jax.block_until_ready((state, _m))
    sync_ms = (time.perf_counter() - t0) / STEPS * 1e3

    # --- async: independently dispatched fan-out + master, swap every K --
    # monitor_traces=True keeps the program doing the same work as the
    # fused step (fig-4 trace psums included), so sync vs async is
    # apples-to-apples; the no-monitor build (zero-collective scoring) is
    # reported separately.
    def time_async(monitor):
        s_step, m_step, _ = dist.make_sharded_async_steps(
            pel, scorer, opt, tcfg, train.size, mesh, train.arrays,
            monitor_traces=monitor)
        pipe = AsyncPipeline(s_step, m_step, SWAP)
        astate = dist.shard_train_state(
            init_async_state(params, opt, train.size), mesh)
        astate, _m = pipe.step(astate, data)   # compile + warm
        jax.block_until_ready((astate, _m))
        t0 = time.perf_counter()
        for _ in range(STEPS):
            astate, _m = pipe.step(astate, data)
        jax.block_until_ready((astate, _m))
        return (time.perf_counter() - t0) / STEPS * 1e3

    async_ms = time_async(True)
    async_nomon_ms = time_async(False)

    print(json.dumps({{
        "devices": ND,
        "swap_every": SWAP,
        "sync_step_ms": sync_ms,
        "async_step_ms": async_ms,
        "async_nomon_step_ms": async_nomon_ms,
        "overlap_gain": sync_ms / async_ms,
    }}))
"""


def _run_child(nd: int, *, n: int, dim: int, sb: int, w: int, steps: int,
               swap: int) -> dict:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={nd}",
               PYTHONPATH=os.path.join(repo, "src"))
    code = textwrap.dedent(_CHILD).format(nd=nd, n=n, dim=dim, sb=sb, w=w,
                                          steps=steps, swap=swap)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=repo, timeout=560)
    if r.returncode != 0:
        raise RuntimeError(f"devices={nd} failed:\n{r.stderr[-2000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def async_overlap(device_counts=(1, 2, 4, 8), n: int = 4096, dim: int = 96,
                  sb: int = 512, steps: int = 10, swap: int = 1):
    """Benchmark-harness entry: (rows, summary)."""
    from benchmarks.common import require_forced_host_cpu
    require_forced_host_cpu("async_overlap")
    w = max(device_counts)  # same logical decomposition at every size
    rows = []
    for nd in device_counts:
        rows.append(_run_child(nd, n=n, dim=dim, sb=sb, w=w, steps=steps,
                               swap=swap))
    summary = {}
    for r in rows:
        d = r["devices"]
        summary[f"sync_ms/{d}dev"] = r["sync_step_ms"]
        summary[f"async_ms/{d}dev"] = r["async_step_ms"]
        summary[f"async_nomon_ms/{d}dev"] = r["async_nomon_step_ms"]
        summary[f"overlap_gain/{d}dev"] = r["overlap_gain"]
    return rows, summary


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="1,2,4,8",
                    help="comma-separated device counts")
    ap.add_argument("--examples", type=int, default=4096)
    ap.add_argument("--score-batch", type=int, default=512)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--swap-every", type=int, default=1)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    counts = tuple(int(x) for x in args.mesh.split(","))
    rows, summary = async_overlap(counts, n=args.examples,
                                  sb=args.score_batch, steps=args.steps,
                                  swap=args.swap_every)
    for r in rows:
        print(r)
    print(json.dumps(summary, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f, indent=2)


if __name__ == "__main__":
    main()
