"""Shared benchmark scaffolding: a reduced permutation-invariant-SVHN setup
mirroring the paper's §5 experiments at CPU scale (same algorithm, smaller
MLP/data so each figure runs in ~a minute)."""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.importance import ISConfig
from repro.core.issgd import ISSGDConfig, init_train_state, make_train_step
from repro.core.scorer import make_mlp_scorer
from repro.core.strategies import make_proposal
from repro.data import make_svhn_like
from repro.models.mlp import MLPConfig, accuracy, init_mlp_classifier
from repro.models.mlp import per_example_loss as mlp_pel
from repro.optim import sgd

CFG = MLPConfig(name="mlp_svhn_bench", input_dim=96, hidden=(256, 256),
                num_classes=10)
N_TRAIN = 8192


def require_forced_host_cpu(bench: str) -> None:
    """Refuse to start forced-host-device children off the CPU.

    The scaling harnesses time XLA host "devices" in child processes. On a
    chip the parent or a sibling holds the device, so the children would
    fail or hang, and host devices are not what a chip run measures."""
    if "cpu" not in os.environ.get("JAX_PLATFORMS", "").split(","):
        raise RuntimeError(
            f"{bench} times forced CPU host devices and runs only with "
            f"JAX_PLATFORMS=cpu; on a TPU use chip_smoke.py instead")


def time_fn(fn, *args, reps: int = 3) -> float:
    """Mean seconds per call of ``fn(*args)`` over ``reps`` timed calls.

    One warmup call (jit compile + execute) is fully awaited via
    ``jax.block_until_ready``, which handles tuple/pytree returns — the
    shared replacement for per-benchmark timers that warmed up by calling
    the function twice and only awaited the first tuple element."""
    jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps


def setup(seed: int = 0):
    train, test = make_svhn_like(jax.random.key(seed), n=N_TRAIN,
                                 dim=CFG.input_dim)
    params = init_mlp_classifier(jax.random.key(seed + 1), CFG)
    return CFG, train, test, params


def run_training(params, train, *, mode: str, steps: int, lr: float,
                 smoothing: float, strategy: str = "ghost",
                 batch: int = 64, score_batch: int = 512,
                 refresh_every: int = 8, staleness_threshold: int = 0,
                 seed: int = 0, record_every: int = 5, mix=None,
                 timings: dict | None = None):
    """Run `steps` of the single-device ISSGD loop; returns (state, hist,
    elapsed_s).  `strategy` takes any zoo name (core/strategies.py), with
    `mix` as the bandit_mixed coefficients.  Pass a dict as `timings` to
    get compile_s and steady-state us_per_step (step 0 excluded) filled
    in — the wall-clock the ablation tables report.
    """
    opt = sgd(lr)
    tcfg = ISSGDConfig(
        batch_size=batch, score_batch_size=score_batch,
        refresh_every=refresh_every, mode=mode,
        is_cfg=ISConfig(smoothing=smoothing,
                        staleness_threshold=staleness_threshold))
    fused = None
    if mode == "fused":
        from repro.models.mlp import per_example_loss_and_score
        fused = lambda p, b: per_example_loss_and_score(p, b, CFG)
    step = jax.jit(make_train_step(
        lambda p, b: mlp_pel(p, b, CFG),
        make_proposal(make_mlp_scorer, CFG, strategy, mix=mix),
        opt, tcfg, train.size, fused_score=fused))
    st = init_train_state(params, opt, train.size, seed=seed)
    hist = []
    t0 = time.time()
    t_warm = t0
    for i in range(steps):
        st, m = step(st, train.arrays)
        if i == 0:
            # retire compile + first execute; steady-state timing starts here
            jax.block_until_ready(st.params)
            t_warm = time.time()
        if i % record_every == 0 or i == steps - 1:
            # ONE host sync for everything this record carries — per-metric
            # float() calls would each block the dispatch queue separately,
            # serializing the timed loop once per field
            vals = jax.device_get((m.loss, m.trace_ideal, m.trace_stale,
                                   m.trace_unif, m.ess_frac))
            hist.append({
                "step": i, "loss": float(vals[0]),
                "trace_ideal": float(vals[1]),
                "trace_stale": float(vals[2]),
                "trace_unif": float(vals[3]),
                "ess": float(vals[4]),
            })
    jax.block_until_ready(st.params)
    t_end = time.time()
    if timings is not None:
        timings["compile_s"] = t_warm - t0
        if steps > 1:
            timings["us_per_step"] = (t_end - t_warm) / (steps - 1) * 1e6
    return st, hist, t_end - t0


def median_runs(fn, runs: int = 5):
    """Run fn(seed) -> list-of-dicts `runs` times; median each key/step
    (the paper reports medians over 50 runs; we use fewer for CPU)."""
    all_h = [fn(s) for s in range(runs)]
    steps = [r["step"] for r in all_h[0]]
    out = []
    for i, s in enumerate(steps):
        rec = {"step": s}
        for k in all_h[0][0]:
            if k == "step":
                continue
            rec[k] = float(np.median([h[i][k] for h in all_h]))
        out.append(rec)
    return out
