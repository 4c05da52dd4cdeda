#!/usr/bin/env python3
"""Smoke test of the ISSGD trainer on a TPU: the quickest proof that the
system still starts on the chip.

  python3 chip_smoke.py             # one chip
  python3 chip_smoke.py --chips 4   # the sharded step on four chips

One chip: the paper's model, mlp_svhn at full width (3072 -> 4x2048 -> 10),
with 65,536 synthetic SVHN-like examples resident on the device.
  1. The multi-tap Prop.-1 kernel against its f32 reference on one
     full-width batch and, bitwise, against chained single-tap launches;
     the ghost scorer against the vmap-of-grad oracle; all at `highest`
     matmul precision.
  2. The scoring program compiles with the Mosaic kernel in it.
  3. `repro.launch.train`'s entry point runs 20 steps of sync relaxed
     ISSGD, then 20 with async scoring (swap every 2); the losses must be
     finite.
Four chips (--chips 4), and nothing else: the sharded step (--mesh 4)
against the one-device run with the same logical decomposition
(--score-shards 4) and seed, at `highest` precision so that the check is
the one tests/test_sharded.py pins on host devices: identical sample
indices and losses within rtol 1e-5.  Each chip must hold its own shard of
the weight store and the dataset.

Everything runs in this one process: a chip belongs to one process at a
time.  Without a TPU it exits non-zero and prints no result.  The last
line of stdout is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

EXAMPLES = 65_536      # SVHN's train split has 73,257 rows
STEPS = 20
SEED = 0
KERNEL_RTOL = 1e-5     # f32 sums of <= 3072 squares, two summation orders
ORACLE_RTOL = 1e-4     # ghost vs vmap-of-grad norms over 14.7M parameters
LOSS_RTOL, LOSS_ATOL = 1e-5, 1e-6
MAX_DEVICE0_SHARE = 1.5  # device 0's live bytes over the least of the rest


class SmokeFailure(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


class CompileClock:
    """Sums XLA backend compile seconds reported by JAX's monitoring."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration

    def lap(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


def run_trainer(argv: list[str]):
    """Drive `repro.launch.train.main` in-process; returns the final
    (state, data), per-step losses, sample indices and step seconds."""
    from repro.launch import train
    losses, indices, step_s = [], [], []
    t = [time.perf_counter()]

    def on_step(i, state, m):
        losses.append(float(m.loss))          # blocks on the step
        indices.append(np.asarray(m.sample_indices))
        now = time.perf_counter()
        step_s.append(now - t[0])
        t[0] = now

    state, data = train.main(argv, on_step=on_step)
    return state, data, np.array(losses), indices, step_s


def mlp_taps(params, batch, cfg):
    """Inputs and output cotangents of mlp_svhn's five linears on one
    batch: the operands the ghost scorer hands the Prop.-1 kernel."""
    from repro.models.layers import Tape
    from repro.models.mlp import mlp_dims, per_example_loss
    dims = mlp_dims(cfg)
    names = [f"fc{i}" for i in range(len(dims) - 1)]
    b = batch["x"].shape[0]
    taps0 = {n: jnp.zeros((b, dims[i + 1]), jnp.float32)
             for i, n in enumerate(names)}

    def f(taps):
        tape = Tape(taps=taps, records={})
        return jnp.sum(per_example_loss(params, batch, cfg, tape=tape)), \
            tape.records

    _, pull, records = jax.vjp(f, taps0, has_aux=True)
    (dtaps,) = pull(jnp.ones((), jnp.float32))
    return tuple(records[n] for n in names), tuple(dtaps[n] for n in names)


def max_rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.abs(want)))


def check_kernels(clock: CompileClock, score_batch: int = 256,
                  oracle_batch: int = 8) -> None:
    from repro.configs.mlp_svhn import CONFIG as cfg
    from repro.core.scorer import make_mlp_scorer
    from repro.data import make_svhn_like
    from repro.kernels import ops, ref
    from repro.models.mlp import init_mlp_classifier

    params = init_mlp_classifier(jax.random.key(SEED + 1), cfg)
    batch, _ = make_svhn_like(jax.random.key(SEED), n=score_batch,
                              dim=cfg.input_dim)
    batch = batch.arrays
    with jax.default_matmul_precision("highest"):
        xs, ds = jax.jit(mlp_taps, static_argnums=2)(params, batch, cfg)
        got = ops.per_example_sqnorm_multi(xs, ds)
        want = jax.jit(ref.per_example_sqnorm_multi_ref)(xs, ds)
        err = max_rel_err(got, want)
        print(f"multi-tap Prop.-1 kernel vs f32 reference, {score_batch} "
              f"examples x {len(xs)} taps: max rel err {err:.3e} "
              f"(tol {KERNEL_RTOL:g})", flush=True)
        check(err <= KERNEL_RTOL, f"kernel rel err {err} > {KERNEL_RTOL}")
        chained = ops.per_example_sqnorm(xs[0], ds[0])
        for x, d in zip(xs[1:], ds[1:]):
            chained = chained + ops.per_example_sqnorm(x, d)
        same = bool(np.array_equal(np.asarray(got), np.asarray(chained)))
        print(f"multi-tap kernel bitwise equal to chained single-tap "
              f"launches: {same}", flush=True)
        check(same, "multi-tap kernel differs from chained launches")

        small = jax.tree.map(lambda a: a[:oracle_batch], batch)
        ghost = jax.jit(make_mlp_scorer(cfg, "ghost"))(params, small)
        full = jax.jit(make_mlp_scorer(cfg, "full"))(params, small)
        err = max_rel_err(ghost, full)
        print(f"ghost scorer vs vmap-of-grad oracle, {oracle_batch} "
              f"examples: max rel err {err:.3e} (tol {ORACLE_RTOL:g})",
              flush=True)
        check(err <= ORACLE_RTOL, f"ghost rel err {err} > {ORACLE_RTOL}")

    clock.lap()
    t0 = time.perf_counter()
    scoring = jax.jit(make_mlp_scorer(cfg, "ghost")).lower(
        params, batch).compile()
    n_calls = scoring.as_text().count("tpu_custom_call")
    print(f"scoring program ({score_batch} examples): {n_calls} "
          f"tpu_custom_call, compiled in {time.perf_counter() - t0:.3f} s "
          f"wall ({clock.lap():.3f} s XLA backend)", flush=True)
    check(n_calls >= 1, "no Mosaic kernel in the compiled scoring program")


def train_phase(clock: CompileClock, name: str, examples: int, steps: int,
                *flags: str):
    """Full-width mlp_svhn with ghost scoring through the trainer's own
    command line, plus ``flags``; the losses must be finite."""
    clock.lap()
    state, data, losses, indices, step_s = run_trainer(
        ["--arch", "mlp_svhn", "--examples", str(examples),
         "--strategy", "ghost", "--batch", "64", "--score-batch", "256",
         "--steps", str(steps), "--seed", str(SEED), "--log-every", "10",
         *flags])
    print(f"{name}: losses {' '.join(f'{x:.4f}' for x in losses)}",
          flush=True)
    print(f"{name}: {clock.lap():.3f} s XLA backend compile; start to first "
          f"step {step_s[0]:.3f} s, median step "
          f"{statistics.median(step_s[1:]):.4f} s (host clock, blocked on "
          f"each step's loss)", flush=True)
    check(len(losses) == steps, f"{name}: {len(losses)} steps ran")
    check(bool(np.all(np.isfinite(losses))), f"{name}: non-finite loss")
    return state, data, losses, indices


def one_chip(clock: CompileClock, examples: int = EXAMPLES,
             steps: int = STEPS) -> None:
    check_kernels(clock)
    train_phase(clock, "sync relaxed", examples, steps)
    train_phase(clock, "async (swap every 2)", examples, steps,
                "--async-scoring", "--swap-every", "2")


def check_sharded(arr, n_dev: int, what: str) -> None:
    devs = {s.device for s in arr.addressable_shards}
    rows = {s.data.shape[0] for s in arr.addressable_shards}
    print(f"{what}: {len(arr.addressable_shards)} shards on {len(devs)} "
          f"devices, {sorted(rows)} rows each", flush=True)
    check(len(devs) == n_dev and rows == {arr.shape[0] // n_dev},
          f"{what} is not split over the {n_dev} devices")


def live_bytes(devices) -> list[int]:
    """Bytes of every live JAX array, per device."""
    held = dict.fromkeys(devices, 0)
    for a in jax.live_arrays():
        for s in a.addressable_shards:
            held[s.device] += s.data.nbytes
    return [held[d] for d in devices]


def four_chips(clock: CompileClock, examples: int = EXAMPLES,
               steps: int = STEPS) -> None:
    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, found "
          f"{len(devices)}")
    with jax.default_matmul_precision("highest"):
        state, data, loss4, idx4 = train_phase(
            clock, "mesh 4", examples, steps, "--mesh", "4")
        check_sharded(state.store.weights, 4, "weight store")
        check_sharded(data["x"], 4, "dataset")
        used = live_bytes(devices)
        print(f"bytes of live arrays per device: {used}", flush=True)
        check(used[0] <= MAX_DEVICE0_SHARE * min(used[1:]),
              f"device 0 holds {used[0]} bytes, the others {used[1:]}")
        del state, data
        _, _, loss1, idx1 = train_phase(
            clock, "one device, 4 score shards", examples, steps,
            "--score-shards", "4")
    same = [bool(np.array_equal(a, b)) for a, b in zip(idx1, idx4)]
    rel = np.abs(loss4 - loss1) / np.maximum(np.abs(loss1), 1e-30)
    print(f"sample indices identical at {sum(same)}/{len(same)} steps; "
          f"max loss rel diff {float(rel.max()):.3e} (rtol {LOSS_RTOL:g})",
          flush=True)
    check(all(same), "sharded and one-device runs sampled differently")
    np.testing.assert_allclose(loss4, loss1, rtol=LOSS_RTOL, atol=LOSS_ATOL)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = ap.parse_args()

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    print(f"device: {dev['platform']} {dev['kind']} x{dev['count']}",
          flush=True)
    if dev["platform"] != "tpu":
        print("chip_smoke: no TPU found; this test never falls back to "
              "another platform", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    try:
        (four_chips if args.chips == 4 else one_chip)(clock)
    except (SmokeFailure, AssertionError) as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
