#!/usr/bin/env python3
"""Readings that set the limits of `correct`: the control and the faults.

  python3 bench/control.py --workload mlp_svhn.score_heavy \
      --seeds 11,12,13 --control
  python3 bench/control.py --workload mlp_svhn.score_heavy \
      --seeds 11,12,13 --fault half_batch --seconds 2
  python3 bench/control.py --workload mlp_svhn.score_heavy \
      --seeds 11,12,...,22 --sound --seconds 2

`--control` puts the reference in the program's place, computed in the
nearest precision below the configuration's (bfloat16 for its float32),
on rows the reference draws itself, and prints the numbers `correct`
compares against the float32 reference.  `--fault <name>` runs the cell
through the harness with the program broken underneath (see FAULTS) and
prints the same numbers.  `--sound` runs the cell as it is, for the
lower readings, one seed after another in this process.  The benchmark's
own runs run none of these; the tests in tests/bench run the control and
the faults at a size the CPU holds.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


@contextlib.contextmanager
def _patched(obj, name: str, wrap):
    original = getattr(obj, name)
    setattr(obj, name, wrap(original))
    try:
        yield
    finally:
        setattr(obj, name, original)


def state_unchanged():
    """The train step returns the state it was given (metrics still come)."""
    from repro.launch import train

    def wrap(make):
        def make_train_step(*a, **k):
            step = make(*a, **k)

            def unchanged(state, *rest):
                out = step(state, *rest)
                return (state, *out[1:])
            unchanged.with_monitors = step.with_monitors
            unchanged.gated = step.gated
            return unchanged
        return make_train_step
    return _patched(train, "make_train_step", wrap)


def half_batch():
    """The master's loss leaves out the second half of its batch and takes
    the mean over the rest (the IS scales carry the cut)."""
    import jax.numpy as jnp
    from repro.core import issgd

    def wrap(scale):
        def is_loss_scale(w, mean_w):
            s = scale(w, mean_w)
            h = s.shape[0] // 2
            return jnp.concatenate([2.0 * s[:h], jnp.zeros_like(s[h:])])
        return is_loss_scale
    return _patched(issgd, "is_loss_scale", wrap)


def score_altered():
    """The scorer doubles the first score of every batch it produces."""
    from repro.launch import train

    def wrap(make):
        def make_proposal(*a, **k):
            score = make(*a, **k)
            return lambda p, b: score(p, b).at[0].multiply(2.0)
        return make_proposal
    return _patched(train, "make_proposal", wrap)


def stale_never_pushed():
    """θ_stale is never refreshed: the scorer keeps θ₀ for good."""
    import dataclasses
    from repro.launch import train

    def wrap(make):
        def make_train_step(pel, scorer, opt, cfg, *a, **k):
            return make(pel, scorer, opt,
                        dataclasses.replace(cfg, refresh_every=2 ** 30),
                        *a, **k)
        return make_train_step
    return _patched(train, "make_train_step", wrap)


def draw_uniform():
    """The sampler ignores the proposal and draws rows uniformly (the IS
    scales still come from the proposal at the drawn rows)."""
    import jax
    from repro.core import issgd

    def wrap(sample):
        def two_stage_sample(key, weights, num_samples, **_):
            return jax.random.randint(key, (num_samples,), 0,
                                      weights.shape[0])
        return two_stage_sample
    return _patched(issgd, "two_stage_sample", wrap)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "score_altered": score_altered,
          "stale_never_pushed": stale_never_pushed,
          "draw_uniform": draw_uniform}


def control_checks(workload: str, seed: int, loaded: dict | None = None,
                   dtype=None) -> dict:
    """The control's numbers: the reference in `dtype` (bfloat16) against
    the reference in float32, on the same rows."""
    import jax.numpy as jnp
    from bench import run
    from bench.reference import issgd
    c = loaded or run.load_cell(workload)
    model = run.load_module("reference", c["config"]["name"]).model(
        c["config"])
    flags = run.parse_flags(c["cell"]["flags"])
    rows = issgd.draw(model, seed, c["config"], flags)
    got = issgd.follow(model, seed, c["config"], flags, rows,
                       dtype=dtype or jnp.bfloat16)
    want = issgd.follow(model, seed, c["config"], flags, rows)
    return {k: {"value": v, "limit": model.LIMITS[k]}
            for k, v in issgd.gaps(got, want).items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one reading each")
    ap.add_argument("--seconds", type=float, default=2.0)
    what = ap.add_mutually_exclusive_group(required=True)
    what.add_argument("--control", action="store_true")
    what.add_argument("--fault", choices=sorted(FAULTS))
    what.add_argument("--sound", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax
    from bench import run
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU found", file=sys.stderr)
        return 1
    (ROOT / ".jax_cache").mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_max_size", -1)
    for seed in map(int, args.seeds.split(",")):
        if args.control:
            what, checks = "control", control_checks(args.workload, seed)
        else:
            what = args.fault or "sound"
            with (FAULTS[args.fault]() if args.fault
                  else contextlib.nullcontext()):
                checks = run.run_cell(args.workload, seed, args.seconds,
                                      False)["checks"]
        print(json.dumps({"seed": seed, "what": what, "checks": checks}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
