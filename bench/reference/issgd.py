"""The plain ISSGD steps the references follow, shared by every model.

A model module gives `make_data(seed, config, flags)` (the training rows as
a dict of arrays), `init_params(seed, config)`, `losses(params, rows)`
(per-example loss) and `grad_norms(params, rows)` (per-example gradient
norm over the parameters the configuration's scorer covers).  These steps
then follow the program's first `refresh_every` steps: the round-robin
scoring slice scored with θ₀ (θ_stale until the first push), the proposal
`max(ω̃, 0) + c` with `c` for never-scored rows, the IS scale
`mean(q) / q[i]` on the drawn rows, a plain SGD update, and at the end the
push θ_stale ← θ_K that the program makes after step K − 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def take(rows: dict, idx) -> dict:
    return {k: v[idx] for k, v in rows.items()}


def cast(rows: dict, dtype) -> dict:
    return {k: v.astype(dtype) if jnp.issubdtype(v.dtype, jnp.floating)
            else v for k, v in rows.items()}


@jax.jit
def leaf_norms(a, b, scale=1.0) -> dict:
    """Per-leaf ‖(a − b)·scale‖ in float32, named by the leaf's path; the
    harness reads the program's states with it too."""
    flat, _ = jax.tree_util.tree_flatten_with_path(a)
    return {jax.tree_util.keystr(p, simple=True, separator="."):
            jnp.sqrt(jnp.sum(jnp.square(
                (x.astype(jnp.float32) - y.astype(jnp.float32)) * scale)))
            for (p, x), y in zip(flat, jax.tree.leaves(b))}


def floats(norms: dict) -> dict:
    return {k: float(v) for k, v in norms.items()}


def proposal(weights, scored, c: float):
    return jnp.maximum(jnp.where(scored, jnp.maximum(weights, 0.0) + c, c),
                       1e-8)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _sgd_step(model, params, rows, scales, lr):
    def loss(p):
        return jnp.mean(model.losses(p, rows).astype(jnp.float32) * scales)
    value, g = jax.value_and_grad(loss)(params)
    new = jax.tree.map(lambda p, gi: (p.astype(jnp.float32)
                                      - lr * gi.astype(jnp.float32)
                                      ).astype(p.dtype), params, g)
    return value, new


def follow(model, seed: int, config: dict, flags: dict, indices,
           dtype=jnp.float32, check_steps: int = 3) -> dict:
    """The steps on the given drawn rows, one a row of `indices`, in
    `dtype`; the readings named as the harness names the program's.  The
    loss and the scores are of the first `check_steps` steps, `change` is
    θ after them less θ₀, `stale` is θ after all of them less θ₀, and
    `drawn_scored`, `expect_scored`, `uniform_scored` are the share of
    the drawn rows that the store had scored, summed over the steps: as
    drawn, as a draw from the proposal would give it, and as a uniform
    draw would."""
    n, sb, lr, c = (flags["examples"], flags["score_batch"], flags["lr"],
                    flags["smoothing"])
    with jax.default_matmul_precision("highest"):
        data = model.make_data(seed, config, flags)
        theta0 = jax.tree.map(lambda a: a.astype(dtype),
                              model.init_params(seed, config))
        params = theta0
        weights = jnp.zeros((n,), jnp.float32)
        scored = jnp.zeros((n,), bool)
        out = {"loss": [], "scores": [], "drawn_scored": 0.0,
               "expect_scored": 0.0, "uniform_scored": 0.0}
        for t, idx in enumerate(indices):
            rows = (t * sb + jnp.arange(sb)) % n
            s = model.grad_norms(theta0, cast(take(data, rows), dtype))
            if t < check_steps:
                out["scores"].append(np.asarray(s, np.float32))
            weights = weights.at[rows].set(s.astype(jnp.float32))
            scored = scored.at[rows].set(True)
            q = proposal(weights, scored, c)
            idx = jnp.asarray(idx)
            out["drawn_scored"] += float(jnp.mean(scored[idx]))
            out["expect_scored"] += float(jnp.sum(jnp.where(scored, q, 0.0))
                                          / jnp.sum(q))
            out["uniform_scored"] += float(jnp.mean(scored))
            value, new = _sgd_step(model, params,
                                   cast(take(data, idx), dtype),
                                   (jnp.sum(q) / n) / q[idx], lr)
            if t < check_steps:
                out["loss"].append(float(value))
            if t == 0:
                out["grad0"] = floats(leaf_norms(theta0, new, 1.0 / lr))
            params = new
            if t == check_steps - 1:
                out["change"] = floats(leaf_norms(params, theta0))
        out["stale"] = floats(leaf_norms(params, theta0))
        out["scores"] = np.concatenate(out["scores"])
    return out


def draw(model, seed: int, config: dict, flags: dict):
    """Rows for the control, which has no program to draw them: a
    proposal-weighted draw from the float32 reference's own scores."""
    n, sb, b, c = (flags["examples"], flags["score_batch"], flags["batch"],
                   flags["smoothing"])
    with jax.default_matmul_precision("highest"):
        data = model.make_data(seed, config, flags)
        theta0 = model.init_params(seed, config)
        key = jax.random.key(seed + 2)
        weights = jnp.zeros((n,), jnp.float32)
        scored = jnp.zeros((n,), bool)
        out = []
        for t in range(flags["refresh_every"]):
            rows = (t * sb + jnp.arange(sb)) % n
            weights = weights.at[rows].set(
                model.grad_norms(theta0, take(data, rows)))
            scored = scored.at[rows].set(True)
            q = proposal(weights, scored, c)
            key, k = jax.random.split(key)
            out.append(np.asarray(jax.random.choice(k, n, (b,),
                                                    p=q / q.sum())))
    return out


def gaps(got: dict, want: dict) -> dict:
    """The numbers compared: the worst step's loss; the worst leaf's norm
    of the first gradient, of the change over the checked steps and of
    θ_stale after the first push; the worst row's score and the median
    row's; and `draw`, how far the share of drawn rows that the store had
    scored lies from what a draw from the reference's proposal gives, as
    a fraction of the way to what a uniform draw gives (0 in expectation,
    about 1 for a sampler that ignores the proposal; `want` alone reads
    it, over the rows `got` drew).  A leaf whose reference first gradient
    is under a thousandth of the median leaf's is left out (nought to
    rounding)."""
    def leaf_gap(a, b, keep):
        med = float(np.median([b[k] for k in keep]))
        return max(abs(a[k] - b[k]) / max(b[k], med) for k in keep)
    g = want["grad0"]
    med = float(np.median(list(g.values())))
    keep = [k for k in g if g[k] >= 1e-3 * med]
    s_got = np.asarray(got["scores"], np.float64)
    s_want = np.asarray(want["scores"], np.float64)
    s_gap = np.abs(s_got - s_want) / np.maximum(s_want, np.median(s_want))
    return {
        "loss": max(abs(a - b) / abs(b)
                    for a, b in zip(got["loss"], want["loss"])),
        "grad0": leaf_gap(got["grad0"], g, keep),
        "change": leaf_gap(got["change"], want["change"], keep),
        "stale": leaf_gap(got["stale"], want["stale"], keep),
        "scores": float(np.max(s_gap)),
        "scores_median": float(np.median(s_gap)),
        "draw": abs(want["drawn_scored"] - want["expect_scored"])
        / (want["expect_scored"] - want["uniform_scored"]),
    }


def check(model, record: dict, config: dict, flags: dict, seed: int) -> dict:
    """Compare the program's readings with the reference's; each number
    beside its limit.  `flags` are the cell's trainer flags, parsed."""
    want = follow(model, seed, config, flags, record["indices"])
    return {k: {"value": v, "limit": model.LIMITS[k]}
            for k, v in gaps(record, want).items()}
