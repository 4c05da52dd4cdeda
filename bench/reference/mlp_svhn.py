"""Plain reference of the paper's MLP, for the `correct` check.

Straightforward `jax.numpy` in float32 at `highest` matmul precision, and
independent of the program under test: it makes its own data and weights
from the seed (the same random draws as the configuration's generator and
initialiser), and computes per-example gradient norms over every weight
and bias by a vmap of `jax.grad` in blocks of rows.  bench/reference/
issgd.py follows the ISSGD steps with it.  In bfloat16 it is the control.
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
SCORE_BLOCK = 32          # rows per vmap-of-grad block: 32 x 75.6 MB

# Each limit lies between the largest reading of sound runs of the program
# and the smallest reading of the bfloat16 control or of a planted fault
# (PERF.md, section 2, gives both readings of each).
LIMITS = {
    "loss": 0.015,
    "grad0": 0.009,
    "change": 0.015,
    "stale": 0.03,
    "scores": 0.2,
    "scores_median": 0.05,
    "draw": 0.2,
}


def make_data(seed: int, config: dict, flags: dict) -> dict:
    """The configuration's synthetic SVHN-like train split, from the seed."""
    d, n = config["data"], flags["examples"]
    dim, classes = config["input_dim"], config["num_classes"]
    k1, k2, *_ = jax.random.split(jax.random.key(seed), 6)
    means = jax.random.normal(k1, (classes, dim)) * d["mean_scale"]
    ka, kb, kc, kd = jax.random.split(k2, 4)
    y = jax.random.randint(ka, (n,), 0, classes)
    noisy = jax.random.uniform(kb, (n,)) < d["noisy_frac"]
    scale = jnp.where(noisy, d["noisy_scale"], d["clean_scale"])[:, None]
    x = means[y] + jax.random.normal(kc, (n, dim)) * scale
    flip = jax.random.uniform(kd, (n,)) < d["label_noise"]
    y = jnp.where(flip, (y + 1) % classes, y).astype(jnp.int32)
    x = x.astype(jnp.float32)
    mu = x.mean(axis=0, keepdims=True)
    sd = x.std(axis=0, keepdims=True) + d["std_eps"]
    return {"x": (x - mu) / sd, "y": y}


def init_params(seed: int, config: dict) -> dict:
    """He-normal weights and zero biases, one key per layer from seed+1."""
    dims = [config["input_dim"], *config["hidden"], config["num_classes"]]
    ks = jax.random.split(jax.random.key(seed + 1), len(dims) - 1)
    return {f"fc{i}": {"w": jax.random.normal(ks[i], (dims[i], dims[i + 1]),
                                              jnp.float32)
                       * (2.0 / dims[i]) ** 0.5,
                       "b": jnp.zeros((dims[i + 1],), jnp.float32)}
            for i in range(len(dims) - 1)}


def losses(params: dict, rows: dict):
    """Per-example softmax cross-entropy of the ReLU MLP."""
    n = len(params)
    h = rows["x"]
    for i in range(n):
        p = params[f"fc{i}"]
        h = jnp.dot(h, p["w"], precision=HIGHEST) + p["b"]
        if i < n - 1:
            h = jax.nn.relu(h)
    lp = jax.nn.log_softmax(h.astype(jnp.float32))
    return -jnp.take_along_axis(lp, rows["y"][:, None], axis=-1)[:, 0]


@jax.jit
def _norm_block(params, rows):
    def one(p, r):
        return losses(p, jax.tree.map(lambda a: a[None], r))[0]
    g = jax.vmap(jax.grad(one), in_axes=(None, 0))(params, rows)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)),
                                axis=tuple(range(1, l.ndim)))
                        for l in jax.tree.leaves(g)))


def grad_norms(params, rows: dict) -> jax.Array:
    """‖∇θ L(x_n)‖ per row, over every weight and bias."""
    n = rows["y"].shape[0]
    return jnp.concatenate([
        _norm_block(params, jax.tree.map(lambda a: a[i:i + SCORE_BLOCK],
                                         rows))
        for i in range(0, n, SCORE_BLOCK)])


def model(config: dict):
    """What bench/reference/issgd.py follows: this module's functions."""
    return sys.modules[__name__]
