"""Plain reference of the cut DeepSeek LLM 7B decoder, for the `correct` check.

Straightforward `jax.numpy` at `highest` matmul precision, and independent
of the program under test: it makes its own tokens and weights from the
seed, with the same random draws as the program's `make_token_dataset` and
`init_transformer` but in code of its own, and its parameter tree has the
program's leaf paths, since `correct` compares leaf by leaf.  The model is
the published block (arXiv:2401.02954, the deepseek-llm-7b-base
config.json): pre-RMSNorm, RoPE (rotate-half, θ from the configuration),
causal multi-head attention, a SwiGLU feed-forward, an untied head, and
the mean next-token cross-entropy of each sequence.  Activations and
matmuls run in the weights' dtype; the norms' statistics, the attention
softmax and the final log-softmax run in float32, as a bfloat16 deployment
runs them too.  Per-example gradient norms are `jax.grad` of one row at a
time over the tapped leaves only (the linears and the head; the
configuration's `tapped`): the ghost scorer's quantity, not the full
gradient.  In the update each row and each layer is rematerialised
(`jax.checkpoint`), which changes no number, so that the reference fits
beside the program's freed state on one chip.  bench/reference/issgd.py follows the ISSGD
steps with it; in bfloat16 it is the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
NUM_PATTERNS = 32         # make_token_dataset's motifs
MOTIF_LEN = 16

# Each limit lies between the largest reading of sound runs of the program
# and the smallest reading of the bfloat16 control or of the planted fault
# that targets it, at the cell's sizes on a TPU v5e (7 sound runs, and the
# numbers a fault leaves alone in its runs; the control and each fault on 3
# seeds; PERF.md, section 2), above the geometric mean of the two, so with
# more room above the sound reading.
LIMITS = {
    # sound 0.0005; half_batch 0.61, state_unchanged 1.48 (the control
    # reads 0.0003: the loss alone cannot see bfloat16)
    "loss": 0.03,
    # sound 0.0030; control 0.090, half_batch 0.139
    "grad0": 0.02,
    # sound 0.0021; control 0.113, half_batch 0.119
    "change": 0.02,
    # sound 0.0043; control 0.110, half_batch 0.37, stale_never_pushed 1
    "stale": 0.03,
    # sound 0.0004; score_altered 0.9997 (the control reads 0.0003: the
    # program's one bfloat16 pass is what the control's weights add)
    "scores": 0.2,
    # sound 0.00016; state_unchanged 0.93
    "scores_median": 0.05,
    # at --refresh-every 32: sound 0.33, draw_uniform 0.957; simulated
    # from the measured scores, a sound run passes 0.7 but for 8 in a
    # million, a uniform sampler for 13 in a million.  At 8 no limit
    # separates them: simulated sound runs read above 1 one time in 7.
    "draw": 0.7,
}


def make_data(seed: int, config: dict, flags: dict) -> dict:
    """The motif corpus from the seed: `examples` rows of `seq` + 1
    tokens, each row one motif repeated, each token replaced by a uniform
    one at the row's rate, uniform in [0, 0.5)."""
    n, width, vocab = flags["examples"], flags["seq"] + 1, config["vocab_size"]
    k1, k2, k3, k4 = jax.random.split(jax.random.key(seed), 4)
    motifs = jax.random.randint(k1, (NUM_PATTERNS, MOTIF_LEN), 0, vocab)
    which = jax.random.randint(k2, (n,), 0, NUM_PATTERNS)
    base = motifs[which][:, jnp.arange(width) % MOTIF_LEN]
    rate = jax.random.uniform(k3, (n, 1)) * 0.5
    noise = jax.random.randint(k4, (n, width), 0, vocab)
    corrupt = jax.random.uniform(jax.random.fold_in(k3, 1), (n, width)) < rate
    return {"tokens": jnp.where(corrupt, noise, base).astype(jnp.int32)}


def _dense(key, din: int, dout: int):
    return jax.random.normal(key, (din, dout), jnp.float32) * din ** -0.5


def init_params(seed: int, config: dict) -> dict:
    """Weights from seed+1, drawn as the program draws them: normal
    linears scaled by 1/√fan-in, a 0.02-normal token table, unit norm
    scales; one key per layer, the layers stacked on a leading axis."""
    d, f, v = config["d_model"], config["d_ff"], config["vocab_size"]
    hq = config["num_heads"] * config["head_dim"]
    hkv = config["num_kv_heads"] * config["head_dim"]
    k_embed, k_layers, _ = jax.random.split(jax.random.key(seed + 1), 3)
    layers = []
    for k in jax.random.split(k_layers, config["num_layers"]):
        (k,) = jax.random.split(k, 1)          # the one layer of its period
        k_attn, k_ff = jax.random.split(k)
        kq, kk, kv, ko = jax.random.split(k_attn, 4)
        ki, kg, kout = jax.random.split(k_ff, 3)
        layers.append({
            "ln1": {"scale": jnp.ones((d,), jnp.float32)},
            "mixer": {"wq": _dense(kq, d, hq), "wk": _dense(kk, d, hkv),
                      "wv": _dense(kv, d, hkv), "wo": _dense(ko, hq, d)},
            "ln2": {"scale": jnp.ones((d,), jnp.float32)},
            "ff": {"w_in": _dense(ki, d, f), "w_gate": _dense(kg, d, f),
                   "w_out": _dense(kout, f, d)}})
    k_tok, k_head = jax.random.split(k_embed)
    return {
        "embed": {"tokens": jax.random.normal(k_tok, (v, d), jnp.float32)
                  * 0.02,
                  "unembed": _dense(k_head, d, v)},
        "layers": {"l0": jax.tree.map(lambda *a: jnp.stack(a), *layers)},
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
    }


def _rmsnorm(x, scale, eps: float):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                            + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta: float):
    """x: (S, H, hd); rotate-half RoPE at positions 0..S-1."""
    s, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _layer(config: dict, p: dict, h):
    """One decoder layer on one sequence h: (S, d)."""
    s = h.shape[0]
    nh, nkv, hd = (config["num_heads"], config["num_kv_heads"],
                   config["head_dim"])
    eps, theta = config["norm_eps"], config["rope_theta"]
    mm = functools.partial(jnp.dot, precision=HIGHEST)
    a = p["mixer"]
    x = _rmsnorm(h, p["ln1"]["scale"], eps)
    q = _rope(mm(x, a["wq"]).reshape(s, nh, hd), theta)
    k = _rope(mm(x, a["wk"]).reshape(s, nkv, hd), theta)
    v = mm(x, a["wv"]).reshape(s, nkv, hd)
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    logits = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                        k.astype(jnp.float32), precision=HIGHEST) * hd ** -0.5
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    w = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", w, v.astype(jnp.float32),
                     precision=HIGHEST).astype(h.dtype)
    h = h + mm(out.reshape(s, nh * hd), a["wo"])
    ff = p["ff"]
    x = _rmsnorm(h, p["ln2"]["scale"], eps)
    return h + mm(jax.nn.silu(mm(x, ff["w_gate"])) * mm(x, ff["w_in"]),
                  ff["w_out"])


def _row_loss(config: dict, params: dict, tokens, remat: bool = False):
    """Mean next-token cross-entropy of one row of `seq` + 1 tokens; with
    `remat`, each layer is recomputed in the backward pass."""
    h = params["embed"]["tokens"][tokens[:-1]]
    layer = functools.partial(_layer, config)
    if remat:
        layer = jax.checkpoint(layer)
    for i in range(config["num_layers"]):
        h = layer(jax.tree.map(lambda a: a[i], params["layers"]["l0"]), h)
    h = _rmsnorm(h, params["final_norm"]["scale"], config["norm_eps"])
    logits = jnp.dot(h, params["embed"]["unembed"], precision=HIGHEST)
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(lp, tokens[1:, None], -1))


class Model:
    """What bench/reference/issgd.py follows for one configuration: this
    module's functions, hashable by the configuration's numbers, so that
    `jax.jit` caches by it."""
    LIMITS = LIMITS
    make_data = staticmethod(make_data)
    init_params = staticmethod(init_params)

    def __init__(self, config: dict):
        self.config = config
        self._key = tuple(sorted(
            (k, v) for k, v in config.items()
            if isinstance(v, (bool, int, float, str))))

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, Model) and self._key == other._key

    def losses(self, params, rows):
        """Per-example loss, one row at a time, each row and layer
        recomputed in the backward pass (the master's update holds θ₀, θ
        and the gradient besides)."""
        one = jax.checkpoint(functools.partial(_row_loss, self.config,
                                               params, remat=True))
        return jax.lax.map(one, rows["tokens"])

    def grad_norms(self, params, rows):
        """‖∇θ L(x_n)‖ over the tapped leaves, one row at a time."""
        return jnp.sqrt(jnp.stack([_row_sqnorm(self, params, t)
                                   for t in rows["tokens"]]))


@functools.partial(jax.jit, static_argnums=0)
def _row_sqnorm(model: Model, params, tokens):
    g = jax.grad(functools.partial(_row_loss, model.config))(params, tokens)
    layer = g["layers"]["l0"]
    tapped = [g["embed"]["unembed"],
              *(layer["mixer"][w] for w in ("wq", "wk", "wv", "wo")),
              *(layer["ff"][w] for w in ("w_in", "w_gate", "w_out"))]
    return sum(jnp.sum(jnp.square(t.astype(jnp.float32))) for t in tapped)


def model(config: dict) -> Model:
    return Model(config)
