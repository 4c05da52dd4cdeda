"""Operations an ISSGD step of the cut DeepSeek decoder requires, from its
shapes.

Counted: matmul multiply-adds as 2 FLOPs, the required work only (no
recompute of what `remat` throws away).  Per sequence of S positions:

- the linears' forward, 2·S·W, where W is the tapped weights' size (every
  layer's wq, wk, wv, wo, w_in, w_gate, w_out and the head); the
  embedding is a lookup;
- causal attention at half of S²: the scores and the weighted values,
  2·S²·H·hd a layer forward, twice that backward;
- the master (`--batch` sequences): forward, dW (2·S·W), and dX down to
  the embedding (2·S·W);
- the scoring pass (`--score-batch` sequences): forward, then backward to
  the taps: dX through every linear but the first layer's wq, wk and wv,
  whose input gradient nothing needs, and through attention;
- the Gram ghost norm (bench/costs/ghost_norm.py): S(S+1)(din+dout) per
  row and tap, the symmetric Gram matrices of its input and output.

Left out: norms, RoPE, softmax, SiLU, the loss, the sampler, the update
and the norm reductions, each under 1% of the matmuls here.
"""


def taps(config: dict) -> list:
    """(name, din, dout, per_layer) of each tapped linear."""
    d, f, v = config["d_model"], config["d_ff"], config["vocab_size"]
    hq = config["num_heads"] * config["head_dim"]
    hkv = config["num_kv_heads"] * config["head_dim"]
    return [("wq", d, hq, True), ("wk", d, hkv, True), ("wv", d, hkv, True),
            ("wo", hq, d, True), ("w_in", d, f, True),
            ("w_gate", d, f, True), ("w_out", f, d, True),
            ("unembed", d, v, False)]


def gram_taps(config: dict, score_batch: int) -> list:
    """(rows, din, dout) of each ghost-norm launch of a step: a layer tap
    covers every (layer, scored row) pair, the head one row per scored
    sequence."""
    layers = config["num_layers"]
    return [(score_batch * (layers if per_layer else 1), din, dout)
            for _, din, dout, per_layer in taps(config)]


def step_flops(config: dict, flags: dict) -> float:
    s, layers = flags["seq"], config["num_layers"]
    weights = sum(din * dout * (layers if per_layer else 1)
                  for _, din, dout, per_layer in taps(config))
    first_qkv = sum(din * dout for name, din, dout, _ in taps(config)
                    if name in ("wq", "wk", "wv"))
    attn = 2 * s * s * config["num_heads"] * config["head_dim"] * layers
    linear = 2 * s * weights
    master = flags["batch"] * (3 * linear + 3 * attn)
    scoring = flags["score_batch"] * (
        2 * linear - 2 * s * first_qkv + 3 * attn)
    gram = sum(rows * s * (s + 1) * (din + dout)
               for rows, din, dout in gram_taps(config, flags["score_batch"]))
    return float(master + scoring + gram)
