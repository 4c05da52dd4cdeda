"""Operations an ISSGD step of the MLP requires, from its shapes.

Counted: matmul multiply-adds as 2 FLOPs.  The master's forward, its
weight gradients, and its backward through every layer but the first
(nothing needs the input's gradient); the scoring pass's forward and its
backward to each layer's output (the ghost taps), again stopping above the
first layer.  Left out: biases, activations, softmax, the norm
reductions, the sampler: each is under 0.1% of the matmuls here.
"""


def dims(config: dict) -> list:
    return [config["input_dim"], *config["hidden"], config["num_classes"]]


def parameters(config: dict) -> int:
    d = dims(config)
    return sum(a * b + b for a, b in zip(d, d[1:]))


def step_flops(config: dict, flags: dict) -> float:
    d = dims(config)
    w = [a * b for a, b in zip(d, d[1:])]
    fwd = 2 * sum(w)                 # per example
    back_acts = 2 * sum(w[1:])       # dX of every layer above the first
    master = flags["batch"] * (2 * fwd + back_acts)   # fwd + dW + dX
    scoring = flags["score_batch"] * (fwd + back_acts)
    return float(master + scoring)
