"""The benchmark's operation and byte counts against counts by hand."""
import json

import pytest
from conftest import ROOT

from bench import run

MLP = json.loads((ROOT / "bench" / "configs" / "mlp_svhn.json").read_text())
COSTS = run.load_module("costs", "mlp_svhn")
W = [3072 * 2048, 2048 * 2048, 2048 * 2048, 2048 * 2048, 2048 * 10]


def test_mlp_parameters():
    # 3072 -> 4 x 2048 -> 10 with biases; not the 14.7M once written
    assert COSTS.parameters(MLP) == 18_903_050 == MLP["parameters"]


@pytest.mark.parametrize("batch,score,six_pb_four_ps", [
    (64, 256, 26.6e9),        # score_heavy
    (1024, 256, 135.5e9),     # master_heavy
])
def test_mlp_step_flops(batch, score, six_pb_four_ps):
    """6·P·B + 4·P·S (the usual count, biases aside) less the input
    layer's input-gradient, which nothing needs, on both passes."""
    p = sum(W)
    usual = 6 * p * batch + 4 * p * score
    assert usual == pytest.approx(six_pb_four_ps, rel=2e-3)
    flops = COSTS.step_flops(MLP, {"batch": batch, "score_batch": score})
    assert flops == usual - 2 * W[0] * (batch + score)


def test_sqnorm_multi_cost():
    cost = run.load_module("costs", "per_example_sqnorm_multi").cost
    c = cost(256, [3072, 2048, 2048, 2048, 2048], [2048, 2048, 2048, 2048, 10])
    elems = 256 * (11264 + 8202)
    assert c["bytes"] == 4 * elems + 4 * 256 * 5
    assert c["flops"] == 2 * elems + 2 * 256 * 5
    # bound by bytes on a v5e, by two orders of magnitude
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    v5e = peaks["TPU v5 lite"]
    assert (c["bytes"] / v5e["hbm_bytes_per_s"]
            > 100 * c["flops"] / v5e["bf16_flops_per_s"])

