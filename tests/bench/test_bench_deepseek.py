"""The DeepSeek cell at a size the CPU holds: the plain reference against
the program (tokens, weights, losses, gradients, ghost scores), a whole
harness run that passes, the control and a planted fault that must not,
and the cell's flags resolving to its configuration file."""
import copy
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import ROOT

from bench import control, run

SEED = 2_147_483_659          # over 31 bits, as a benchmark run's seed may be
CELL = "deepseek_7b.seq2048"
# the cell's published widths cut to the CPU, by the trainer's own flag
SMOKE = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
         "d_ff": 128, "vocab_size": 256, "dtype": "float32"}
SMOKE_FLAGS = {"--seq": "16", "--examples": "256"}


def lm_smoke_cell() -> dict:
    """The cell's files as the harness loads them, with the widths cut by
    `--config-override` and 256 rows of 17 tokens."""
    c = copy.deepcopy(run.load_cell(CELL))
    flags = [f for i, f in enumerate(c["cell"]["flags"])
             if f != "--config-override"
             and c["cell"]["flags"][i - 1] != "--config-override"]
    for name, value in SMOKE_FLAGS.items():
        flags[flags.index(name) + 1] = value
    for key, value in SMOKE.items():
        flags += ["--config-override", f"{key}={value}"]
    c["cell"]["flags"] = flags
    c["config"].update({k: v for k, v in SMOKE.items() if k != "dtype"},
                       head_dim=SMOKE["d_model"] // SMOKE["num_heads"])
    return c


@pytest.fixture(scope="module")
def cell():
    return lm_smoke_cell()


@pytest.fixture(scope="module")
def ref(cell):
    return run.load_module("reference", "deepseek_7b").model(cell["config"])


def program_config(flags: list):
    from repro.launch import train
    ap = train.argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--config-override", action="append", default=[])
    args, _ = ap.parse_known_args(flags)
    return train.resolve_config(args)


@pytest.fixture(scope="module")
def cfg(cell):
    return program_config(cell["cell"]["flags"])


@pytest.fixture(scope="module")
def flags(cell):
    return run.parse_flags(cell["cell"]["flags"])


def test_reference_tokens_and_weights_are_the_programs(cell, ref, cfg,
                                                       flags):
    from repro.data import make_token_dataset
    from repro.models.transformer import init_transformer
    train = make_token_dataset(jax.random.key(SEED), n=flags["examples"],
                               seq=flags["seq"] + 1, vocab=cfg.vocab_size)
    rows = ref.make_data(SEED, cell["config"], flags)
    np.testing.assert_array_equal(np.asarray(rows["tokens"]),
                                  np.asarray(train.arrays["tokens"]))
    want = init_transformer(jax.random.key(SEED + 1), cfg)
    got = ref.init_params(SEED, cell["config"])
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    jax.tree.map(np.testing.assert_array_equal, got, want)


def test_reference_losses_and_first_gradients_are_the_programs(cell, ref,
                                                               cfg, flags):
    """Per-example losses against `per_example_loss`, and each leaf's
    gradient of their mean against `jax.grad` of the program's."""
    from repro.models.transformer import per_example_loss
    params = ref.init_params(SEED, cell["config"])
    rows = {"tokens": ref.make_data(SEED, cell["config"], flags)["tokens"][:4]}

    @jax.jit
    def both(params, rows):
        want = lambda p: per_example_loss(p, cfg, rows)[0]
        return [(f(params), jax.grad(lambda p: jnp.mean(f(p)))(params))
                for f in (lambda p: ref.losses(p, rows), want)]

    with jax.default_matmul_precision("highest"):
        (got, g_got), (want, g_want) = both(params, rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)
    jax.tree.map(functools.partial(np.testing.assert_allclose, rtol=1e-4,
                                   atol=1e-6), g_got, g_want)


@pytest.mark.parametrize("path", ["auto", "gram"])
def test_reference_scores_are_the_ghost_scorers(cell, ref, cfg, flags,
                                                monkeypatch, path):
    """Gradient norms over the tapped leaves, one row at a time, against
    `make_lm_scorer(cfg, "ghost")`: once on the path the CPU selects (the
    direct einsum) and once with `uses_gram` patched so that every tap
    takes the Gram kernel (interpret mode).  The two use different row
    counts, so neither finds the other's trace of `ghost_norm` cached."""
    from repro.core.scorer import make_lm_scorer
    from repro.kernels import ops
    n = 4
    if path == "gram":
        monkeypatch.setattr(ops, "uses_gram", lambda *_: True)
        n = 3
    params = ref.init_params(SEED, cell["config"])
    rows = {"tokens": ref.make_data(SEED, cell["config"], flags)["tokens"][:n]}
    with jax.default_matmul_precision("highest"):
        ghost = jax.jit(make_lm_scorer(cfg, "ghost"))(params, rows)
        plain = ref.grad_norms(params, rows)
    np.testing.assert_allclose(np.asarray(ghost), np.asarray(plain),
                               rtol=1e-4)


def test_lm_harness_run_is_correct(cell, ref):
    res = run.run_cell(CELL, SEED, 0.5, False, require_tpu=False,
                       loaded=cell)
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == set(ref.LIMITS)
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"examples_per_s", "setup_s"}


def test_lm_score_altered_is_not_correct(cell):
    with control.FAULTS["score_altered"]():
        res = run.run_cell(CELL, SEED, 0.5, False, require_tpu=False,
                           loaded=cell)
    assert not res["correct"], res["checks"]
    assert res["checks"]["scores"]["value"] > res["checks"]["scores"]["limit"]


def test_lm_bf16_control_fails_a_limit(cell):
    checks = control.control_checks(CELL, SEED, loaded=cell,
                                    dtype=jnp.bfloat16)
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


def test_cell_flags_resolve_to_the_configuration():
    """The cell's trainer flags give, through the trainer's resolver, the
    widths, depth, vocabulary and dtype of bench/configs/deepseek_7b.json;
    the published values of what `reduced` names differ, and nothing
    else does."""
    from repro.configs import get_config
    c = run.load_cell(CELL)
    body = c["config"]
    cfg = program_config(c["cell"]["flags"])
    published = get_config("deepseek_7b")
    for key in ("num_layers", "d_model", "num_heads", "num_kv_heads",
                "d_ff", "vocab_size", "rope_theta", "norm_eps",
                "tie_embeddings", "act", "dtype"):
        assert getattr(cfg, key) == body[key], key
    assert cfg.resolved_head_dim == body["head_dim"]
    assert cfg.param_count() == body["parameters"]
    changed = sorted(k for k in ("num_layers", "d_model", "num_heads",
                                 "num_kv_heads", "d_ff", "vocab_size",
                                 "rope_theta", "norm_eps", "head_dim",
                                 "tie_embeddings", "act")
                     if getattr(cfg, k) != getattr(published, k))
    assert changed == sorted(body["reduced"])
    assert {k: getattr(published, k) for k in body["reduced"]} == {
        k: body["published"][k] for k in body["reduced"]}
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(x for x in manifest["configs"] if x["name"] == "deepseek_7b")
    assert entry["reduced"] == body["reduced"]
    assert run.parse_flags(c["cell"]["flags"])["seq"] == body["seq"]


def test_cost_model_counts_the_step():
    """The step's FLOPs by hand at the cell's shapes: ≈ 11.65 TFLOP of
    master, ≈ 15.0 of scoring and ≈ 2.90 of Gram work."""
    c = run.load_cell(CELL)
    costs = run.load_module("costs", "deepseek_7b")
    flags = run.parse_flags(c["cell"]["flags"])
    s, d, f, v = 2048, 4096, 11008, 12800
    w = 2 * (4 * d * d + 3 * d * f) + d * v
    attn = 2 * (2 * s * s * d)
    master = 2 * 3 * (2 * s * w + attn)
    scoring = 4 * (2 * (2 * s * w) - 2 * s * 3 * d * d + 3 * attn)
    gram = s * (s + 1) * (8 * (4 * 2 * d + 3 * (d + f)) + 4 * (d + v))
    total = costs.step_flops(c["config"], flags)
    assert total == master + scoring + gram
    assert master == pytest.approx(11.65e12, rel=1e-3)
    assert gram == pytest.approx(2.90e12, rel=2e-3)
    kernel = run.load_module("costs", "ghost_norm").cost(
        s, costs.gram_taps(c["config"], 4))
    assert sum(k["flops"] for k in kernel) == gram
    assert [k["bytes"] for k in kernel][0] == 4 * 8 * s * 2 * d + 4 * 8
