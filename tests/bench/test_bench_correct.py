"""The `correct` check, at a size the CPU holds: the plain reference
against the program, a whole harness run that passes, the same run with
the timed path broken underneath (each fault must fail it), and the
bfloat16 control (it must fail a limit)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import smoke_cell

from bench import control, run

SEED = 2_147_483_659          # over 31 bits, as the driver's seeds are


@pytest.fixture(scope="module")
def cell():
    return smoke_cell()


@pytest.fixture(scope="module")
def ref(cell):
    return run.load_module("reference", cell["config"]["name"])


def program_config():
    from repro.configs.mlp_svhn import smoke
    return smoke()


def test_reference_data_is_the_programs(cell, ref):
    from repro.data import make_svhn_like
    cfg = program_config()
    train, _ = make_svhn_like(jax.random.key(SEED), n=1024,
                              dim=cfg.input_dim)
    rows = ref.make_data(SEED, cell["config"], {"examples": 1024})
    np.testing.assert_array_equal(np.asarray(rows["y"]),
                                  np.asarray(train.arrays["y"]))
    np.testing.assert_allclose(np.asarray(rows["x"]),
                               np.asarray(train.arrays["x"]),
                               rtol=1e-5, atol=1e-6)


def test_reference_init_is_the_programs(cell, ref):
    from repro.models.mlp import init_mlp_classifier
    want = init_mlp_classifier(jax.random.key(SEED + 1), program_config())
    got = ref.init_params(SEED, cell["config"])
    jax.tree.map(np.testing.assert_array_equal, got, want)


def test_reference_scores_are_the_ghost_scorers(cell, ref):
    """Per-example gradient norms by vmap-of-grad against the program's
    Prop.-1 ghost scorer (interpret-mode kernel on the CPU)."""
    from repro.core.scorer import make_mlp_scorer
    cfg = program_config()
    params = ref.init_params(SEED, cell["config"])
    rows = ref.make_data(SEED, cell["config"], {"examples": 64})
    with jax.default_matmul_precision("highest"):
        ghost = make_mlp_scorer(cfg, "ghost")(params, rows)
        plain = ref.grad_norms(params, rows)
    np.testing.assert_allclose(np.asarray(ghost), np.asarray(plain),
                               rtol=1e-4)


def test_harness_run_is_correct(cell, ref):
    res = run.run_cell("mlp_svhn.score_heavy", SEED, 0.5, False,
                       require_tpu=False, loaded=cell)
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == set(ref.LIMITS)
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"examples_per_s", "setup_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(control.FAULTS))
def test_broken_step_is_not_correct(cell, fault):
    with control.FAULTS[fault]():
        res = run.run_cell("mlp_svhn.score_heavy", SEED, 0.5, False,
                           require_tpu=False, loaded=cell)
    assert not res["correct"], (fault, res["checks"])


def test_bf16_control_fails_a_limit(cell):
    checks = control.control_checks("mlp_svhn.score_heavy", SEED,
                                    loaded=cell, dtype=jnp.bfloat16)
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


def test_no_tpu_no_result(cell):
    with pytest.raises(run.BenchError, match="no TPU"):
        run.run_cell("mlp_svhn.score_heavy", SEED, 0.5, False,
                     loaded=cell)
