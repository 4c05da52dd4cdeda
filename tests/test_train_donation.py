"""The trainer's fused step donates its input state, all but θ_stale.

`launch/train.py` jits the fused step with every buffer of the state
donated except `stale_params`, so XLA writes the new params, store, step
and rng into the buffers they replace.  Pinned here: θ_stale starts in
buffers of its own, donation engages on the single-device and the mesh
branch while a held θ_stale stays readable, and the trajectory is bitwise
that of a plain `jax.jit` of the same step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _helpers import run_py
from repro.core.issgd import init_train_state
from repro.launch import train
from repro.models.mlp import MLPConfig, init_mlp_classifier
from repro.optim import sgd

REFRESH = 4
ARGV = ["--arch", "mlp_svhn", "--smoke", "--examples", "256", "--batch", "8",
        "--score-batch", "32", "--refresh-every", str(REFRESH),
        "--log-every", "100", "--seed", "3"]


def _buffers(tree) -> list:
    return [x.unsafe_buffer_pointer() for x in jax.tree.leaves(tree)]


def test_init_stale_params_are_buffers_of_their_own():
    params = init_mlp_classifier(
        jax.random.key(0), MLPConfig(input_dim=16, hidden=(32,),
                                     num_classes=10))
    st = init_train_state(params, sgd(0.1), 64)
    assert set(_buffers(st.stale_params)).isdisjoint(_buffers(st.params))
    for p, s in zip(jax.tree.leaves(st.params),
                    jax.tree.leaves(st.stale_params)):
        assert p is not s
        np.testing.assert_array_equal(np.asarray(p), np.asarray(s))


# Runs `train.main` on `devices` forced host devices (`--mesh` when more
# than one) with a callback that holds step 0's params and θ_stale.
_HELD = """
    import jax, numpy as np
    from repro.launch import train
    argv = {argv} + {mesh}
    held = {{}}

    def on_step(i, state, m):
        if i == 0:
            held["params"] = state.params
            held["theta0"] = state.stale_params
        if i == 1:
            assert all(x.is_deleted()
                       for x in jax.tree.leaves(held["params"]))
        if i == {last}:   # θ_stale still θ₀: the push follows this step
            for x in jax.tree.leaves(held["theta0"]):
                assert not x.is_deleted()
            held["read"] = jax.tree.map(np.asarray, held["theta0"])

    state, _ = train.main(argv + ["--steps", "{steps}"], on_step=on_step)
    args = train.argparse.Namespace(smoke=True, examples=256, seed=3,
                                    strategy="ghost", proposal_strategy="")
    theta0 = train.build_mlp(args)[0]
    for a, b in zip(jax.tree.leaves(held["read"]), jax.tree.leaves(theta0)):
        np.testing.assert_array_equal(a, np.asarray(b))
    assert int(state.step) == {steps}
    print("held ok")
"""


@pytest.mark.parametrize("devices", [1, 2])
def test_state_donated_and_held_theta0_kept(devices):
    """Step 0's params are deleted once step 1 is dispatched, on the
    single-device branch and on the mesh branch (`--mesh 2`); step 0's
    θ_stale is readable through step `refresh_every` − 1 and holds the
    initial params."""
    mesh = ["--mesh", str(devices)] if devices > 1 else []
    out = run_py(_HELD.format(argv=ARGV, mesh=mesh, last=REFRESH - 1,
                              steps=REFRESH + 2), devices=devices)
    assert "held ok" in out


def test_donating_trainer_is_bitwise_a_plain_jit(monkeypatch):
    """Same seed, `refresh_every` + 2 steps: `train.main`'s final state
    equals, bit for bit, a loop over a plain `jax.jit` of the step it
    built, from a fresh `init_train_state` of the same params."""
    built, inits = [], []
    make_train_step = train.make_train_step

    def make(*a, **k):
        built.append((a, k))
        return make_train_step(*a, **k)

    def init(params, *a, **k):
        inits.append((jax.tree.map(np.asarray, params), a, k))
        return init_train_state(params, *a, **k)

    monkeypatch.setattr(train, "make_train_step", make)
    monkeypatch.setattr(train, "init_train_state", init)
    steps = REFRESH + 2
    final, data = train.main(ARGV + ["--steps", str(steps)])

    (a, k), = built
    params, init_a, init_k = inits[0]
    step = jax.jit(make_train_step(*a, **k))
    st = init_train_state(jax.tree.map(jnp.asarray, params), *init_a,
                          **init_k)
    for _ in range(steps):
        st, _ = step(st, data)
    got = jax.tree.map(np.asarray, final._replace(
        rng=jax.random.key_data(final.rng)))
    want = jax.tree.map(np.asarray, st._replace(
        rng=jax.random.key_data(st.rng)))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(g, w)
