"""Compile for a described TPU v5e, without the chip.

The TPU compiler is installed with JAX and compiles for devices that are
described, not attached.  That catches what interpret-mode parity cannot:
Mosaic's block-tiling rules, unimplemented kernel primitives, and programs
that do not fit the chip's memory.  Nothing runs here, so nothing here is a
result or a time.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every xdist worker
imports this file.  The persistent compilation cache is off around these
compiles (their entries cannot be read back without a chip).

The kernels that Mosaic still refuses are pinned as strict xfails quoting
the refusal, so the change that fixes one flips its test.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

V5E_HBM_BYTES = 16 * 10**9   # one v5e chip (Google Cloud, "TPU v5e")

# mlp_svhn's five tapped linears: 3072 -> 4 x 2048 -> 10
MLP_IN = (3072, 2048, 2048, 2048, 2048)
MLP_OUT = (2048, 2048, 2048, 2048, 10)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Steer `ops` to lower its kernels through Mosaic, as on a TPU host.
    The jitted wrappers cache traces by shape, so the caches are cleared
    on both sides: no interpret-mode trace leaks in, no Mosaic one out."""
    jax.clear_caches()
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    yield
    monkeypatch.undo()
    jax.clear_caches()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("b", [64, 256])
def test_per_example_sqnorm_compiles(one_chip, b):
    from repro.kernels.per_example_sqnorm import per_example_sqnorm
    c = _compile(lambda x, d: per_example_sqnorm(x, d),
                 _spec(one_chip, (b, max(MLP_IN))),
                 _spec(one_chip, (b, max(MLP_OUT))))
    assert _kernel_calls(c) == 1


@pytest.mark.parametrize("b", [64, 256])
def test_per_example_sqnorm_multi_compiles(one_chip, b):
    from repro.kernels.per_example_sqnorm import per_example_sqnorm_multi
    xs = tuple(_spec(one_chip, (b, w)) for w in MLP_IN)
    ds = tuple(_spec(one_chip, (b, w)) for w in MLP_OUT)
    c = _compile(lambda xs, ds: per_example_sqnorm_multi(xs, ds), xs, ds)
    assert _kernel_calls(c) == 1


def test_ghost_norm_gram_compiles(one_chip, mosaic):
    """At S=512, din=dout=4096 the FLOP model picks the Gram kernel."""
    b, s, d = 2, 512, 4096
    assert ops.ghost_cost(s, d, d) <= ops.direct_cost(s, d, d)
    c = ops.ghost_norm.lower(_spec(one_chip, (b, s, d)),
                             _spec(one_chip, (b, s, d))).compile()
    assert _kernel_calls(c) == 1


def test_mlp_svhn_relaxed_ghost_step_compiles(one_chip, mosaic):
    """The paper's training step at full width (3072 -> 4x2048 -> 10),
    65,536 resident examples, relaxed mode with the ghost scorer: the
    multi-tap Prop.-1 kernel lowers through Mosaic and the whole program
    fits one chip's HBM."""
    from repro.configs.mlp_svhn import CONFIG as cfg
    from repro.core.issgd import ISSGDConfig, init_train_state, make_train_step
    from repro.core.scorer import make_mlp_scorer
    from repro.models.mlp import init_mlp_classifier, per_example_loss
    from repro.optim import sgd

    n = 65_536
    opt = sgd(0.01)
    step = make_train_step(
        lambda p, b: per_example_loss(p, b, cfg),
        make_mlp_scorer(cfg, "ghost"), opt,
        ISSGDConfig(batch_size=64, score_batch_size=256, mode="relaxed"), n)
    state = jax.eval_shape(lambda: init_train_state(
        init_mlp_classifier(jax.random.key(1), cfg), opt, n))
    place = lambda t: jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype), t)
    data = {"x": _spec(one_chip, (n, cfg.input_dim)),
            "y": _spec(one_chip, (n,), jnp.int32)}
    c = _compile(step, place(state), data)
    assert _kernel_calls(c) >= 1
    mem = c.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert n * cfg.input_dim * 4 < mem.argument_size_in_bytes
    assert total < V5E_HBM_BYTES, mem


# the deepseek_7b.seq2048 cell: 2 layers x 4 scored sequences of 2,048
# positions, so each layer tap's launch holds 8 rows
DEEPSEEK_CELL = ["--arch", "deepseek_7b", "--config-override", "num_layers=2",
                 "--config-override", "vocab_size=12800",
                 "--config-override", "dtype=float32"]


@pytest.mark.parametrize("din,dout", [(4096, 4096), (4096, 11008)],
                         ids=["attention", "feed_forward"])
def test_ghost_norm_compiles_at_the_deepseek_cell_shapes(one_chip, mosaic,
                                                         din, dout):
    """The cell's layer taps: 4096 -> 4096 (wq, wk, wv, wo: the FLOP
    model's tie, which goes to Gram) and 4096 -> 11008 (w_in, w_gate)."""
    rows, s = 8, 2048
    assert ops.uses_gram(s, din, dout)
    c = ops.ghost_norm.lower(_spec(one_chip, (rows, s, din)),
                             _spec(one_chip, (rows, s, dout))).compile()
    assert _kernel_calls(c) == 1


def test_deepseek_cell_step_compiles(one_chip, mosaic):
    """The cell's whole training step as the trainer builds it from the
    cell's flags (relaxed, ghost scorer, batch 2, score batch 4, 4,096
    rows of 2,049 tokens, the state donated): one Gram ghost-norm launch
    per tap, 8, and the program fits one chip's HBM."""
    from repro.core.issgd import ISSGDConfig, init_train_state, make_train_step
    from repro.core.scorer import make_lm_scorer
    from repro.launch import train
    from repro.models.transformer import init_transformer, per_example_loss
    from repro.optim import sgd

    ap = train.argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--config-override", action="append", default=[])
    cfg = train.resolve_config(ap.parse_args(DEEPSEEK_CELL))
    n, s = 4096, 2048
    opt = sgd(0.01)
    step = make_train_step(
        lambda p, b: per_example_loss(p, cfg, b)[0],
        make_lm_scorer(cfg, "ghost"), opt,
        ISSGDConfig(batch_size=2, score_batch_size=4, mode="relaxed"), n)
    state = jax.eval_shape(lambda: init_train_state(
        init_transformer(jax.random.key(1), cfg), opt, n))
    place = lambda t: jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype), t)
    state = place(state)
    data = {"tokens": _spec(one_chip, (n, s + 1), jnp.int32)}

    def run(state, stale, data):     # train._jit_donating_state's program
        return step(state._replace(stale_params=stale), data)

    c = jax.jit(run, donate_argnums=0).lower(
        state._replace(stale_params=None), state.stale_params, data).compile()
    assert _kernel_calls(c) == 8
    mem = c.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes
             + mem.generated_code_size_in_bytes)
    assert mem.alias_size_in_bytes > 4 * cfg.param_count() * 0.99
    assert total < V5E_HBM_BYTES, mem


# ------------------------------------------- kernels Mosaic still refuses
_TILING = ("ValueError: The Pallas TPU lowering currently requires that the "
           "last two dimensions of your block shape are divisible by 8 and "
           "128 respectively, or be equal to the respective dimensions of "
           "the overall array.")


def _flash(sh):
    from repro.kernels.flash_attention import flash_attention
    q, k = _spec(sh, (2, 1024, 8, 128), jnp.bfloat16), \
        _spec(sh, (2, 1024, 2, 128), jnp.bfloat16)
    return _compile(lambda q, k, v: flash_attention(q, k, v), q, k, k)


def _flash_bwd(sh, with_scores):
    from repro.kernels.flash_attention_bwd import flash_attention_bwd
    q, k = _spec(sh, (2, 1024, 8, 128), jnp.bfloat16), \
        _spec(sh, (2, 1024, 2, 128), jnp.bfloat16)
    lse = _spec(sh, (2, 8, 1024))
    return _compile(lambda q, k, v, o, l, do: flash_attention_bwd(
        q, k, v, o, l, do, with_scores=with_scores), q, k, k, q, lse, q)


def _score_sweep(sh):
    from repro.kernels.flash_attention_bwd import attn_score_sweep
    return _compile(lambda a, b, c: attn_score_sweep(a, b, c),
                    _spec(sh, (2, 1024, 8, 128)), _spec(sh, (2, 1024, 2, 128)),
                    _spec(sh, (2, 1024, 2, 128)))


def _decode(sh):
    from repro.kernels.decode_attention import decode_attention
    kv = _spec(sh, (8, 4096, 2, 128), jnp.bfloat16)
    return _compile(lambda q, k, v, n: decode_attention(q, k, v, n),
                    _spec(sh, (8, 8, 128), jnp.bfloat16), kv, kv,
                    _spec(sh, (8,), jnp.int32))


def _scan(sh):
    from repro.kernels.selective_scan import selective_scan
    seq, di, ds = _spec(sh, (2, 1024, 1024)), 1024, 16
    bc = _spec(sh, (2, 1024, ds))
    return _compile(lambda *args: selective_scan(*args), seq, seq,
                    _spec(sh, (di, ds)), bc, bc, _spec(sh, (di,)))


def _refused(reason, raises=ValueError):
    return pytest.mark.xfail(strict=True, raises=raises, reason=reason)


@pytest.mark.parametrize("build", [
    pytest.param(_flash, id="flash_attention", marks=_refused(
        _TILING + " Block spec for args[0] in pallas_call _kernel at "
        "flash_attention.py:28 has block shape (1, 256, 1, 128), array "
        "shape (2, 1024, 8, 128)")),
    pytest.param(lambda sh: _flash_bwd(sh, False), id="flash_attention_bwd",
                 marks=_refused(
        _TILING + " Block spec for args[0] in pallas_call _dkdv_kernel at "
        "flash_attention_bwd.py:51 has block shape (1, 256, 1, 128), array "
        "shape (2, 1024, 8, 128)")),
    pytest.param(lambda sh: _flash_bwd(sh, True),
                 id="flash_attention_bwd_with_scores", marks=_refused(
        _TILING + " Block spec for args[0] in pallas_call _dkdv_kernel at "
        "flash_attention_bwd.py:51 has block shape (1, 256, 1, 128), array "
        "shape (2, 1024, 8, 128)")),
    pytest.param(_score_sweep, id="attn_score_sweep", marks=_refused(
        _TILING + " Block spec for args[0] in pallas_call _sweep_kv_kernel "
        "at flash_attention_bwd.py:289 has block shape (1, 256, 1, 128), "
        "array shape (2, 1024, 2, 128)")),
    pytest.param(_decode, id="decode_attention", marks=_refused(
        "ValueError: The Pallas TPU lowering currently requires that rank 1 "
        "block shapes, either 1) the first (and only) dimension of the block "
        "shape is equal to the first (and only) dimension of the array "
        "shape, or 2) the first (and only) dimension of the block shape is "
        "a multiple of the tiling size (128 = 128 * (32 // 32)) of the array "
        "shape. Block spec for args[0] in pallas_call _kernel at "
        "decode_attention.py:23 has block shape (1,), array shape (8,), in "
        "memory space smem.")),
    pytest.param(_scan, id="selective_scan", marks=_refused(
        "NotImplementedError: Unimplemented primitive in Pallas TPU "
        "lowering for KernelType.TC: dynamic_slice.",
        raises=NotImplementedError)),
])
def test_opt_in_kernel_compiles(one_chip, build):
    """Off the default path (attn_impl, attn_scores, decode_kernel and
    ssm_mode all default to the jnp references)."""
    assert _kernel_calls(build(one_chip)) >= 1
