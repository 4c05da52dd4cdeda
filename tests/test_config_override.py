"""`--config-override KEY=VALUE`: the trainer's one resolver from flags to
a configuration, and the scorer's counters of the ghost norm's paths."""
import jax
import pytest

from repro.core.scorer import ghost_tap_paths
from repro.kernels import ops
from repro.launch import train

# the deepseek_7b.seq2048 cell's cut, as its flags give it
CUT = ["--config-override", "num_layers=2",
       "--config-override", "vocab_size=12800",
       "--config-override", "dtype=float32"]


def resolve(argv):
    ap = train.argparse.ArgumentParser()
    ap.add_argument("--arch", default="mlp_svhn")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--config-override", action="append", default=[])
    return train.resolve_config(ap.parse_args(argv))


def test_overrides_replace_fields_by_their_types():
    cfg = resolve(["--arch", "deepseek_7b", *CUT,
                   "--config-override", "norm_eps=1e-5",
                   "--config-override", "tie_embeddings=true",
                   "--config-override", "attention=gqa"])
    assert (cfg.num_layers, cfg.vocab_size, cfg.dtype) == (2, 12800,
                                                           "float32")
    assert cfg.norm_eps == 1e-5 and cfg.tie_embeddings is True
    assert (cfg.d_model, cfg.num_heads, cfg.d_ff) == (4096, 32, 11008)
    # after --smoke, on the smoke variant
    smoke = resolve(["--arch", "deepseek_7b", "--smoke",
                     "--config-override", "d_ff=128"])
    assert (smoke.d_model, smoke.d_ff) == (256, 128)


def test_published_deepseek_norm_eps():
    assert resolve(["--arch", "deepseek_7b"]).norm_eps == 1e-6


@pytest.mark.parametrize("argv,message", [
    (["--arch", "deepseek_7b", "--config-override", "no_such_field=1"],
     "no field 'no_such_field'"),
    (["--arch", "deepseek_7b", "--config-override", "num_layers"],
     "KEY=VALUE"),
    (["--arch", "deepseek_7b", "--config-override", "num_layers=two"],
     "num_layers is int"),
    (["--arch", "deepseek_7b", "--config-override", "attention=dense"],
     "attention must be one of"),
    (["--arch", "mlp_svhn", "--config-override", "num_layers=2"],
     "not to --arch mlp_svhn"),
])
def test_trainer_refuses_bad_overrides(argv, message, capsys):
    """Refused up front by argparse (exit 2), naming the fault."""
    with pytest.raises(SystemExit) as e:
        train.main(argv + ["--steps", "1"])
    assert e.value.code == 2
    assert message in capsys.readouterr().err


def test_deepseek_cell_taps_all_take_gram(monkeypatch):
    """At the cell's shapes (S = 2048) every one of the 8 taps (wq, wk, wv,
    wo, w_in, w_gate, w_out, unembed) takes the Gram kernel on the TPU,
    and none in interpret mode."""
    cfg = resolve(["--arch", "deepseek_7b", *CUT])
    assert ghost_tap_paths(cfg, 2048) == {"gram": 0, "direct": 8}
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    assert ghost_tap_paths(cfg, 2048) == {"gram": 8, "direct": 0}
    # twice the sequence: the Gram path's S² outgrows the direct einsum
    assert ghost_tap_paths(cfg, 4096) == {"gram": 0, "direct": 8}


def test_square_taps_tie_goes_to_gram(monkeypatch):
    """For the 4096 x 4096 taps at S = 2048 the two FLOP counts are equal
    (2048 · 8192 = 4096²) and `uses_gram` picks the Gram kernel, as the
    inline choice it replaced did."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    s, d = 2048, 4096
    assert ops.ghost_cost(s, d, d) == ops.direct_cost(s, d, d)
    assert ops.uses_gram(s, d, d)
    assert not ops.uses_gram(s + 1, d, d)
    for s, din, dout in [(2048, 4096, 11008), (2048, 11008, 4096),
                         (2048, 4096, 12800), (512, 4096, 4096),
                         (4096, 4096, 4096), (64, 256, 512)]:
        inline = ops.ghost_cost(s, din, dout) <= ops.direct_cost(s, din, dout)
        assert ops.uses_gram(s, din, dout) == inline


def test_ghost_norm_lowers_to_the_chosen_path_alone():
    """The factoring adds nothing to `ghost_norm`'s program: on the CPU it
    lowers to exactly the direct einsum's."""
    from repro.kernels import ref
    x = jax.ShapeDtypeStruct((2, 16, 64), jax.numpy.float32)
    d = jax.ShapeDtypeStruct((2, 16, 128), jax.numpy.float32)
    assert not ops.uses_gram(16, 64, 128)

    def body(text):
        return [line for line in text.splitlines()
                if not line.startswith("module @")]
    got = body(ops.ghost_norm.lower(x, d).as_text())
    want = body(jax.jit(ref.ghost_norm_direct_ref).lower(x, d).as_text())
    assert got == want


def test_trainer_emits_tap_path_counters(tmp_path):
    """The LM ghost scorer's build emits `scorer.gram_taps` and
    `scorer.direct_taps` through the run's telemetry."""
    from repro.telemetry.events import read_events
    out = tmp_path / "m.jsonl"
    train.main(["--arch", "deepseek_7b", "--smoke",
                "--config-override", "d_model=64",
                "--config-override", "d_ff=128",
                "--config-override", "vocab_size=128",
                "--seq", "16", "--examples", "64", "--batch", "2",
                "--score-batch", "4", "--steps", "1",
                "--metrics-jsonl", str(out)])
    counters = {e["name"]: e["value"] for e in read_events(str(out))
                if e["kind"] == "counter"}
    assert counters["scorer.gram_taps"] == 0
    assert counters["scorer.direct_taps"] == 8
