"""The reduction from a profiler trace to per-layer metrics, on made-up
intervals and on a trace recorded on a TPU v5e: 20 steps of
`mlp_svhn.score_heavy` (python3 bench/run.py --workload
mlp_svhn.score_heavy --trace 1 --trace-dir ...)."""
import dataclasses
import gzip
import json

import pytest
from conftest import ROOT

from bench import run, trace

DATA = ROOT / "tests" / "bench" / "data"
RECORDED = json.loads((DATA / "score_heavy.json").read_text())


def ops(*spans):
    return [(f"op{i}", s, d, False) for i, (s, d) in enumerate(spans)]


@pytest.mark.parametrize("spans,busy,gaps,span", [
    ([(0, 10), (20, 5)], 15, [(10, 10)], 25),
    ([(0, 10), (5, 10)], 15, [], 15),                 # overlap counted once
    ([(0, 30), (5, 5), (40, 1)], 31, [(30, 10)], 41),  # nested op
    ([], 0, [], 0),
])
def test_busy_and_gaps(spans, busy, gaps, span):
    assert trace.busy_ns(ops(*spans)) == busy
    assert trace.idle_gaps(ops(*spans)) == gaps
    assert trace.span_ns(ops(*spans)) == span


def test_parse_flags():
    assert run.parse_flags(["--smoke", "--batch", "64", "--lr", "0.01",
                              "--arch", "mlp_svhn"]) == {
        "smoke": True, "batch": 64, "lr": 0.01, "arch": "mlp_svhn"}


def test_host_activity_is_innermost():
    host = [("main/1", "outer", 0, 100), ("main/1", "inner", 40, 20)]
    assert trace.host_activity(host, 50) == "main: inner"
    assert trace.host_activity(host, 10) == "main: outer"
    assert trace.host_activity(host, 500) == "host: no event"


@pytest.fixture(scope="module")
def ctx(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "score_heavy.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "score_heavy.xplane.pb.gz").read_bytes()))
    c = run.load_cell("mlp_svhn.score_heavy")
    return trace.Context(
        events=trace.load(str(path)), chips=1, steps=RECORDED["steps"],
        window_s=RECORDED["window_s"], cell=c["cell"], config=c["config"],
        trainer_flags=run.parse_flags(c["cell"]["flags"]),
        device_kind="TPU v5 lite",
        peaks=run.load_json(ROOT / "bench" / "peaks.json"),
        load_module=run.load_module)


def test_recorded_trace_has_one_chip(ctx):
    assert list(ctx.events["devices"]) == ["/device:TPU:0"]
    assert ctx.events["host"]
    assert 0 < ctx.busy_s < ctx.window_s


def test_kernel_found_by_name(ctx):
    seconds, launches = ctx.kernel_seconds("per_example_sqnorm_multi")
    assert launches == RECORDED["steps"]     # one launch a step
    assert 0 < seconds < ctx.busy_s
    assert ctx.kernel_seconds("no_such_kernel") == (0.0, 0)


@pytest.mark.parametrize("name", ["device_idle_share", "step_mfu",
                                  "sqnorm_multi_roofline"])
def test_readers_on_recorded_trace(ctx, name):
    value = run.load_module("metrics", name).read(ctx)
    assert 0 < value < 100


@pytest.mark.parametrize("name", ["device_idle_share", "step_mfu",
                                  "sqnorm_multi_roofline"])
def test_readers_give_what_the_recorded_run_printed(ctx, name):
    assert ctx.busy_s == pytest.approx(RECORDED["busy_s"], rel=1e-9)
    value = run.load_module("metrics", name).read(ctx)
    assert value == pytest.approx(RECORDED["metrics"][name], rel=1e-9)


def test_idle_share_is_one_minus_busy(ctx):
    value = run.load_module("metrics", "device_idle_share").read(ctx)
    assert value == pytest.approx(100 * (1 - ctx.busy_s / ctx.window_s))


def test_breakdown(ctx):
    b = ctx.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    times = [s for _, s in b["device_ops"]]
    assert times == sorted(times, reverse=True)
    assert all(isinstance(name, str) and s > 0 for name, s in b["idle_gaps"])


def test_unknown_device_is_an_error(ctx):
    other = dataclasses.replace(ctx, device_kind="TPU v9")
    with pytest.raises(KeyError, match="peaks.json"):
        other.peak("bf16_flops_per_s")
