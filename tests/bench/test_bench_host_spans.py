"""The host-loop metrics (`host_dispatch_us`, `host_sync_us`,
`idle_in_dispatch_share`): the trainer's spans reach a profiler trace on
the host plane, and the readers reduce them as defined, on made-up
intervals and on traces recorded on a TPU v5e."""
import gzip
import json

import pytest
from conftest import ROOT

from bench import program_spans, run, trace

DATA = ROOT / "tests" / "bench" / "data"
READERS = ["host_dispatch_us", "host_sync_us", "idle_in_dispatch_share"]


def context(host, device_ops=(), steps=1, window_s=1.0, path=None):
    c = run.load_cell("mlp_svhn.score_heavy")
    events = (trace.load(str(path)) if path else
              {"devices": {"/device:TPU:0": [
                  (f"op{i}", s, d, False)
                  for i, (s, d) in enumerate(device_ops)]},
               "host": [("python3/1", n, s, d) for n, s, d in host]})
    return trace.Context(
        events=events, chips=1, steps=steps, window_s=window_s,
        cell=c["cell"], config=c["config"],
        trainer_flags=run.parse_flags(c["cell"]["flags"]),
        device_kind="TPU v5 lite",
        peaks=run.load_json(ROOT / "bench" / "peaks.json"),
        load_module=run.load_module)


def read(name, ctx):
    return run.load_module("metrics", name).read(ctx)


@pytest.mark.parametrize("gaps,spans,covered", [
    ([(10, 20)], [(5, 25)], 15),                 # a span over a gap's start
    ([(10, 20)], [(20, 40)], 10),                # over a gap's end
    ([(10, 20)], [(0, 50)], 20),                 # over the whole gap
    ([(10, 20)], [(12, 18)], 6),                 # inside the gap
    ([(10, 20)], [(12, 18), (15, 25)], 13),      # overlapping: counted once
    ([(10, 10), (40, 10)], [(15, 45)], 10),      # one span over two gaps
    ([(10, 10)], [(0, 10), (20, 30)], 0),        # touching, not covering
    ([], [(0, 10)], 0),
])
def test_covered_ns(gaps, spans, covered):
    assert program_spans.covered_ns(gaps, spans) == covered


# Device ops at [0, 10], [30, 40], [70, 80] us: idle [10, 30] and [40, 70],
# 50 us in all.  Dispatch spans [5, 25] (15 us of the first gap, partly
# over an op), [50, 60] (10 us of the second) and [72, 78] (an op runs
# through it): 25 us of 50 covered.  Two log syncs of 3 and 5 us.
US = 1000
OPS = [(0, 10 * US), (30 * US, 10 * US), (70 * US, 10 * US)]
HOST = [("train.dispatch", 5 * US, 20 * US),
        ("train.dispatch", 50 * US, 10 * US),
        ("train.dispatch", 72 * US, 6 * US),
        ("train.log_sync", 26 * US, 3 * US),
        ("train.log_sync", 62 * US, 5 * US),
        ("train.callback", 10 * US, 50 * US),   # another span: not read
        ("PjitFunction(train_step)", 6 * US, 18 * US)]


@pytest.mark.parametrize("name,value", [
    ("host_dispatch_us", (20 + 10 + 6) / 3),
    ("host_sync_us", (3 + 5) / 3),
    ("idle_in_dispatch_share", 100 * 25 / 50),
])
def test_readers_on_made_up_intervals(name, value):
    ctx = context(HOST, OPS, steps=3)
    assert read(name, ctx) == pytest.approx(value, rel=1e-12)


@pytest.mark.parametrize("name", READERS)
def test_readers_without_spans_give_none(name):
    other = [h for h in HOST if not h[0].startswith("train.")]
    assert read(name, context(other, OPS, steps=3)) is None


def test_idle_share_without_idle_gaps_is_none():
    assert read("idle_in_dispatch_share",
                context(HOST, [(0, 100 * US)], steps=3)) is None


def recorded(tmp_path_factory, stem):
    """A recorded trace, decompressed, in the context of its run."""
    printed = json.loads((DATA / f"{stem}.json").read_text())
    path = tmp_path_factory.mktemp("trace") / f"{stem}.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / f"{stem}.xplane.pb.gz").read_bytes()))
    return printed, context(None, steps=printed["steps"],
                            window_s=printed["window_s"], path=path)


@pytest.fixture(scope="module")
def spanless_run(tmp_path_factory):
    """The first recorded trace, of a program without spans."""
    return recorded(tmp_path_factory, "score_heavy")


@pytest.fixture(scope="module")
def spans_run(tmp_path_factory):
    return recorded(tmp_path_factory, "score_heavy_spans")


@pytest.mark.parametrize("name", READERS)
def test_readers_give_none_on_a_trace_without_program_spans(spanless_run,
                                                            name):
    _, ctx = spanless_run
    assert ctx.busy_s > 0
    assert read(name, ctx) is None


@pytest.mark.parametrize("name", READERS + [
    "device_idle_share", "step_mfu", "sqnorm_multi_roofline"])
def test_readers_give_what_the_recorded_spans_run_printed(spans_run, name):
    printed, ctx = spans_run
    assert ctx.busy_s == pytest.approx(printed["busy_s"], rel=1e-9)
    assert read(name, ctx) == pytest.approx(printed["metrics"][name],
                                            rel=1e-9)


def test_recorded_spans_run_is_consistent(spans_run):
    """One dispatch a traced step, the host-loop spans within the traced
    window, and a share in (0, 100]."""
    printed, ctx = spans_run
    dispatches = program_spans.intervals(ctx, program_spans.DISPATCH)
    assert len(dispatches) == ctx.steps
    per_step_us = ctx.window_s / ctx.steps * 1e6
    m = printed["metrics"]
    assert m["host_dispatch_us"] + m["host_sync_us"] <= per_step_us
    assert 0 < m["idle_in_dispatch_share"] <= 100


def test_trainer_spans_reach_the_host_plane(tmp_path):
    """`train.main` with no sink (`--metrics-jsonl` absent) and a
    `--profile-dir` window of three steps: each profiled step has one
    dispatch and one callback span carrying its step, and the logging
    step its log sync, on `/host:CPU`."""
    from jax.profiler import ProfileData
    from repro.launch import train

    seen = []
    train.main(["--arch", "mlp_svhn", "--smoke", "--steps", "5",
                "--examples", "256", "--batch", "8", "--score-batch", "32",
                "--log-every", "2", "--profile-dir", str(tmp_path),
                "--profile-steps", "1:3"],
               on_step=lambda i, state, m: seen.append(i))
    assert seen == [0, 1, 2, 3, 4]
    events = trace.load(str(tmp_path))
    names = [name for _, name, _, _ in events["host"]]
    assert names.count("train.dispatch") == 3
    assert names.count("train.callback") == 3
    assert names.count("train.log_sync") == 1        # step 2 of 1..3

    data = ProfileData.from_file(trace.xplane_file(str(tmp_path)))
    host = next(p for p in data.planes if p.name == trace.HOST_PLANE)
    steps = {}
    for line in host.lines:
        for e in line.events:
            if e.name.startswith("train"):
                stats = dict(e.stats)
                key = "step_num" if e.name == "train" else "step"
                steps.setdefault(e.name, []).append(int(stats[key]))
    assert sorted(steps["train.dispatch"]) == [1, 2, 3]
    assert sorted(steps["train.callback"]) == [1, 2, 3]
    assert steps["train.log_sync"] == [2]
    assert sorted(steps["train"]) == [1, 2, 3]       # step annotations
