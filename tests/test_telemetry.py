"""Telemetry (repro/telemetry/): monitors, events, spans, and the report.

The two contracts that make in-step monitors safe to ship on by default
are pinned here first: monitors OFF is the identity code path (the step's
HLO is byte-identical to a build that never heard of telemetry), and
monitors ON never perturbs the trajectory (bitwise-equal params/store
after N steps).  Then value correctness (every monitor against a numpy
brute force, ESS cross-checked against StepMetrics.ess_frac, entropy
against importance.proposal_entropy), the async staleness monitor
observing exactly the PR-2 lag L(t) = t − K⌊t/K⌋ + 1, mesh/single-device
agreement, and the non-blocking span contract: dispatch spans stay far
below the blocked phase wall-clock, the witness that instrumentation did
not re-serialize the scoring/master overlap.

Satellites: score_trace_metrics (NaN path, brute-force eqs. 6-9,
collective-freeness under a mesh) and tools/metrics_report.py
reproducing the √TrΣ trajectory from a run's JSONL.
"""
import json
import math
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _helpers import REPO, mesh_src, run_py as _run_py


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

def _setup(n=256, hidden=(32,), dim=16, batch=16, score_batch=64,
           smoothing=0.1):
    from repro.core.importance import ISConfig
    from repro.core.issgd import ISSGDConfig
    from repro.core.scorer import make_mlp_scorer
    from repro.data import make_svhn_like
    from repro.models.mlp import (MLPConfig, init_mlp_classifier,
                                  per_example_loss)
    from repro.optim import sgd

    cfg = MLPConfig(input_dim=dim, hidden=hidden, num_classes=4)
    train, _ = make_svhn_like(jax.random.key(0), n=n, dim=dim, classes=4)
    params = init_mlp_classifier(jax.random.key(1), cfg)
    opt = sgd(0.05)
    tcfg = ISSGDConfig(batch_size=batch, score_batch_size=score_batch,
                       mode="relaxed", is_cfg=ISConfig(smoothing=smoothing),
                       score_shards=4)
    pel = lambda p, b: per_example_loss(p, b, cfg)
    scorer = make_mlp_scorer(cfg, "ghost")
    return pel, scorer, opt, tcfg, params, train


# ---------------------------------------------------------------------------
# MonitorSet
# ---------------------------------------------------------------------------

def test_monitor_set_parse_and_validate():
    from repro.telemetry import MONITOR_NAMES, MonitorSet

    assert MonitorSet.parse("all").names == MONITOR_NAMES
    assert MonitorSet.parse("none").names == ()
    assert MonitorSet.parse("").names == ()
    assert MonitorSet.parse("off").names == ()
    # order-normalized regardless of spelling order
    assert MonitorSet.parse("staleness,ess").names == ("ess", "staleness")
    assert not MonitorSet(())          # falsy -> collapses to the off path
    assert MonitorSet(("ess",))
    assert (MonitorSet(()) or None) is None
    with pytest.raises(ValueError, match="unknown monitor"):
        MonitorSet.parse("ess,bogus")
    with pytest.raises(ValueError, match="unknown monitor"):
        MonitorSet(("bogus",))


# ---------------------------------------------------------------------------
# events + spans
# ---------------------------------------------------------------------------

def test_event_sink_roundtrip(tmp_path):
    from repro.telemetry import SCHEMA_VERSION, EventSink
    from repro.telemetry.events import read_events

    p = str(tmp_path / "run.jsonl")
    sink = EventSink(p, run={"arch": "mlp", "seed": 3}, flush_every=2)
    sink.span("scoring.dispatch", 0.0123, step=0)
    sink.counter("stream.hit_rate", 0.5, step=0)
    sink.emit("metrics", step=1, loss=float(np.float32(1.5)),
              idx=np.arange(2))
    sink.close()
    sink.close()   # idempotent

    recs = read_events(p)
    assert [r["kind"] for r in recs] == ["run", "span", "counter", "metrics"]
    assert all(r["v"] == SCHEMA_VERSION for r in recs)
    assert all("t" in r for r in recs)
    assert recs[0]["arch"] == "mlp"
    assert recs[1]["name"] == "scoring.dispatch"
    assert recs[1]["dur_s"] == pytest.approx(0.0123)
    assert recs[2]["value"] == 0.5
    assert recs[3]["loss"] == 1.5 and recs[3]["idx"] == [0, 1]

    # appended garbage is skipped, not fatal (crashed runs truncate lines)
    with open(p, "a") as f:
        f.write("{not json\n")
    assert len(read_events(p)) == 4


def test_null_sink_is_inert(tmp_path):
    from repro.telemetry import NullSink, Telemetry

    sink = NullSink()
    assert not sink
    sink.emit("metrics", loss=1.0)
    sink.span("x", 0.1)
    sink.counter("c", 1)
    sink.flush(), sink.close()
    assert sink.emitted == 0 and sink.path is None

    tel = Telemetry.null()
    assert not tel
    assert tel is Telemetry.null()     # shared instance
    assert tel.timed("x", lambda a: a + 1, 1) == 2   # annotates only
    with tel.span("y"):
        pass
    assert not tel.due(0)              # never due: nothing to emit into


def test_span_context_and_timed(tmp_path):
    """Both span forms write one record each into a truthy sink; `timed`
    returns the call's result without waiting on it."""
    from repro.telemetry import EventSink
    from repro.telemetry.events import read_events
    from repro.telemetry.spans import span, timed

    p = str(tmp_path / "s.jsonl")
    sink = EventSink(p)
    with span(sink, "serve.tick", step=4):
        time.sleep(0.01)
    out = timed(sink, "master.dispatch", jnp.square, jnp.float32(3.0),
                step=5)
    assert float(out) == 9.0
    sink.close()
    recs = [r for r in read_events(p) if r["kind"] == "span"]
    assert [r["name"] for r in recs] == ["serve.tick", "master.dispatch"]
    assert recs[0]["step"] == 4 and recs[1]["step"] == 5
    assert recs[0]["dur_s"] >= 0.01
    assert recs[1]["dur_s"] > 0


def test_null_sink_spans_reach_the_profiler(tmp_path):
    """With nothing to write into, spans still annotate: each lands in a
    profiler trace's host plane under its own name, with its step."""
    from jax.profiler import ProfileData
    from repro.telemetry import Telemetry

    tel = Telemetry.null()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tel.step(7):
            assert tel.timed("train.dispatch", jnp.square,
                             jnp.float32(2.0), step=7) is not None
            with tel.span("train.callback", step=7):
                pass
        with tel.span("store.publish"):
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
               for f in fs if f.endswith(".xplane.pb")]
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    got = {}
    for line in host.lines:
        for e in line.events:
            got[e.name] = {k: v for k, v in e.stats}
    assert got["train.dispatch"]["step"] == 7
    assert got["train.callback"]["step"] == 7
    assert got["train"]["step_num"] == 7
    assert "store.publish" in got


def test_span_names_are_the_taxonomy():
    """Every span name a call site passes is listed once in the taxonomy
    of telemetry/spans.py, which the benchmark's readers match on."""
    import re

    from repro.telemetry import spans

    src = os.path.join(REPO, "src", "repro")
    used = set()
    for d, _, files in os.walk(src):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    used |= set(re.findall(
                        r"\.(?:span|timed)\(\s*\"([a-z_]+\.[a-z_]+)\"",
                        fh.read()))
    assert {"train.dispatch", "train.callback", "train.log_sync",
            "train.probe", "scoring.dispatch", "master.dispatch"} <= used
    for name in used:
        assert spans.__doc__.count(f"``{name}``") == 1, name


def test_step_phases_are_named_scopes():
    """Each phase of the fused train step carries its `issgd.*` scope in
    the lowered program's op metadata (the names XProf shows per op)."""
    from repro.core.issgd import init_train_state, make_train_step

    pel, scorer, opt, tcfg, params, train = _setup()
    state = init_train_state(params, opt, train.size, seed=0)
    step = make_train_step(pel, scorer, opt, tcfg, train.size)
    text = jax.jit(step).lower(state, train.arrays).as_text(debug_info=True)
    for phase in ("score", "proposal", "sample", "update", "push",
                  "monitors"):
        assert f"/issgd.{phase}/" in text, phase


# ---------------------------------------------------------------------------
# the two safety contracts
# ---------------------------------------------------------------------------

def test_monitors_off_is_hlo_identical():
    """A monitors-off build compiles to the byte-identical program of a
    build that never passed the kwarg — the gate that telemetry costs
    nothing when unused."""
    from repro.core.issgd import init_train_state, make_train_step
    from repro.telemetry import MonitorSet

    pel, scorer, opt, tcfg, params, train = _setup()
    state = init_train_state(params, opt, train.size, seed=0)

    def lowered(**kw):
        step = make_train_step(pel, scorer, opt, tcfg, train.size, **kw)
        return jax.jit(step).lower(state, train.arrays).as_text()

    base = lowered()
    assert lowered(monitors=None) == base
    assert lowered(monitors=MonitorSet(())) == base


def test_monitors_on_is_bitwise_noninvasive():
    """Enabling every monitor adds outputs but never changes the
    trajectory: params, store, and metrics stay bitwise equal."""
    from repro.core.issgd import init_train_state, make_train_step
    from repro.telemetry import MONITOR_NAMES, MonitorSet

    pel, scorer, opt, tcfg, params, train = _setup()
    plain = jax.jit(make_train_step(pel, scorer, opt, tcfg, train.size))
    mon_step = make_train_step(pel, scorer, opt, tcfg, train.size,
                               monitors=MonitorSet.all())
    assert mon_step.with_monitors
    mon_step = jax.jit(mon_step)

    s_a = init_train_state(params, opt, train.size, seed=0)
    s_b = init_train_state(params, opt, train.size, seed=0)
    for _ in range(6):
        s_a, m_a = plain(s_a, train.arrays)
        s_b, m_b, mon = mon_step(s_b, train.arrays)
    assert set(mon) == set(MONITOR_NAMES)
    s_a = s_a._replace(rng=jax.random.key_data(s_a.rng))
    s_b = s_b._replace(rng=jax.random.key_data(s_b.rng))
    for a, b in zip(jax.tree.leaves(s_a), jax.tree.leaves(s_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(m_a.loss), np.asarray(m_b.loss))


# ---------------------------------------------------------------------------
# monitor values
# ---------------------------------------------------------------------------

def test_monitor_values_match_brute_force():
    """Each monitor against a numpy reference computed from the exact
    proposal the master sampled from (the untouched read_buf of an async
    step), plus cross-checks against the repo's own ESS / entropy
    helpers and StepMetrics.ess_frac."""
    from repro.core.async_pipeline import init_async_state, make_async_steps
    from repro.core.importance import proposal_entropy
    from repro.core.weight_store import read_proposal
    from repro.telemetry import MonitorSet

    pel, scorer, opt, tcfg, params, train = _setup()
    _, master = make_async_steps(pel, scorer, opt, tcfg, train.size,
                                 monitors=MonitorSet.all())
    assert master.with_monitors
    state = init_async_state(params, opt, train.size, seed=0)
    read_buf = state.store.read_buf

    *_, metrics, mon = jax.jit(master)(
        state.params, state.opt_state, state.stale_params, read_buf,
        state.step, state.rng, train.arrays)

    w = np.asarray(read_proposal(read_buf, state.step, tcfg.is_cfg),
                   np.float64)
    n = train.size
    ess_ref = (w.sum() ** 2 / (w ** 2).sum()) / n
    wn = w / w.sum()
    ent_ref = -(wn[wn > 0] * np.log(wn[wn > 0])).sum()
    assert float(mon["ess"]) == pytest.approx(ess_ref, rel=1e-5)
    assert float(mon["entropy"]) == pytest.approx(ent_ref, rel=1e-5)
    assert float(mon["entropy"]) == pytest.approx(
        float(proposal_entropy(jnp.asarray(w, jnp.float32))), rel=1e-5)
    assert float(mon["max_weight_frac"]) == pytest.approx(
        w.max() / w.sum(), rel=1e-5)
    assert int(mon["empty_rows"]) == 0
    # cold store: scored_at == -1 everywhere -> staleness = step + 1
    assert int(mon["staleness"]) == 1
    # the same proposal's ESS/N is already a StepMetrics field — agree
    assert float(mon["ess"]) == pytest.approx(float(metrics.ess_frac),
                                              rel=1e-6)


def test_empty_rows_counts_reserved_capacity():
    """The empty_rows monitor counts exactly the EMPTY-reserved serving
    rows, which carry zero proposal mass."""
    from repro.core.async_pipeline import init_async_state, make_async_steps
    from repro.core.weight_store import reserve_tail
    from repro.telemetry import MonitorSet

    pel, scorer, opt, tcfg, params, train = _setup()
    _, master = make_async_steps(pel, scorer, opt, tcfg, train.size,
                                 monitors=MonitorSet(("empty_rows", "ess")))
    state = init_async_state(params, opt, train.size, seed=0)
    n_live = train.size - 32
    rb = reserve_tail(state.store.read_buf, n_live)

    *_, mon = jax.jit(master)(
        state.params, state.opt_state, state.stale_params, rb, state.step,
        state.rng, train.arrays)
    assert int(mon["empty_rows"]) == 32
    # reserved rows are proposal-invisible: ESS is over the live mass only
    assert float(mon["ess"]) == pytest.approx(n_live / train.size, rel=1e-5)


@pytest.mark.parametrize("swap_every", [1, 3])
def test_async_staleness_monitor_observes_lag(swap_every):
    """The staleness monitor reads L(t) = t − K⌊t/K⌋ + 1 right off the
    read_buf the master sampled from — the PR-2 invariant, now observable
    per step from telemetry instead of only provable in tests."""
    from repro.core.async_pipeline import (AsyncPipeline, init_async_state,
                                           make_async_steps)
    from repro.telemetry import MonitorSet

    pel, scorer, opt, tcfg, params, train = _setup()
    s_step, m_step = make_async_steps(
        pel, scorer, opt, tcfg, train.size,
        monitors=MonitorSet(("staleness",)))
    pipe = AsyncPipeline(s_step, m_step, swap_every)
    state = init_async_state(params, opt, train.size, seed=0)
    K = swap_every
    for t in range(3 * K + 2):
        state, _ = pipe.step(state, train.arrays)
        assert int(pipe.last_monitors["staleness"]) == t - K * (t // K) + 1


def test_mesh_monitors_match_single_device():
    """Monitor scalars psum/pmax to globals: a mesh-4 run reports the
    same values (to float tolerance) as the single-device build."""
    code = """
        import jax, numpy as np
        from repro.core import distributed as D
        from repro.core.issgd import init_train_state, make_train_step
        from repro.telemetry import MonitorSet
        import sys; sys.path.insert(0, "tests")
        from test_telemetry import _setup

        pel, scorer, opt, tcfg, params, train = _setup()
        state = init_train_state(params, opt, train.size, seed=0)

        ref_step = jax.jit(make_train_step(
            pel, scorer, opt, tcfg, train.size, monitors=MonitorSet.all()))
        _, _, ref = ref_step(state, train.arrays)

        %s
        step4, tcfg4 = D.make_sharded_train_step(
            pel, scorer, opt, tcfg, train.size, mesh, train.arrays,
            monitors=MonitorSet.all())
        assert step4.with_monitors
        st4 = D.shard_train_state(state, mesh)
        d4 = D.shard_dataset(train.arrays, mesh)
        _, _, mon = jax.jit(step4)(st4, d4)
        for k in ref:
            np.testing.assert_allclose(np.asarray(mon[k]),
                                       np.asarray(ref[k]), rtol=1e-5)
        print("MESH_MONITORS_OK")
    """ % mesh_src(4)
    assert "MESH_MONITORS_OK" in _run_py(code, devices=4)


# ---------------------------------------------------------------------------
# the overlap witness
# ---------------------------------------------------------------------------

def test_async_dispatch_spans_witness_overlap(tmp_path):
    """Non-blocking spans time only dispatch: with a deliberately heavy
    scoring computation, the recorded scoring.dispatch span must be far
    below the phase's blocked wall-clock — proof the master was dispatched
    while scoring was still in flight (instrumentation did not
    re-serialize the PR-2 overlap)."""
    from repro.core.async_pipeline import (AsyncPipeline, init_async_state,
                                           make_async_steps)
    from repro.telemetry import EventSink, Telemetry
    from repro.telemetry.events import read_events

    pel, scorer, opt, tcfg, params, train = _setup(
        n=4096, hidden=(256, 256), dim=64, score_batch=1024)
    s_step, m_step = make_async_steps(pel, scorer, opt, tcfg, train.size)

    p = str(tmp_path / "spans.jsonl")
    tel = Telemetry(EventSink(p), every=1)
    pipe = AsyncPipeline(s_step, m_step, telemetry=tel)
    state = init_async_state(params, opt, train.size, seed=0)

    state, m = pipe.step(state, train.arrays)     # warm-up / compile
    jax.block_until_ready((state.params, m))
    # blocked wall-clock of one scoring dispatch, measured directly
    t0 = time.perf_counter()
    out = pipe._scoring(state.stale_params, state.store.write_buf,
                        state.step, train.arrays)
    jax.block_until_ready(out)
    t_block = time.perf_counter() - t0
    # rebuild: the measurement above consumed the donated write_buf
    state = init_async_state(params, opt, train.size, seed=0)
    for _ in range(3):
        state, m = pipe.step(state, train.arrays)
    jax.block_until_ready((state.params, m))
    tel.sink.close()

    spans = [r["dur_s"] for r in read_events(p)
             if r["kind"] == "span" and r["name"] == "scoring.dispatch"]
    assert len(spans) == 4
    # post-warm-up dispatches return long before the compute finishes
    assert min(spans[1:]) < 0.5 * t_block, (spans, t_block)


# ---------------------------------------------------------------------------
# score_trace_metrics satellites
# ---------------------------------------------------------------------------

def test_score_trace_metrics_monitor_false_is_nan():
    from repro.core.async_pipeline import score_trace_metrics

    g = jnp.abs(jax.random.normal(jax.random.key(0), (64,)))
    w = jnp.abs(jax.random.normal(jax.random.key(1), (64,))) + 0.1
    sm = score_trace_metrics(g, w, axes=(), n_total=64, monitor=False)
    assert all(math.isnan(float(v)) for v in sm)


def test_score_trace_metrics_matches_brute_force():
    """√TrΣ against the eq. 6-9 formulas in float64 numpy."""
    from repro.core.async_pipeline import score_trace_metrics

    rng = np.random.default_rng(0)
    g = np.abs(rng.normal(size=(128,))).astype(np.float32)
    w = (np.abs(rng.normal(size=(128,))) + 0.1).astype(np.float32)
    sm = score_trace_metrics(jnp.asarray(g), jnp.asarray(w), axes=(),
                             n_total=128)
    g64, w64 = g.astype(np.float64), w.astype(np.float64)
    ideal = g64.mean() ** 2
    stale = w64.mean() * (g64 ** 2 / w64).mean()
    unif = (g64 ** 2).mean()
    assert float(sm.trace_ideal) == pytest.approx(math.sqrt(ideal), rel=1e-5)
    assert float(sm.trace_stale) == pytest.approx(math.sqrt(stale), rel=1e-5)
    assert float(sm.trace_unif) == pytest.approx(math.sqrt(unif), rel=1e-5)


def test_score_trace_metrics_collectives_under_mesh():
    """Under shard_map the monitored build psums (all-reduce in the HLO);
    monitor=False lowers collective-free — the async scoring step can
    stay rendezvous-free when traces are off."""
    code = """
        import jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.core.async_pipeline import ScoreMetrics, score_trace_metrics
        from repro.dist import shard_map
        %s

        g = jnp.abs(jax.random.normal(jax.random.key(0), (256,)))
        w = jnp.abs(jax.random.normal(jax.random.key(1), (256,))) + 0.1

        def lowered(monitor):
            f = shard_map(
                partial(score_trace_metrics, axes=("data",), n_total=256,
                        monitor=monitor),
                mesh=mesh, in_specs=(P("data"), P("data")),
                out_specs=ScoreMetrics(P(), P(), P()))
            return jax.jit(f).lower(g, w).compile().as_text()

        assert "all-reduce" in lowered(True)
        assert "all-reduce" not in lowered(False)
        print("TRACE_COLLECTIVES_OK")
    """ % mesh_src(4)
    assert "TRACE_COLLECTIVES_OK" in _run_py(code, devices=4)


# ---------------------------------------------------------------------------
# metrics_report
# ---------------------------------------------------------------------------

def _run_report(jsonl, out_json):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "metrics_report.py"),
         jsonl, "--json", out_json],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout


def test_metrics_report_reproduces_trajectory(tmp_path):
    """The report's --json trajectory is exactly the metrics records of
    the event stream, and the rendered text carries the fig-4 table."""
    from repro.telemetry import EventSink

    p = str(tmp_path / "run.jsonl")
    sink = EventSink(p, run={"arch": "mlp_svhn", "mode": "relaxed"})
    expect = []
    for i, t in enumerate(range(0, 30, 10)):
        row = {"step": t, "trace_ideal": 10.0 - i, "trace_stale": 11.0 - i,
               "trace_unif": 12.0 - i, "loss": 2.0 / (i + 1)}
        expect.append(row)
        sink.emit("metrics", step=t,
                  **{k: v for k, v in row.items() if k != "step"})
        sink.emit("monitors", step=t, ess=0.5 + 0.1 * i, staleness=1)
    sink.span("scoring.dispatch", 0.004, step=0)
    sink.counter("store.swaps", 3, step=20)
    sink.emit("run_end", step=20, steps=21)
    sink.close()

    out_json = str(tmp_path / "summary.json")
    text = _run_report(p, out_json)
    with open(out_json) as f:
        summary = json.load(f)
    assert summary["trajectory"] == expect
    assert summary["spans"]["scoring.dispatch"]["count"] == 1
    assert summary["counters"]["store.swaps"] == 3
    assert summary["monitors"]["ess"] == [0.5, 0.6, 0.7]
    assert summary["run"]["arch"] == "mlp_svhn"
    assert "√TrΣ trajectory" in text and "scoring.dispatch" in text


@pytest.mark.slow
def test_train_cli_telemetry_end_to_end(tmp_path):
    """train.py --metrics-jsonl + --monitors all, then metrics_report:
    the reported √TrΣ trajectory is the run's own metrics records, and
    span + monitor events are present (the CI smoke greps the same)."""
    jsonl = str(tmp_path / "run.jsonl")
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "mlp_svhn",
         "--smoke", "--steps", "8", "--examples", "256", "--batch", "8",
         "--score-batch", "32", "--log-every", "4", "--monitors", "all",
         "--async-scoring", "--swap-every", "2",
         "--metrics-jsonl", jsonl],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=560)
    assert r.returncode == 0, r.stderr[-3000:]

    from repro.telemetry.events import read_events
    recs = read_events(jsonl)
    kinds = {x["kind"] for x in recs}
    assert {"run", "span", "counter", "metrics", "monitors",
            "run_end"} <= kinds
    mets = [x for x in recs if x["kind"] == "metrics"]

    out_json = str(tmp_path / "summary.json")
    _run_report(jsonl, out_json)
    with open(out_json) as f:
        summary = json.load(f)
    assert [row["step"] for row in summary["trajectory"]] == \
        [m["step"] for m in mets]
    for row, m in zip(summary["trajectory"], mets):
        for f_ in ("trace_ideal", "trace_stale", "trace_unif", "loss"):
            assert row[f_] == m[f_]
    mons = [x for x in recs if x["kind"] == "monitors"]
    assert all(x["staleness"] >= 1 for x in mons)   # async: always lagged
    assert summary["spans"]["scoring.dispatch"]["count"] == 8
