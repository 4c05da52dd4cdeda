#!/usr/bin/env python3
"""Benchmark harness: one cell of BENCHMARK.json on the chips of this host.

  python3 bench/run.py --workload mlp_svhn.score_heavy --seed 7 \
      --seconds 10 --trace 0

Each run drives the trainer's own entry point, `repro.launch.train.main`,
in this process with the cell's trainer flags.  A step callback lets the
cell's warm-up steps pass (set-up), blocks once and starts the clock,
never blocks inside the window (the trainer's own log sync stays), and
after `--seconds` blocks once more and ends the run.  With `--trace 1` a
short part of the window is traced by `jax.profiler` and the cell's
per-layer metrics are read from the trace; with `--trace 0` the
end-to-end metrics are printed.  Either way the first steps, which ran
through the window's own call, up to and including the first push of
θ_stale, are compared with a plain float32 reference once the window has
closed and the program's state is freed.

Everything a cell, a configuration or a per-layer metric needs lives in
files found by name: `bench/workloads/<cell>.json`, the configuration's
file named in BENCHMARK.json, `bench/reference/<config>.py` and
`bench/metrics/<metric>.py`.  Without a TPU, or with fewer chips than the
cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CHECK_STEPS = 3     # steps whose loss, scores and change are compared


class BenchError(Exception):
    """A cell that cannot be run as its files describe."""


class StopWindow(Exception):
    """Raised from the step callback to end the trainer's loop."""


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """`bench/<kind>/<name>.py` as a module (a metric reader, a cost
    model, a reference); None when there is no such file."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        return None
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str) -> dict:
    """The manifest entry, the cell's file and its configuration."""
    manifest = load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in manifest["configs"]
                if c["name"] == entry["config"])
    return {"manifest": manifest, "entry": entry,
            "cell": load_json(BENCH / "workloads" / f"{name}.json"),
            "config": load_json(ROOT / conf["file"])}


def parse_flags(flags: list) -> dict:
    """`["--score-batch", "256", ...]` -> `{"score_batch": 256, ...}`."""
    out = {}
    for i, f in enumerate(flags):
        if not f.startswith("--"):
            continue
        val = flags[i + 1] if i + 1 < len(flags) else None
        key = f[2:].replace("-", "_")
        if val is None or val.startswith("--"):
            out[key] = True
            continue
        for cast in (int, float, str):
            try:
                out[key] = cast(val)
                break
            except ValueError:
                pass
    return out


# flags the harness and the references read; a cell states each of them
STATED = ("batch", "score_batch", "examples", "lr", "refresh_every",
          "smoothing")


def listed(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


class CompileCounter:
    """Counts XLA backend compiles, so a compile inside the window shows."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1


class Window:
    """The trainer's step callback: warm-up, the measured window, the
    traced part of it, and the readings the correctness check needs."""

    def __init__(self, jax, cell: dict, tf: dict, seconds: float,
                 trace_dir: str | None, compiles: CompileCounter):
        self.jax = jax
        self.warmup = int(cell["warmup_steps"])
        if tf["refresh_every"] <= CHECK_STEPS:
            raise BenchError("--refresh-every must exceed the checked "
                             "steps: θ_stale holds θ₀ through them")
        # the reference follows the steps up to the first push of θ_stale
        self.followed = tf["refresh_every"]
        if self.warmup < self.followed:
            raise BenchError("warmup_steps must reach the first push of "
                             "θ_stale (--refresh-every)")
        self.inv_lr = 1.0 / tf["lr"]
        self.scored_rows = CHECK_STEPS * tf["score_batch"]
        self.vrange = cell.get("variance_steps")
        self.trace_at = cell["trace_steps"] if trace_dir else None
        self.trace_dir = trace_dir
        self.seconds = seconds
        self.compiles = compiles
        self.stamps: list = []      # host time at each step of the window
        self.check = {"loss": [], "indices": []}
        self.variance: list = []
        self.window_losses: list = []
        self.steps = 0
        self.t0 = self.t1 = None
        self.traced = None          # (steps, seconds) of the traced part
        self.closed = False
        self.compiles_in_window = 0
        from bench.reference.issgd import leaf_norms
        self.leaf_norms = leaf_norms

    def _block(self, state):
        self.jax.block_until_ready(state)
        return time.perf_counter()

    def __call__(self, i, state, m):
        if i == 0:
            self.t_first = time.perf_counter()
        if i < self.followed:
            self.check["indices"].append(m.sample_indices)
        if i < CHECK_STEPS:
            self.check["loss"].append(m.loss)
            if i == 0:   # θ_stale is θ₀: the first gradient, as SGD got it
                self.theta0 = state.stale_params
                self.check["grad0"] = self.leaf_norms(
                    state.stale_params, state.params, self.inv_lr)
            if i == CHECK_STEPS - 1:
                self.check["change"] = self.leaf_norms(
                    state.params, state.stale_params, 1.0)
                self.check["scores"] = state.store.weights[
                    :self.scored_rows]
        if i == self.followed - 1:   # step K−1 pushed θ_K to θ_stale
            self.check["stale"] = self.leaf_norms(
                state.stale_params, self.theta0, 1.0)
            del self.theta0
        if self.vrange and self.vrange[0] <= i < self.vrange[1]:
            self.variance.append((m.trace_stale, m.trace_unif))
        if i == self.warmup - 1:
            self.t0 = self._block(state)
            self.compiles_at_start = self.compiles.count
        elif i >= self.warmup and not self.closed:
            self.steps += 1
            self.window_losses.append(m.loss)
            if self.trace_at:
                self._trace(state)
            now = time.perf_counter()
            self.stamps.append(now)
            if now - self.t0 >= self.seconds:
                self.t1 = self._block(state)
                self.closed = True
                self.compiles_in_window = (self.compiles.count
                                           - self.compiles_at_start)
        if self.closed and (not self.vrange or i + 1 >= self.vrange[1]):
            raise StopWindow

    def quarters(self) -> list:
        """Steps a second in each quarter of the window, by the host's
        stamps: a drift inside a run shows here."""
        out, edge = [], self.t0
        for q in range(1, 5):
            stop = self.t0 + q * (self.t1 - self.t0) / 4
            n = sum(1 for t in self.stamps if edge < t <= stop)
            out.append(n / (stop - edge))
            edge = stop
        return out

    def longest_gap(self) -> tuple[float, int]:
        """The longest time between two steps' callbacks in the window, and
        the trainer's step that ended it: a stall of the host shows here."""
        t = [self.t0, *self.stamps]
        k = max(range(1, len(t)), key=lambda j: t[j] - t[j - 1])
        return t[k] - t[k - 1], self.warmup + k - 1

    def _trace(self, state):
        start, count = self.trace_at
        if self.steps == start:
            self._block(state)
            opts = self.jax.profiler.ProfileOptions()
            # host TraceMe events name the idle gaps; the Python tracer
            # would slow the very host loop the trace measures
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            self.jax.profiler.start_trace(self.trace_dir,
                                          profiler_options=opts)
            self.trace_t0 = time.perf_counter()
        elif self.steps == start + count:
            t = self._block(state)
            self.jax.profiler.stop_trace()
            self.traced = (count, t - self.trace_t0)


def device_info(jax) -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def peak_bytes(jax, n_chips: int) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:n_chips])


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, loaded: dict | None = None,
             trace_dir: str | None = None) -> dict:
    """One run of one cell; returns the result line as a dict.  Tests
    pass `loaded` (a smoke-size cell) and `require_tpu=False`."""
    import jax
    import numpy as np
    from bench.reference import issgd

    c = loaded or load_cell(workload)
    entry, cell, config = c["entry"], c["cell"], c["config"]
    dev = device_info(jax)
    t_devices = time.perf_counter()
    if require_tpu and dev["platform"] != "tpu":
        raise BenchError(f"no TPU: JAX found {dev['count']} "
                         f"{dev['platform']} device(s); this benchmark "
                         f"never falls back to another platform")
    if dev["count"] < entry["chips"]:
        raise BenchError(f"{workload} needs {entry['chips']} chips, JAX "
                         f"found {dev['count']}")
    flags = list(cell["flags"])
    tf = parse_flags(flags)
    missing = [k for k in STATED if k not in tf]
    if missing:
        raise BenchError(f"the cell's flags do not state {missing}")
    argv = flags + ["--seed", str(seed), "--steps", str(10 ** 9)]
    own_trace_dir = trace and trace_dir is None
    if own_trace_dir:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    compiles = CompileCounter(jax)
    win = Window(jax, cell, tf, seconds, trace_dir if trace else None,
                 compiles)

    from repro.launch import train
    try:
        train.main(argv, on_step=win)
    except StopWindow:
        pass
    else:
        raise BenchError("the trainer stopped before the window closed")
    setup_s = win.t0 - T_START
    memory_peak = peak_bytes(jax, entry["chips"])
    gc.collect()

    window_s = win.t1 - win.t0
    batch = tf["batch"]
    losses = np.asarray(jax.device_get(win.window_losses))
    failed = int(np.sum(~np.isfinite(losses)))
    record = {
        "loss": [float(x) for x in jax.device_get(win.check["loss"])],
        "indices": [np.asarray(x) for x in
                    jax.device_get(win.check["indices"])],
        "grad0": issgd.floats(win.check["grad0"]),
        "change": issgd.floats(win.check["change"]),
        "stale": issgd.floats(win.check["stale"]),
        "scores": np.asarray(jax.device_get(win.check["scores"])),
    }
    variance = [tuple(map(float, v)) for v in jax.device_get(win.variance)]
    del win.check, win.variance, win.window_losses
    gc.collect()

    names = [m["name"] for m in c["manifest"]["end_to_end"]]
    metrics = {}
    result_device = dict(dev, memory_peak_bytes=memory_peak)
    breakdown = None
    if not trace:
        e2e = {"examples_per_s": (batch * win.steps / window_s,
                                  "examples/s"),
               "setup_s": (setup_s, "s")}
        for m in c["manifest"]["end_to_end"]:
            if listed(m, workload) and m["name"] in e2e:
                v, unit = e2e[m["name"]]
                metrics[m["name"]] = {"value": v, "unit": unit}
        missing = [n for n in names if n not in e2e]
        if missing:
            raise BenchError(f"no harness formula for {missing}")
    else:
        if win.traced is None:
            raise BenchError("the window closed before the traced steps "
                             "ran: lengthen --seconds")
        from bench import trace as trace_mod
        events = trace_mod.load(trace_dir)
        if own_trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = trace_mod.Context(
            events=events, chips=entry["chips"], steps=win.traced[0],
            window_s=win.traced[1], cell=cell, config=config,
            trainer_flags=tf,
            device_kind=dev["kind"], variance=variance,
            peaks=load_json(BENCH / "peaks.json"), load_module=load_module)
        result_device.update(busy_s=ctx.busy_s, window_s=ctx.window_s)
        breakdown = ctx.breakdown()
        for m in c["manifest"]["per_layer"]:
            if not listed(m, workload):
                continue
            reader = load_module("metrics", m["name"])
            if reader is None:
                raise BenchError(f"no reader bench/metrics/{m['name']}.py")
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    t_ref = time.perf_counter()
    model = load_module("reference", config["name"]).model(config)
    checks = issgd.check(model, record, config, tf, seed)
    print(f"bench: set-up {setup_s:.3f} s, window {window_s:.3f} s, "
          f"to the window's close and the last variance step "
          f"{t_ref - win.t1:.3f} s, reference "
          f"{time.perf_counter() - t_ref:.3f} s", file=sys.stderr)
    print(f"bench: set-up parts: JAX and its devices "
          f"{t_devices - T_START:.3f} s, the trainer to its first step "
          f"{win.t_first - t_devices:.3f} s, the other warm-up steps "
          f"{win.t0 - win.t_first:.3f} s", file=sys.stderr)
    gap, at = win.longest_gap()
    print(f"bench: steps/s by quarter of the window "
          f"{[round(r, 1) for r in win.quarters()]}; longest time between "
          f"two steps {gap:.4f} s, before step {at}", file=sys.stderr)
    correct = failed == 0 and all(
        math.isfinite(ch["value"]) and ch["value"] <= ch["limit"]
        for ch in checks.values())
    for ch in checks.values():       # JSON has no NaN: a missing number
        if not math.isfinite(ch["value"]):
            ch["value"] = None
    result = {"correct": correct, "attempted": win.steps, "failed": failed,
              "metrics": metrics, "device": result_device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window"] = {"steps": win.steps, "seconds": window_s,
                        "compiles": win.compiles_in_window}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                    "temporary directory, deleted after reading)")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax
    # every program, the eager ones of data generation too, goes to the
    # checkout's fixed cache, so only a cell's first run there compiles
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)      # JAX does not make it, and then caches nothing
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no LRU eviction: its bookkeeping files fail on a fresh directory
    jax.config.update("jax_compilation_cache_max_size", -1)
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), trace_dir=args.trace_dir)
    except (BenchError, ImportError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, ch in res["checks"].items():
        print(f"check {name}: {ch['value']!r} (limit {ch['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
