"""Benchmark driver (deliverable d): one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV lines plus per-benchmark detail CSVs
under benchmarks/results/.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only fig4,scoring

Exits non-zero when any selected benchmark errored or --only matched none.
"""
from __future__ import annotations

import argparse
import csv
import json
import sys
import time
from pathlib import Path

RESULTS_DIR = Path(__file__).parent / "results"


def _write_rows(name: str, rows: list[dict]):
    RESULTS_DIR.mkdir(exist_ok=True)
    if not rows:
        return
    keys = sorted({k for r in rows for k in r})
    with open(RESULTS_DIR / f"{name}.csv", "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=keys)
        w.writeheader()
        w.writerows(rows)


BENCHES = {}


def bench(name):
    def deco(fn):
        BENCHES[name] = fn
        return fn
    return deco


@bench("fig2_convergence")
def _fig2():
    from benchmarks.paper_figures import fig2_convergence
    return fig2_convergence()


@bench("fig3_table1_test_error")
def _fig3():
    from benchmarks.paper_figures import fig3_table1_test_error
    return fig3_table1_test_error()


@bench("fig4_variance")
def _fig4():
    from benchmarks.paper_figures import fig4_variance
    return fig4_variance()


@bench("b1_staleness")
def _b1():
    from benchmarks.paper_figures import b1_staleness
    return b1_staleness()


@bench("b3_smoothing")
def _b3():
    from benchmarks.paper_figures import b3_smoothing
    return b3_smoothing()


@bench("scoring_throughput")
def _scoring():
    from benchmarks.scoring_throughput import scoring_throughput
    return scoring_throughput()


@bench("strategy_ablation")
def _ablation():
    from benchmarks.strategy_ablation import strategy_ablation
    return strategy_ablation()


@bench("asgd_comparison")
def _asgd():
    from benchmarks.asgd_comparison import asgd_comparison
    return asgd_comparison()


@bench("roofline")
def _roofline():
    from benchmarks.roofline import run
    return run()


@bench("sharded_scaling")
def _sharded_scaling():
    from benchmarks.sharded_scaling import sharded_scaling
    return sharded_scaling()


@bench("transformer_scaling")
def _transformer_scaling():
    from benchmarks.sharded_scaling import transformer_scaling
    return transformer_scaling()


@bench("async_overlap")
def _async_overlap():
    from benchmarks.async_overlap import async_overlap
    return async_overlap()


@bench("streaming_io")
def _streaming_io():
    from benchmarks.streaming_io import streaming_io
    return streaming_io()


@bench("sampling_scale")
def _sampling_scale():
    from benchmarks.sampling_scale import sampling_scale
    return sampling_scale()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--bench-json", default="",
                    help="also write the summaries to this path (CI "
                    "uploads it as the BENCH_* artifact)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    only = [s.strip() for s in args.only.split(",") if s.strip()]

    print("name,us_per_call,derived")
    all_summaries = {}
    errors = []
    for name, fn in BENCHES.items():
        if only and not any(o in name for o in only):
            continue
        t0 = time.time()
        try:
            rows, summary = fn()
        except Exception as e:  # run the rest, then exit non-zero
            print(f"{name},ERROR,{e!r}")
            errors.append(name)
            continue
        dt_us = (time.time() - t0) * 1e6
        _write_rows(name, rows)
        all_summaries[name] = summary
        derived = ";".join(f"{k}={v:.4g}" if isinstance(v, float)
                           else f"{k}={v}" for k, v in list(summary.items())[:6])
        print(f"{name},{dt_us:.0f},{derived}")
    RESULTS_DIR.mkdir(exist_ok=True)
    with open(RESULTS_DIR / "summaries.json", "w") as f:
        json.dump(all_summaries, f, indent=2, default=str)
    if args.bench_json:
        with open(args.bench_json, "w") as f:
            json.dump(all_summaries, f, indent=2, default=str)
    if errors or not all_summaries:
        print(f"{len(errors)} errored, {len(all_summaries)} succeeded",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
