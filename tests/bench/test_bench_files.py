"""Every cell of BENCHMARK.json resolves to its files, and every name,
unit and text keeps to the characters the manifest allows."""
import json
import re

import pytest
from conftest import ROOT

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
E2E_SOURCES = {"device_trace", "host_clock"}
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def one_line(text: str, most: int = 200) -> bool:
    return 1 <= len(text) <= most and "\n" not in text and "\t" not in text


def test_manifest_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert all(one_line(w) for w in MANIFEST["command"])
    for p in MANIFEST["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert (ROOT / p).is_dir()
    assert (ROOT / MANIFEST["command"][1]).is_file()
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_are_unique_and_plain():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    if metric in MANIFEST["end_to_end"]:
        assert metric["source"] in E2E_SOURCES
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert one_line(metric["layer"])
        assert metric["moves"] in [m["name"] for m in MANIFEST["end_to_end"]]
        assert (ROOT / "bench" / "metrics" / f"{metric['name']}.py").is_file()
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_config_resolves(config):
    assert one_line(config["source"]) and one_line(config["why"])
    assert config["file"].startswith("bench/")
    body = json.loads((ROOT / config["file"]).read_text())
    assert body["name"] == config["name"]
    assert body["reduced"] == config["reduced"]
    assert all(NAME.match(k) for k in config["reduced"])
    assert (ROOT / "bench" / "reference" / f"{config['name']}.py").is_file()
    assert (ROOT / "bench" / "costs" / f"{config['name']}.py").is_file()
    assert any(w["config"] == config["name"] for w in MANIFEST["workloads"])


@pytest.mark.parametrize("entry", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_cell_resolves(entry):
    from bench import run
    c = run.load_cell(entry["name"])
    assert entry["chips"] in (1, 4)
    assert NAME.match(entry["traffic"]) and one_line(entry["why"])
    assert entry["name"] == f"{entry['config']}.{entry['traffic']}"
    assert c["cell"]["config"] == entry["config"]
    flags = run.parse_flags(c["cell"]["flags"])
    assert all(name in flags for name in run.STATED)
    # the compared steps, through the first push of θ_stale, are warm-up
    assert flags["refresh_every"] > run.CHECK_STEPS
    assert c["cell"]["warmup_steps"] >= flags["refresh_every"]
    reported = [m for m in METRICS if run.listed(m, entry["name"])]
    assert "setup_s" in [m["name"] for m in reported]
    assert any(m in MANIFEST["per_layer"] for m in reported)
    assert len([m for m in reported if m in MANIFEST["end_to_end"]]) >= 2


def test_four_chip_cells_are_few():
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 2)


def test_unknown_workload_is_refused():
    from bench import run
    with pytest.raises(run.BenchError):
        run.load_cell("no_such.cell")
