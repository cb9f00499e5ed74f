"""The benchmark's files agree with ``BENCHMARK.json`` and with each other:
every cell's workload file, configuration, traffic kind, entry and jobs
exist, every metric has its reader, and every metric's ``moves`` is an
end-to-end metric that each of its cells reports."""
from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import core  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]
WORKLOAD_FILES = sorted(p.stem for p in (BENCH / "workloads").glob("*.json"))
CONFIG_FILES = sorted(p.stem for p in (BENCH / "configs").glob("*.json"))
METRIC_FILES = sorted(p.stem for p in (BENCH / "metrics").glob("*.py"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["benchmarks/chip"]
    assert (ROOT / SPEC["command"][1]).is_file()
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_are_unique_and_well_formed():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    for n in names + CELLS + [c["name"] for c in SPEC["configs"]]:
        assert NAME.match(n), n


@pytest.mark.parametrize("cell", WORKLOAD_FILES)
def test_cell_files_agree(cell):
    """Every workload file, listed in ``BENCHMARK.json`` or kept for a
    later one, names files that exist; a listed one agrees with its
    entry."""
    workload, config = core.cell_files(cell)
    assert workload["config"] == config["name"]
    assert workload["chips"] == 1 and len(workload["why"]) <= 200
    assert cell == f"{workload['config']}.{cell.split('.', 1)[1]}"
    if cell in CELLS:
        entry = next(w for w in SPEC["workloads"] if w["name"] == cell)
        assert workload["config"] == entry["config"]
        assert workload["chips"] == entry["chips"]
        assert workload["why"] == entry["why"]
        assert cell == f"{entry['config']}.{entry['traffic']}"
    traffic = workload["traffic"]
    assert (BENCH / "traffic" / f"{traffic['kind']}.py").is_file()
    assert (BENCH / "entries" / f"{traffic['entry']}.py").is_file()
    for job in traffic["jobs"]:
        assert (BENCH / "jobs" / f"{job['job']}.py").is_file()
    # a limit for every number the cell compares, and no other
    jobs = {core.load_module("jobs", j["job"]).NUMBER
            for j in traffic["jobs"]}
    if traffic["kind"] == "closed_loop":
        pinned = {f"{k}_dev" for k in workload["pinned"]}
    else:
        pinned = {"instructions_dev", "busy_cycles_dev"}
    assert set(workload["limits"]) == jobs | pinned
    assert all(workload["limits"][k] == 0 for k in pinned)


@pytest.mark.parametrize("name", CONFIG_FILES)
def test_config_files(name):
    path = BENCH / "configs" / f"{name}.json"
    data = json.loads(path.read_text())
    assert data["name"] == name and data["reduced"] == []
    assert core.device_config(data).n_sms == 4
    listed = [c for c in SPEC["configs"] if c["name"] == name]
    if listed:
        assert ROOT / listed[0]["file"] == path
        assert listed[0]["reduced"] == []
        assert name in {w["config"] for w in SPEC["workloads"]}


@pytest.mark.parametrize("name", METRIC_FILES)
def test_every_reader_takes_an_empty_record(name):
    """Every reader, of a metric listed or kept for later, reads a run
    that measured nothing without raising: ``None`` or a number."""
    value = core.load_module("metrics", name).read(core.Record())
    assert value is None or isinstance(value, (int, float))


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_reader_and_what_it_moves(metric):
    assert (BENCH / "metrics" / f"{metric['name']}.py").is_file()
    assert metric["better"] in ("lower", "higher")
    cells = metric.get("workloads", CELLS)
    assert set(cells) <= set(CELLS)
    if "moves" in metric:
        moved = next(m for m in SPEC["end_to_end"]
                     if m["name"] == metric["moves"])
        for cell in cells:
            assert cell in moved.get("workloads", CELLS), (cell, moved)


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_enough(cell):
    e2e = {m["name"] for m in core.cell_metrics(SPEC, cell, False)}
    layer = core.cell_metrics(SPEC, cell, True)
    assert "setup_s" in e2e and len(e2e) >= 2
    assert layer


def test_bounds():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_peaks_known_and_unknown_kind():
    """Peaks are keyed by JAX's ``device_kind``, with their source; a
    kind that is not in the table has no entry to fall back on."""
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert "TPU v5e" in peaks["source"]
    assert peaks["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert "TPU v9 imaginary" not in peaks["devices"]
    assert all(isinstance(v, float) for d in peaks["devices"].values()
               for v in d.values())
