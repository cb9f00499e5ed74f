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
from repro.core import FleetConfig  # noqa: E402

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


def check_cell(cell: str, workload: dict, config: dict, spec: dict) -> None:
    """A workload file and its configuration agree with each other, with
    the files they name and, where ``spec`` lists the cell, with its
    entry. A cell takes 1 or 4 chips; a four-chip cell's configuration
    has a ``fleet`` block of as many devices, a one-chip cell's has
    none."""
    assert workload["config"] == config["name"]
    assert len(workload["why"]) <= 200
    assert workload["chips"] in (1, 4)
    if workload["chips"] == 4:
        assert config["fleet"]["n_devices"] == workload["chips"]
    else:
        assert "fleet" not in config
    assert cell == f"{workload['config']}.{cell.split('.', 1)[1]}"
    listed = [w for w in spec["workloads"] if w["name"] == cell]
    if listed:
        (entry,) = listed
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


def check_share(spec: dict) -> None:
    """At most half of the listed cells, rounded down, take four chips;
    one always may."""
    cells = spec["workloads"]
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 2), (four, len(cells))


def check_config(name: str, config: dict, spec: dict) -> None:
    """A configuration builds; one with a ``fleet`` block builds through
    ``fleet_config`` and states the ``shard_map`` placement (a launch
    that cannot take it raises, where ``auto`` would fall back to the
    host loop) and the ``block`` route."""
    assert config["name"] == name and config["reduced"] == []
    assert core.device_config(config).n_sms == 4
    if "fleet" in config:
        fleet = core.fleet_config(config)
        assert fleet.placement == "shard_map" and fleet.route == "block"
    listed = [c for c in spec["configs"] if c["name"] == name]
    if listed:
        assert ROOT / listed[0]["file"] == BENCH / "configs" / f"{name}.json"
        assert listed[0]["reduced"] == []
        assert name in {w["config"] for w in spec["workloads"]}


@pytest.mark.parametrize("cell", WORKLOAD_FILES)
def test_cell_files_agree(cell):
    """Every workload file, listed in ``BENCHMARK.json`` or kept for a
    later one, names files that exist; a listed one agrees with its
    entry."""
    workload, config = core.cell_files(cell)
    check_cell(cell, workload, config, SPEC)


def test_four_chip_share():
    check_share(SPEC)


@pytest.mark.parametrize("name", CONFIG_FILES)
def test_config_files(name):
    path = BENCH / "configs" / f"{name}.json"
    check_config(name, json.loads(path.read_text()), SPEC)


def _fleet_example(n_devices: int | None = 4, chips: int = 4):
    """An in-test fleet cell of ``chips`` chips, its configuration with a
    ``fleet`` block of ``n_devices`` (none where that is None), and a
    spec that lists it after the benchmark's own cells."""
    config = json.loads((BENCH / "configs" / "sector4.json").read_text())
    config["name"] = "fleet4"
    if n_devices is not None:
        config["fleet"] = {"n_devices": n_devices, "route": "block",
                           "placement": "shard_map",
                           "remote_gmem_latency": 0}
    workload = core.cell_files("sector4.fft256_qrd16_batch")[0]
    workload.update(config="fleet4", chips=chips,
                    why="four sector4 devices, one per chip")
    cell = "fleet4.fft256_qrd16_batch"
    spec = dict(SPEC, workloads=SPEC["workloads"] + [{
        "name": cell, "config": "fleet4", "traffic": "fft256_qrd16_batch",
        "chips": chips, "why": workload["why"]}])
    return cell, workload, config, spec


def test_layout_takes_a_four_chip_fleet_cell():
    cell, workload, config, spec = _fleet_example()
    check_cell(cell, workload, config, spec)
    check_config("fleet4", config, spec)
    check_share(spec)
    check_share({"workloads": [spec["workloads"][-1]]})     # one always may


@pytest.mark.parametrize("case", ["two_chips", "n_devices_not_chips",
                                  "one_four_chip_cell_too_many",
                                  "fleet_on_one_chip"])
def test_layout_refuses(case):
    if case == "one_four_chip_cell_too_many":
        # one of three cells may take four chips; this spec asks two
        spec = _fleet_example()[3]
        spec["workloads"] = [dict(w, chips=4) if w["name"] == CELLS[0]
                             else w for w in spec["workloads"]]
        with pytest.raises(AssertionError):
            check_share(spec)
        return
    cell, workload, config, spec = _fleet_example(**{
        "two_chips": {"n_devices": None, "chips": 2},
        "n_devices_not_chips": {"n_devices": 2},
        "fleet_on_one_chip": {"chips": 1}}[case])
    with pytest.raises(AssertionError):
        check_cell(cell, workload, config, spec)


def test_fleet_config_builds_from_the_block():
    config = _fleet_example()[2]
    config["fleet"]["remote_gmem_latency"] = 7
    fleet = core.fleet_config(config)
    assert isinstance(fleet, FleetConfig)
    assert fleet.device == core.device_config(config)
    assert (fleet.n_devices, fleet.route, fleet.placement,
            fleet.remote_gmem_latency) == (4, "block", "shard_map", 7)
    del config["fleet"]["remote_gmem_latency"]
    with pytest.raises(ValueError, match="fleet block"):
        core.fleet_config(config)


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
