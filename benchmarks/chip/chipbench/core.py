"""Find a cell's pieces by name and run it once.

A cell (``workloads/<cell>.json``) names its configuration
(``configs/<config>.json``), its entry (``entries/<entry>.py``: the call
into the system under test, the comparison and the control) and its traffic
kind (``traffic/<kind>.py``: the generator that drives the entry through
the measured window). Each metric is read by ``metrics/<metric>.py`` from
the run's ``Record``. Adding a configuration, a cell or a metric therefore
adds files and entries in ``BENCHMARK.json`` and edits none. A
configuration of one device builds through ``device_config``; one with a
``fleet`` block, for a cell on four chips, through ``fleet_config``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import time
from pathlib import Path
from typing import Any

BENCH_DIR = Path(__file__).resolve().parents[1]        # benchmarks/chip
ROOT = BENCH_DIR.parents[1]                            # the checkout
JAX_CACHE = ROOT / ".jax_cache"         # fixed: the path is in the key
EGPU_CACHE = ROOT / ".egpu_cache"       # host lowerings (EGPU_CACHE_DIR)


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than a cell needs."""


def open_chips(chips: int):
    """Check that JAX's devices are at least ``chips`` TPUs, keep every
    compilation cache at its fixed path in the checkout, and return JAX's
    module. Call it before anything imports the system under test."""
    import os

    os.environ["EGPU_CACHE_DIR"] = str(EGPU_CACHE)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(f"need {chips} TPU chip(s); JAX sees {len(devices)} "
                     f"{devices[0].platform} device(s)")
    jax.config.update("jax_compilation_cache_dir", str(JAX_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def load_json(path: Path) -> Any:
    with open(path) as fh:
        return json.load(fh)


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` under the benchmark's directory, by file."""
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark_spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell_files(cell: str) -> tuple[dict, dict]:
    """The cell's workload file and its configuration file."""
    workload = load_json(BENCH_DIR / "workloads" / f"{cell}.json")
    config = load_json(BENCH_DIR / "configs" / f"{workload['config']}.json")
    return workload, config


def device_config(config: dict):
    """The configuration's ``device`` block as a ``DeviceConfig``, after
    checking that the simulated SM has the ``machine`` widths it states."""
    from repro.core import DeviceConfig, SMConfig
    from repro.core.machine import MAX_THREADS, N_REGS, N_SP

    have = {"sps_per_sm": N_SP, "threads_per_sm": MAX_THREADS,
            "regs_per_thread": N_REGS}
    if config["machine"] != have:
        raise ValueError(f"configuration states {config['machine']}, the "
                         f"simulator has {have}")
    dev = dict(config["device"])
    return DeviceConfig(sm=SMConfig(**dev.pop("sm")), **dev)


FLEET_KEYS = {"n_devices", "route", "placement", "remote_gmem_latency"}


def fleet_config(config: dict):
    """The configuration's ``fleet`` block as a ``FleetConfig`` of
    ``n_devices`` copies of ``device_config(config)``; the block states
    exactly ``FLEET_KEYS``."""
    from repro.core import FleetConfig

    block = config["fleet"]
    if set(block) != FLEET_KEYS:
        raise ValueError(f"fleet block states {sorted(block)}, not "
                         f"{sorted(FLEET_KEYS)}")
    return FleetConfig(device=device_config(config), **block)


def cell_metrics(spec: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: end-to-end ones untraced,
    per-layer ones traced; a metric without ``workloads`` is every
    cell's."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def percentile(values, q: float) -> float:
    """The q-th percentile, linear between closest ranks (numpy's
    default)."""
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), q))


@dataclasses.dataclass
class Record:
    """What one run measured, for the metric readers."""

    setup_s: float = math.nan
    window_s: float = math.nan
    attempted: int = 0
    failed: int = 0
    latencies_ms: list = dataclasses.field(default_factory=list)
    instructions: int = 0           # simulated instructions completed
    gen_late_ms: list = dataclasses.field(default_factory=list)
    batch_sizes: list = dataclasses.field(default_factory=list)
    profile: dict | None = None     # profile() of the window's last launch
    compiles: int = 0               # compile requests inside the window
    gc_full_ms: float = math.nan    # the full collection closing set-up
    gc_pauses: list = dataclasses.field(default_factory=list)
    host_gap: tuple = (0.0, 0.0, 0.0)   # HostGaps.longest
    trace: Any = None               # profile_trace.Trace of the window
    memory_peak_bytes: int = 0


TRACED_SHARE = 0.25     # the profiled window, as a share of the run's


def settle() -> float:
    """End set-up: collect the garbage it left and freeze what survives,
    so that no collection in the window walks set-up's objects again (the
    plans of every warmed shape: a full walk of a served set-up's took
    0.45 s on a TPU v5e host); returns the milliseconds of that
    collection."""
    t = time.perf_counter()
    gc.collect()
    ms = (time.perf_counter() - t) * 1e3
    gc.freeze()
    return ms


class HostGaps:
    """A thread that wakes every ``TICK_S`` while the body runs and keeps
    its longest late wake-up: how long, the process's CPU seconds in it,
    and when it began, in seconds from the body's start. A long gap with
    little CPU means the whole process waited (held by one thread in C,
    or not scheduled); a stall that leaves the ticks on time waited
    inside one call, on the device or a lock."""

    TICK_S = 0.02

    def __init__(self):
        import threading

        self.longest = (0.0, 0.0, 0.0)      # (gap s, cpu s, at s)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._tick,
                                        name="bench-host-gaps", daemon=True)

    def _tick(self) -> None:
        t0 = last = time.perf_counter()
        cpu = time.process_time()
        while not self._stop.wait(self.TICK_S):
            now, c = time.perf_counter(), time.process_time()
            gap = now - last - self.TICK_S
            if gap > self.longest[0]:
                self.longest = (gap, c - cpu, last - t0)
            last, cpu = now, c

    def __enter__(self) -> "HostGaps":
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()


class GcPauses:
    """The garbage collector's passes while the body runs, as
    ``(generation, milliseconds)``."""

    def __init__(self):
        self.pauses: list[tuple[int, float]] = []
        self._t = None

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((int(info["generation"]),
                                (time.perf_counter() - self._t) * 1e3))
            self._t = None

    def __enter__(self) -> "GcPauses":
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._on_gc)


class CompileCounter:
    """Compile requests that reach JAX's compilation cache (a persistent-
    cache hit or a real compile), counted from ``jax.monitoring``."""

    EVENT = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1


def device_info(jax) -> dict:
    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def judge(numbers: dict[str, float], limits: dict[str, float]
          ) -> tuple[bool, dict]:
    """Each number against its limit (a number passes at or below it). A
    number without a limit, or one that is not finite, fails."""
    checks, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        good = (limit is not None and math.isfinite(value)
                and value <= limit)
        ok = ok and good
        checks[name] = {"value": value, "limit": limit}
    return ok, checks


def read_metrics(rec: Record, metrics: list[dict]) -> dict:
    """Each metric's reading, with its unit; a reader that finds nothing
    to read leaves its metric out."""
    values = {}
    for m in metrics:
        v = load_module("metrics", m["name"]).read(rec)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    return values


def diagnose(rec: Record, file=None) -> None:
    """One line on standard error that the result line does not carry:
    the window's compile requests, its slowest calls (a stall shows here),
    the garbage collector's passes, the longest host gap (``HostGaps``),
    and how many requests rode in batches of each size."""
    import collections
    import sys

    import numpy as np

    lat = np.asarray(rec.latencies_ms, np.float64)
    slow = np.argsort(lat)[::-1][:3]
    line = {"compiles": rec.compiles, "calls": int(lat.size),
            "slowest_ms": [[int(i), float(lat[i])] for i in slow],
            "gc_full_ms": rec.gc_full_ms,
            "gc_passes": dict(sorted(collections.Counter(
                g for g, _ in rec.gc_pauses).items())),
            "gc_longest_ms": max((ms for _, ms in rec.gc_pauses),
                                 default=0.0),
            "host_gap_s": list(rec.host_gap)}
    if rec.batch_sizes:
        line["requests_by_batch_size"] = dict(sorted(collections.Counter(
            int(b) for b in rec.batch_sizes).items()))
    print(f"window {json.dumps(line)}", file=file or sys.stderr)


def run_cell(workload: dict, config: dict, *, seed: int,
             seconds: float, trace: bool, metrics: list[dict],
             t_start: float) -> dict:
    """Set up, measure one window, compare, and build the result line.

    ``t_start`` is the process's start on ``time.perf_counter``; set-up
    runs from it to the window's first timed call. Traced, a second,
    profiled window of ``TRACED_SHARE`` of the run's follows the measured
    one: metrics read from the device trace come from it, the others from
    the first, which runs as an untraced run's does. ``compare`` checks
    the answers of both."""
    import jax

    from . import profile_trace

    traffic = load_module("traffic", workload["traffic"]["kind"])
    entry = load_module("entries", workload["traffic"]["entry"])
    rec = Record()
    counter = CompileCounter()

    run = traffic.prepare(entry, config, workload, seed, seconds)
    rec.gc_full_ms = settle()
    c0 = counter.count
    rec.setup_s = time.perf_counter() - t_start
    with GcPauses() as watch, HostGaps() as gaps:
        run.window(rec)
    rec.compiles = counter.count - c0
    rec.gc_pauses = watch.pauses
    rec.host_gap = gaps.longest
    traced = Record()
    if trace:
        with profile_trace.capture() as cap:
            run.window(traced, seconds * TRACED_SHARE)
        traced.trace = cap.trace
    dev = device_info(jax)
    rec.memory_peak_bytes = dev["memory_peak_bytes"]

    diagnose(rec)
    numbers = run.compare(rec)      # frees the program's state first
    ok, checks = judge(numbers, workload["limits"])
    values = read_metrics(rec, [m for m in metrics
                                if m["source"] != "device_trace"])
    values.update(read_metrics(traced, [m for m in metrics
                                        if m["source"] == "device_trace"]))
    attempted = rec.attempted + traced.attempted
    failed = rec.failed + traced.failed
    out: dict[str, Any] = {
        "correct": bool(ok and failed == 0 and attempted > 0),
        "attempted": attempted, "failed": failed,
        "metrics": values, "device": dev}
    if traced.trace is not None:
        out["device"]["busy_s"] = traced.trace.busy_s()
        out["device"]["window_s"] = traced.trace.window_s()
        out["breakdown"] = traced.trace.breakdown()
    out["checks"] = checks
    return out
