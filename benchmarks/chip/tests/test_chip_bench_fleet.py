"""The four-chip cell ``fleet4.fft256_shard_map``, on the CPU.

- Its configuration is ``sector4``'s device four times over, built
  through ``core.fleet_config``.
- The control (the plain reference at three-pass bfloat16) fails the
  cell's limits.
- A whole run (set-up, a 0.5 s window, the comparison) with the chip check
  skipped is correct on four virtual CPU devices, and is not correct
  under each planted fault: a launch that returns its state unchanged,
  half of the blocks left out, an answer altered where it is produced,
  and one chip's slice dropped from the merge and the assembly, so that
  its blocks keep the state they were given. The last is the exchange
  between chips that a one-chip cell cannot have. The test command's JAX
  sees one CPU device, so these runs are made once, in a subprocess that
  runs this file as a script with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` and
  ``JAX_PLATFORMS=cpu``; it prints one JSON line a run.
- Each ``fleet.*_ms`` reader reads its span family on a hand-made traced
  window, and reads nothing where the window holds no such span.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import core, program_spans  # noqa: E402
from chipbench.profile_trace import Trace  # noqa: E402
from repro.core import tracing  # noqa: E402
from repro.core.tracing import Span  # noqa: E402

CELL = "fleet4.fft256_shard_map"
FAULTS = ("unchanged", "half", "altered", "chip_dropped")
TIMEOUT_S = 600


# ---------------------------------------------------------------------------
# the subprocess: whole runs with faults planted, this file as a script
# ---------------------------------------------------------------------------

def _images(kw: dict) -> np.ndarray:
    """Each block's shared memory as the launch received it."""
    img = np.asarray(kw["shmem"][0])
    return img.view(np.uint32) if img.dtype == np.float32 else img


def _break_result(real, fault: str):
    """``launch_fleet`` with the fault planted in the blocks' shared
    memory, where the FFTs leave their answers."""
    import jax.numpy as jnp

    def launch_fleet(fcfg, *args, **kw):
        res = real(fcfg, *args, **kw)
        sh = np.array(res.shmem)
        init = _images(kw)
        if fault == "unchanged":
            sh = init
        elif fault == "half":
            sh[1::2] = init[1::2]
        else:
            sh[0] = (sh[0].view(np.float32) + 1.0).view(np.uint32)
        res.shmem = jnp.asarray(sh)
        return res

    return launch_fleet


def _drop_last_chip(real):
    """The fleet's staging, with a mapped call whose last chip's slice
    never reaches the merge and the assembly: its blocks' shared memory
    stays as placed, its registers zero, its gmem replica the launch
    image."""
    import jax.numpy as jnp

    def stage(*args, **kw):
        mapped, margs, order, sched = real(*args, **kw)

        def dropped(xs, bid, pid, sh, gm):
            regs, sh_out, gmems, oob = mapped(xs, bid, pid, sh, gm)
            last = jnp.arange(sh.shape[0]) == sh.shape[0] - 1
            return (jnp.where(last[:, None, None, None], 0, regs),
                    jnp.where(last[:, None, None], sh, sh_out),
                    jnp.where(last[:, None], gm[None], gmems),
                    jnp.where(last[:, None], False, oob))

        return dropped, margs, order, sched

    return stage


def main() -> int:
    import repro.core
    from repro.core import fleet

    workload, config = core.cell_files(CELL)
    metrics = core.cell_metrics(core.benchmark_spec(), CELL, False)
    for case in ("clean",) + FAULTS:
        if case == "chip_dropped":
            real, where, name = fleet._stage_shard_map, fleet, \
                "_stage_shard_map"
            setattr(fleet, name, _drop_last_chip(real))
        elif case != "clean":
            real, where, name = repro.core.launch_fleet, repro.core, \
                "launch_fleet"
            setattr(where, name, _break_result(real, case))
        try:
            out = core.run_cell(workload, config, seed=2**31 + 7,
                                seconds=0.5, trace=False, metrics=metrics,
                                t_start=time.perf_counter())
        finally:
            if case != "clean":
                setattr(where, name, real)
        print(json.dumps({"case": case, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "checks": out["checks"]}), flush=True)
    return 0


# ---------------------------------------------------------------------------
# the configuration and the control
# ---------------------------------------------------------------------------

def test_fleet4_is_four_sector4_devices():
    fleet4 = core.load_json(BENCH / "configs" / "fleet4.json")
    sector4 = core.load_json(BENCH / "configs" / "sector4.json")
    assert fleet4["machine"] == sector4["machine"]
    assert fleet4["device"] == sector4["device"]
    fcfg = core.fleet_config(fleet4)
    assert fcfg.n_devices == 4 and fcfg.device == core.device_config(sector4)
    assert (fcfg.route, fcfg.placement, fcfg.remote_gmem_latency) == \
        ("block", "shard_map", 0)
    workload, _ = core.cell_files(CELL)
    (job,) = workload["traffic"]["jobs"]
    # whole waves of the sector's SMs on every chip
    assert job["count"] % (fcfg.n_devices * fcfg.device.n_sms) == 0


def test_control_fails_the_fleet_cell():
    workload, _ = core.cell_files(CELL)
    (spec,) = workload["traffic"]["jobs"]
    job = core.load_module("jobs", spec["job"])
    xs = job.inputs(np.random.default_rng(2**31 + 19), spec,
                    int(spec["count"]))
    ok, checks = core.judge(
        {job.NUMBER: float(job.error(xs, job.control(xs)).max())},
        workload["limits"])
    assert not ok
    assert all(c["value"] > c["limit"] for c in checks.values()), checks


# ---------------------------------------------------------------------------
# whole runs on four virtual devices
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def runs() -> dict[str, dict]:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, __file__], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=TIMEOUT_S)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    return {ln["case"]: ln for ln in lines}


def test_a_clean_run_is_correct(runs):
    clean = runs["clean"]
    assert clean["correct"], clean["checks"]
    assert clean["attempted"] > 0 and clean["failed"] == 0


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_faults_are_not_correct(runs, fault):
    out = runs[fault]
    # every launch returned: the comparison, not an error, refuses it
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["correct"] is False, out["checks"]


# ---------------------------------------------------------------------------
# the fleet.*_ms readers
# ---------------------------------------------------------------------------

MS = 1_000_000
# one call's fleet spans, in order, as the shard_map path opens them:
# stage and unpack twice each (ms at scale 1)
CALL = [("plan", 1.0), ("stage", 0.5), ("stage", 1.5), ("dispatch", 3.0),
        ("merge", 4.0), ("unpack", 4.0), ("unpack", 1.0), ("schedule", 6.0)]
FLEET_PHASES = ("plan", "stage", "dispatch", "merge", "unpack", "schedule")


def _window(without: str | None = None):
    """Two ``egpu.launch_fleet`` calls at scales 1 and 3, each paired with
    one ``bench.launch`` span of the traced window; ``without`` leaves one
    phase's spans out."""
    spans, host = [], [("bench.window", 0, 10**12)]
    for k, scale in enumerate((1, 3)):
        r = i = 1000 * (k + 1)
        t0 = t = (k + 1) * 10**9
        for phase, ms in CALL:
            dt = int(ms * MS * scale)
            i += 1
            if phase != without:
                spans.append(Span(f"egpu.fleet.{phase}", t, t + dt, r, r, i))
            t += dt
        spans.append(Span("egpu.launch_fleet", t0, t + MS, None, r, r))
        host.append(("bench.launch", 5 * 10**9 + t0,
                     5 * 10**9 + t + 2 * MS))
    rec = core.Record()
    rec.attempted = 2
    rec.trace = Trace(window=(0, 10**12), device_ops=[], host_spans=host)
    return rec, spans


def _read(phase: str, rec):
    return core.load_module("metrics", f"fleet.{phase}_ms").read(rec)


@pytest.mark.parametrize("phase", FLEET_PHASES)
def test_fleet_reader_reads_its_spans(phase, monkeypatch):
    rec, spans = _window()
    monkeypatch.setattr(tracing, "recent", lambda: list(spans))
    per_call = sum(ms for p, ms in CALL if p == phase)
    # the median of the two calls, at scales 1 and 3
    assert _read(phase, rec) == pytest.approx(2 * per_call)
    # the fleet's time stays in the launch readers' "other"
    assert program_spans.calls(rec)[0]["other"] == pytest.approx(
        sum(ms for _, ms in CALL) + 2.0)


@pytest.mark.parametrize("phase", FLEET_PHASES)
def test_fleet_reader_reads_nothing_without_its_spans(phase, monkeypatch):
    rec, spans = _window(without=phase)
    monkeypatch.setattr(tracing, "recent", lambda: list(spans))
    assert _read(phase, rec) is None
    others = [p for p in FLEET_PHASES if p != phase]
    assert all(_read(p, rec) is not None for p in others)


if __name__ == "__main__":
    sys.exit(main())
