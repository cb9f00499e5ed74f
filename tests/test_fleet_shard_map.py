"""The fleet's ``shard_map`` placement on four JAX devices.

``launch_fleet`` runs a uniform grid as one compiled ``shard_map`` program
per launch shape, each simulated eGPU's block slice placed from the host
on its own JAX device. This suite checks, on four virtual CPU devices:

- bit-identity with the one-device ``launch`` (32 FFT-256 blocks, two
  waves a device, and the ``saxpy256_g4`` grid of ``test_fleet``), and
  the FFT against ``np.fft.fft`` in float64;
- compilation: the second and third launches of a shape compile nothing;
  a new block count builds one more mapped program, and its next launch
  compiles nothing;
- spans: each launch is one ``egpu.launch_fleet`` root over the six
  ``egpu.fleet.*`` phases;
- placement: ``shard_map``, one slice on each of four distinct devices.

The test command's JAX sees one CPU device, so the checks run once, in a
subprocess that runs this file as a script with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` and
``JAX_PLATFORMS=cpu``; it prints one JSON line per check, and each test
reads its line.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

pytestmark = pytest.mark.fleet

N_DEVICES = 4
N_FFT = 256
FFT_BLOCKS = 32         # 8 a device: two waves of four SMs
NEW_BLOCKS = 16         # a second launch shape: one wave a device
FFT_REL_ERR = 3e-6      # the chip cell's limit on the same measure
PHASES = {"egpu.fleet.plan", "egpu.fleet.stage", "egpu.fleet.dispatch",
          "egpu.fleet.merge", "egpu.fleet.unpack", "egpu.fleet.schedule"}
TIMEOUT_S = 600


# ---------------------------------------------------------------------------
# the subprocess: this file as a script
# ---------------------------------------------------------------------------

def _emit(check: str, **data) -> None:
    print(json.dumps({"check": check, **data}), flush=True)


def _same(a, b) -> bool:
    return all(np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f)))
               for f in ("regs", "shmem", "gmem", "oob"))


def _calls(tracing) -> list[tuple[str, set[str]]]:
    """Per root span since the last ``reset()``: its name and the names
    of its direct children."""
    spans = tracing.recent()
    return [(r.name, {s.name for s in spans if s.parent == r.id})
            for r in spans if r.parent is None]


def main() -> int:
    import jax

    # every compile request is then a backend compile, counted below
    jax.config.update("jax_enable_compilation_cache", False)
    compiles = [0]

    def on_duration(event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    from repro.core import (DeviceConfig, FleetConfig, SMConfig, fleet,
                            launch, launch_fleet, tracing)
    from repro.core.programs.fft import bitrev_indices, fft_kernel, \
        fft_shmem

    dcfg = DeviceConfig(n_sms=4, global_mem_depth=8192,
                        sm=SMConfig(shmem_depth=3072, imem_depth=1024,
                                    max_steps=200_000))
    fcfg = FleetConfig(n_devices=N_DEVICES, device=dcfg,
                       placement="shard_map")
    rng = np.random.default_rng(2**31 + 17)

    def fft_launch(n_blocks: int, launcher, cfg):
        xs = (rng.standard_normal((n_blocks, N_FFT))
              + 1j * rng.standard_normal((n_blocks, N_FFT))) \
            .astype(np.complex64)
        images = np.stack([fft_shmem(x, dcfg.sm.shmem_depth) for x in xs])
        kw = dict(programs=[fft_kernel(N_FFT)], grid_map=[0] * n_blocks,
                  shmem=[images])
        c0 = compiles[0]
        res = launcher(cfg, **kw)
        np.asarray(res.shmem)
        return res, kw, xs, compiles[0] - c0

    tracing.reset()
    counts, roots = [], []
    for i in range(3):
        res, kw, xs, n = fft_launch(FFT_BLOCKS, launch_fleet, fcfg)
        counts.append(n)
        if i == 0:
            first, first_kw, first_xs = res, kw, xs
    roots += _calls(tracing)
    _emit("compiles_same_shape", counts=counts)

    plain = launch(dcfg, **first_kw)
    _emit("bit_identical_fft256_x32", same=_same(first, plain),
          cycles=int(first.cycles), steps=int(first.steps))
    mem = np.asarray(first.shmem).view(np.float32)
    X = np.empty((FFT_BLOCKS, N_FFT), np.complex64)
    X[:, bitrev_indices(N_FFT)] = mem[:, 0:2 * N_FFT:2] \
        + 1j * mem[:, 1:2 * N_FFT:2]
    ref = np.fft.fft(first_xs.astype(np.complex128), axis=-1)
    err = np.abs(X - ref).max(axis=-1) / np.abs(ref).max(axis=-1)
    _emit("fft_reference", rel_err=float(err.max()))

    info = first.profile()["fleet"]
    _emit("placement", placement=info["placement"],
          shard_devices=info.get("shard_devices"), engine=first.engine)

    built = len(fleet._FLEET_PROGRAMS)
    tracing.reset()
    counts = [fft_launch(NEW_BLOCKS, launch_fleet, fcfg)[3]
              for _ in range(2)]
    roots += _calls(tracing)
    _emit("compiles_new_shape", counts=counts,
          programs_built=len(fleet._FLEET_PROGRAMS) - built)

    sys.path.insert(0, str(Path(__file__).resolve().parent))
    from test_fleet import CASES

    saxpy_dcfg, kw = CASES["saxpy256_g4"]()
    tracing.reset()
    res = launch_fleet(FleetConfig(n_devices=N_DEVICES, device=saxpy_dcfg,
                                   placement="shard_map"), **kw)
    roots += _calls(tracing)
    _emit("bit_identical_saxpy256_g4",
          same=_same(res, launch(saxpy_dcfg, **kw)),
          placement=res.profile()["fleet"]["placement"])
    _emit("spans", roots=[[name, sorted(kids)] for name, kids in roots])
    return 0


# ---------------------------------------------------------------------------
# the tests
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def checks() -> dict[str, dict]:
    src = Path(__file__).resolve().parents[1] / "src"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{N_DEVICES}",
               PYTHONPATH=os.pathsep.join(
                   [str(src)] + [p for p in [os.environ.get("PYTHONPATH")]
                                 if p]))
    proc = subprocess.run([sys.executable, __file__], env=env,
                          capture_output=True, text=True, timeout=TIMEOUT_S)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    return {ln.pop("check"): ln for ln in lines}


@pytest.mark.parametrize("grid", ["fft256_x32", "saxpy256_g4"])
def test_bit_identical_to_one_device(checks, grid):
    assert checks[f"bit_identical_{grid}"]["same"]


def test_fft_matches_the_float64_reference(checks):
    assert checks["fft_reference"]["rel_err"] <= FFT_REL_ERR


def test_later_launches_of_a_shape_compile_nothing(checks):
    first, *later = checks["compiles_same_shape"]["counts"]
    assert first > 0
    assert later == [0, 0]


def test_a_new_block_count_builds_one_program_then_compiles_nothing(
        checks):
    got = checks["compiles_new_shape"]
    assert got["programs_built"] == 1
    assert got["counts"][0] > 0 and got["counts"][1] == 0


def test_each_launch_is_one_root_over_the_six_phases(checks):
    roots = checks["spans"]["roots"]
    assert len(roots) == 3 + 2 + 1       # one a launch_fleet call
    for name, kids in roots:
        assert name == "egpu.launch_fleet"
        assert set(kids) == PHASES


def test_placement_is_shard_map_on_four_devices(checks):
    got = checks["placement"]
    assert got["placement"] == "shard_map" and got["engine"] == "trace"
    assert len(set(got["shard_devices"])) == N_DEVICES
    assert checks["bit_identical_saxpy256_g4"]["placement"] == "shard_map"


if __name__ == "__main__":
    sys.exit(main())
