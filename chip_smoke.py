"""Chip smoke test: drive the eGPU's main path once on a TPU.

Run from the repository root:

    python chip_smoke.py [--seed N]             # one chip, three phases
    python chip_smoke.py --chips 4 [--seed N]   # four chips, fleet phase only

One chip runs the paper's quad-packed sector (§III.E: 4 SMs x 512
threads, 16 registers, 3072-word shared memory, here with an 8192-word
global memory) through the entry points a user calls:

  1. ``launch_fft_qrd``: 8 FFT-256 signals and 4 random 16x16 QRDs in one
     mixed launch, on engine "auto" and then on every engine under both
     backends. Results are checked against ``np.fft.fft`` and ``Q @ R``;
     registers, shared and global memory must be equal bit for bit
     across all runs, and so must the modeled cycles.
  2. ``device.launch``: the predicated 16x16 Cholesky solve, checked
     against numpy, on both backends.
  3. ``LaunchServer``: six mixed FFT-256/QRD-16 requests, one drain.

``--chips 4`` runs only the fleet's ``shard_map`` placement: a uniform
FFT-256 grid of 16 blocks on four simulated eGPUs, one per chip, compared
bit for bit with the same grid on one device.

Each phase prints one line: the engine and backend that ran, the engine
fallback, whether Pallas ran interpreted, whether the modules handed to
the compiler hold a compiled Pallas kernel (``tpu_custom_call``), the
phase's wall time, its compile requests and persistent-cache hits, and
its largest errors. The last line is the result, printed only when every
check passed. The script exits non-zero, without that line, when JAX
finds no TPU or any check fails. All work runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import (  # noqa: E402
    DeviceConfig,
    FleetConfig,
    Kernel,
    SMConfig,
    launch_fleet,
    trace_engine,
)
from repro.core.programs.cholesky import (  # noqa: E402
    cholesky_imem_depth,
    run_cholesky_batch,
)
from repro.core.programs.fft import bitrev_indices, fft_kernel, fft_shmem  # noqa: E402
from repro.core.programs.mixed import launch_fft_qrd  # noqa: E402
from repro.core.programs.qrd import Q_BASE, R_BASE, qrd_kernel, qrd_shmem  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.serve import LaunchRequest, LaunchServer  # noqa: E402

N_FFT = 256
IR_DIR = ROOT / ".smoke_ir"            # lowered modules, scanned per phase

# the quad-packed sector: default SMConfig widths; the unrolled QRD needs
# a 1024-word I-MEM
SECTOR = DeviceConfig(n_sms=4, global_mem_depth=8192,
                      sm=SMConfig(imem_depth=1024, max_steps=200_000))

_counts = {"cache_hits": 0}


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _counts["cache_hits"] += 1


class Phase:
    """One phase's report line: wall time, lowered modules and cache hits
    counted from a clean in-memory jit cache, and named checks."""

    def __init__(self, name: str):
        self.name = name
        self.info: dict = {}

    def __enter__(self):
        jax.clear_caches()                  # each phase lowers its own
        trace_engine.compile_cache_clear()  # programs (the disk cache
        self.seen = set(IR_DIR.glob("*_compile.mlir"))  # may serve them)
        self.hits0 = _counts["cache_hits"]
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        wall = time.perf_counter() - self.t0
        new = sorted(set(IR_DIR.glob("*_compile.mlir")) - self.seen)
        custom = any("tpu_custom_call" in p.read_text() for p in new)
        line = {"phase": self.name, **self.info,
                "interpret": ops.interpret_mode(),
                "tpu_custom_call": custom, "wall_s": wall,
                "compiles": len(new),
                "cache_hits": _counts["cache_hits"] - self.hits0}
        print(json.dumps(line), flush=True)
        if self.info.get("backend") == "pallas":
            check(not line["interpret"], f"{self.name}: Pallas interpreted")
            check(custom or not new,
                  f"{self.name}: no tpu_custom_call in the lowered modules")
        return False


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _state(res):
    return (np.asarray(res.regs), np.asarray(res.shmem), np.asarray(res.gmem))


def _same(a, b) -> bool:
    return all(np.array_equal(x, y) for x, y in zip(a, b))


def _fft_err(X, xs) -> float:
    ref = np.fft.fft(xs, axis=1)
    return float(np.abs(X - ref).max() / np.abs(ref).max())


def phase_mixed(rng) -> None:
    xs = (rng.standard_normal((8, N_FFT))
          + 1j * rng.standard_normal((8, N_FFT))).astype(np.complex64)
    As = rng.standard_normal((4, 16, 16)).astype(np.float32)
    runs = [("auto", "inline")] + [(e, b) for e in trace_engine.ENGINES
                                   for b in ("inline", "pallas")]
    ref_state = ref_cycles = None
    for engine, backend in runs:
        with Phase("mixed_fft256_qrd16") as ph:
            X, Q, R, res = launch_fft_qrd(xs, As, device=SECTOR,
                                          backend=backend, engine=engine)
            state = _state(res)
            fft_err = _fft_err(X, xs)
            qr_err = float(np.abs(Q @ R - As).max())
            ref_state = ref_state or state
            ref_cycles = ref_cycles or int(res.cycles)
            ph.info = {"engine": res.engine, "requested": engine,
                       "backend": backend,
                       "engine_fallback": res.profile()["engine_fallback"],
                       "fft_rel_err": fft_err, "qr_err": qr_err,
                       "cycles": int(res.cycles),
                       "bit_equal": _same(state, ref_state)}
        check(fft_err < 2e-5, f"FFT-256 error {fft_err} on {engine}/{backend}")
        check(qr_err < 5e-5, f"Q@R error {qr_err} on {engine}/{backend}")
        check(ph.info["bit_equal"], f"{engine}/{backend} state differs")
        check(int(res.cycles) == ref_cycles, f"{engine}/{backend} cycles")


def phase_cholesky(rng) -> None:
    g = rng.standard_normal((4, 16, 16)).astype(np.float32)
    As = (g @ g.transpose(0, 2, 1)
          + 16.0 * np.eye(16, dtype=np.float32)).astype(np.float32)
    bs = rng.standard_normal((4, 16)).astype(np.float32)
    dev = DeviceConfig(n_sms=4, global_mem_depth=8192,
                       sm=SMConfig(imem_depth=cholesky_imem_depth(True),
                                   max_steps=200_000))
    ref_state = None
    for backend in ("inline", "pallas"):
        with Phase("cholesky16_solve") as ph:
            L, y, res = run_cholesky_batch(As, bs, device=dev,
                                           backend=backend)
            state = _state(res)
            ref_state = ref_state or state
            l_err = float(np.abs(L - np.linalg.cholesky(As)).max())
            y_ref = np.stack([np.linalg.solve(np.linalg.cholesky(a), b)
                              for a, b in zip(As, bs)])
            y_err = float(np.abs(y - y_ref).max())
            ph.info = {"engine": res.engine, "backend": backend,
                       "engine_fallback": res.profile()["engine_fallback"],
                       "l_err": l_err, "y_err": y_err,
                       "bit_equal": _same(state, ref_state)}
        check(l_err < 1e-4 and y_err < 1e-4,
              f"Cholesky errors {l_err}, {y_err} on {backend}")
        check(ph.info["bit_equal"], f"Cholesky {backend} state differs")


def phase_serve(rng) -> None:
    depth = SECTOR.sm.shmem_depth
    server = LaunchServer(SECTOR, max_batch=8, backend="pallas")
    work = []
    for i in range(6):
        if i % 2 == 0:
            x = (rng.standard_normal(N_FFT)
                 + 1j * rng.standard_normal(N_FFT)).astype(np.complex64)
            req = LaunchRequest(kernel=fft_kernel(N_FFT),
                                shmem=fft_shmem(x, depth), tag=i)
        else:
            x = rng.standard_normal((16, 16)).astype(np.float32)
            req = LaunchRequest(kernel=qrd_kernel(),
                                shmem=qrd_shmem(x, depth), tag=i)
        work.append((x, server.submit(req)))
    with Phase("serve_mixed") as ph:
        served = server.drain()
        fft_err = qr_err = 0.0
        for x, fut in work:
            r = fut.result()
            mem = np.asarray(r.shmem_f32())[0]
            if x.ndim == 1:
                out = np.empty(N_FFT, np.complex64)
                out[bitrev_indices(N_FFT)] = (mem[0:2 * N_FFT:2]
                                              + 1j * mem[1:2 * N_FFT:2])
                ref = np.fft.fft(x)
                fft_err = max(fft_err, float(np.abs(out - ref).max()
                                             / np.abs(ref).max()))
            else:
                q = mem[Q_BASE:Q_BASE + 256].reshape(16, 16).T
                rr = mem[R_BASE:R_BASE + 256].reshape(16, 16)
                qr_err = max(qr_err, float(np.abs(q @ rr - x).max()))
        prof = work[0][1].result().profile
        ph.info = {"engine": prof["engine"], "backend": "pallas",
                   "engine_fallback": prof["engine_fallback"],
                   "served": served, "batches": server.stats()["batches"],
                   "fft_rel_err": fft_err, "qr_err": qr_err}
    check(served == 6, f"served {served} of 6 requests")
    check(fft_err < 2e-5 and qr_err < 5e-5,
          f"served errors {fft_err}, {qr_err}")


def phase_fleet(rng, n_devices: int) -> None:
    xs = (rng.standard_normal((16, N_FFT))
          + 1j * rng.standard_normal((16, N_FFT))).astype(np.complex64)
    images = np.stack([fft_shmem(x, SECTOR.sm.shmem_depth) for x in xs])
    kern: Kernel = fft_kernel(N_FFT)
    states = {}
    for n in (n_devices, 1):
        fcfg = FleetConfig(n_devices=n, device=SECTOR,
                           placement="shard_map" if n > 1 else "host")
        with Phase(f"fleet_fft256x16[{n}dev]") as ph:
            res = launch_fleet(fcfg, programs=[kern], grid_map=[0] * 16,
                               shmem=[images], backend="pallas")
            states[n] = _state(res)
            mem = states[n][1].view(np.float32)
            X = np.empty((16, N_FFT), np.complex64)
            X[:, bitrev_indices(N_FFT)] = (mem[:, 0:2 * N_FFT:2]
                                           + 1j * mem[:, 1:2 * N_FFT:2])
            fft_err = _fft_err(X, xs)
            fleet = res.profile()["fleet"]
            ph.info = {"engine": res.engine, "backend": "pallas",
                       "engine_fallback": res.profile()["engine_fallback"],
                       "placement": fleet["placement"],
                       "shard_devices": fleet.get("shard_devices"),
                       "fft_rel_err": fft_err}
        check(fft_err < 2e-5, f"fleet({n}) FFT error {fft_err}")
        if n > 1:
            check(fleet["placement"] == "shard_map", "fleet not shard_map")
            want = sorted(d.id for d in jax.devices()[:n])
            check(sorted(fleet["shard_devices"]) == want,
                  f"fleet slices on devices {fleet['shard_devices']}, "
                  f"want one on each of {want}")
    same = _same(states[n_devices], states[1])
    print(json.dumps({"phase": "fleet_compare",
                      "bit_equal": same}), flush=True)
    check(same, f"fleet({n_devices}) differs from fleet(1)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    print(json.dumps({"platform": dev.platform, "device_kind":
                      dev.device_kind, "device_count": len(devices)}),
          flush=True)
    if dev.platform != "tpu":
        print(f"no TPU: JAX's first device is {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} but JAX sees {len(devices)}",
              file=sys.stderr)
        return 2

    shutil.rmtree(IR_DIR, ignore_errors=True)
    IR_DIR.mkdir()
    jax.config.update("jax_dump_ir_to", str(IR_DIR))
    # every compiled program goes to the persistent cache, so a second run
    # compiles nothing (the cache directory is the launch path's choice)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.monitoring.register_event_listener(_on_event)

    rng = np.random.default_rng(args.seed)
    if args.chips == 4:
        phase_fleet(rng, 4)
    else:
        phase_mixed(rng)
        phase_cholesky(rng)
        phase_serve(rng)
    shutil.rmtree(IR_DIR, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
