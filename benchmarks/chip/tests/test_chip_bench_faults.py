"""``correct`` comes out false when it should.

Two kinds of test, run on the CPU:

- the control: the plain reference at three-pass bfloat16, put in the
  program's place on inputs of each cell's own sizes, fails every limit of
  the cell's answers;
- planted faults: a whole run (set-up, window, comparison) with the chip
  check skipped and the system under test broken underneath, once for each
  fault a cell can have: a launch that returns its state unchanged, half
  of the work left out, an answer altered where it is produced, and, where
  a cell decomposes matrices, a QR that skips the orthogonalization (Q the
  columns of A normalized, R their norms on the diagonal: Q R is still A).
  (A cell on one chip has no exchange between chips to leave out.) The
  same run with nothing broken is correct.

The batch cells run at their own sizes over a short window; the served
cell runs FFT-256 requests only, with batches of two, so that its warm-up
compiles two programs instead of every batch mix.
"""
from __future__ import annotations

import copy
import itertools
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import core  # noqa: E402

BATCH = "sector4.fft256_qrd16_batch"
SERVED = "sector4_served.fft256_qrd16_poisson"
REDUCE = "sector4.reduction7680_gmem"
FAULTS = ("unchanged", "half", "altered")
QR_FAULT = "unorthogonalized"


# ---------------------------------------------------------------------------
# the control fails every cell
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", [BATCH, SERVED, REDUCE])
def test_control_fails_the_cell(cell):
    workload, _ = core.cell_files(cell)
    rng = np.random.default_rng(2**31 + 11)
    numbers = {}
    for spec in workload["traffic"]["jobs"]:
        job = core.load_module("jobs", spec["job"])
        xs = job.inputs(rng, spec, 64)
        numbers[job.NUMBER] = float(job.error(xs, job.control(xs)).max())
    ok, checks = core.judge(numbers, workload["limits"])
    assert not ok
    assert all(c["value"] > c["limit"] for c in checks.values()), checks


def _unorthogonalized(A: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Q and R with Q R = A but no orthogonalization: Q is A with each
    column normalized, R the column norms on its diagonal."""
    A = np.asarray(A, np.float32)
    norms = np.sqrt((A.astype(np.float64) ** 2).sum(axis=-2))
    Q = (A / norms[..., None, :]).astype(np.float32)
    R = np.zeros_like(A)
    R[..., np.arange(16), np.arange(16)] = norms
    return Q, R


@pytest.mark.parametrize("cell", [BATCH, SERVED])
def test_unorthogonalized_qr_fails_the_cell(cell):
    workload, _ = core.cell_files(cell)
    job = core.load_module("jobs", "qrd16")
    As = job.inputs(np.random.default_rng(2**31 + 13), {}, 64)
    Q, R = _unorthogonalized(As)
    assert np.abs(Q @ R - As).max() < 1e-5       # it multiplies back
    err = job.error(As, np.stack([Q, R], axis=1)).max()
    assert err > 100 * workload["limits"][job.NUMBER], err


# ---------------------------------------------------------------------------
# planted faults
# ---------------------------------------------------------------------------

def _initial_shmem(kw: dict, depth: int) -> np.ndarray:
    """Each block's shared memory as the launch received it, in grid
    order (multi-program form)."""
    gmap = np.asarray(kw["grid_map"])
    rows = []
    seen = [0] * len(kw["programs"])
    for k in gmap:
        img = np.asarray(kw["shmem"][k])
        img = img.view(np.uint32) if img.dtype == np.float32 else img
        rows.append(img[seen[k]] if img.ndim == 2 else img)
        seen[k] += 1
    out = np.zeros((gmap.size, depth), np.uint32)
    for b, r in enumerate(rows):
        out[b, :r.shape[0]] = r
    return out


def _break_shmem(real, fault: str):
    """``device.launch`` with the fault planted in the blocks' shared
    memory, where the programs leave their answers."""
    import jax.numpy as jnp

    counter = itertools.count()

    def launch(dcfg, *args, **kw):
        res = real(dcfg, *args, **kw)
        sh = np.array(res.shmem)
        init = _initial_shmem(kw, sh.shape[1])
        if fault == "unchanged":
            sh = init
        elif fault == "half":
            for b in range(sh.shape[0]):
                if next(counter) % 2:
                    sh[b] = init[b]
        elif fault == "altered":
            sh[0] = (sh[0].view(np.float32) + 1.0).view(np.uint32)
        else:                               # QR_FAULT, on the QRD blocks
            sh = _unorthogonalize_blocks(sh, init, np.asarray(kw["grid_map"]))
        res.shmem = jnp.asarray(sh)
        return res

    return launch


def _unorthogonalize_blocks(sh, init, gmap):
    """Each QRD block (program 1 of ``launch_fft_qrd``) given the Q and R
    of ``_unorthogonalized`` for the matrix it received."""
    from repro.core.programs.qrd import A_BASE, Q_BASE, R_BASE

    sh = sh.copy()
    for b in np.flatnonzero(gmap == 1):
        A = init[b, A_BASE:A_BASE + 256].view(np.float32).reshape(16, 16).T
        Q, R = _unorthogonalized(A)
        sh[b, Q_BASE:Q_BASE + 256] = Q.T.reshape(-1).view(np.uint32)
        sh[b, R_BASE:R_BASE + 256] = R.reshape(-1).view(np.uint32)
    return sh


def _break_gmem(real, fault: str):
    """``device.launch`` of the reduction with the fault planted in its
    global memory, where the total lands."""
    import jax.numpy as jnp
    from repro.core.device import pack_buffers

    def launch(dcfg, *args, **kw):
        if fault == "half":
            x = np.array(kw["buffers"]["x"])
            x[x.size // 2:] = 0
            kw = dict(kw, buffers=dict(kw["buffers"], x=x))
        res = real(dcfg, *args, **kw)
        gm = np.array(res.gmem)
        if fault == "unchanged":
            gm = np.asarray(pack_buffers(kw["buffers"], gm.size)[0])
        elif fault == "altered":
            off = res.buffer_offsets["result"][0]
            gm[off:off + 1] = (gm[off:off + 1].view(np.float32)
                               + 1.0).view(np.uint32)
        res.gmem = jnp.asarray(gm)
        return res

    return launch


def _run(cell: str, workload: dict, config: dict, seconds: float) -> dict:
    spec = core.benchmark_spec()
    return core.run_cell(workload, config, seed=2**31 + 3,
                         seconds=seconds, trace=False,
                         metrics=core.cell_metrics(spec, cell, False),
                         t_start=time.perf_counter())


def _served_small() -> tuple[dict, dict]:
    workload, config = core.cell_files(SERVED)
    workload, config = copy.deepcopy(workload), copy.deepcopy(config)
    workload["traffic"]["jobs"] = [{"job": "fft", "n": 256, "share": 1}]
    workload["rate_per_s"] = 40
    workload["pinned"] = {"fft256": workload["pinned"]["fft256"]}
    config["server"]["max_batch"] = 2
    return workload, config


CASES = {
    BATCH: ("repro.core.programs.mixed", _break_shmem,
            lambda: core.cell_files(BATCH), FAULTS + (QR_FAULT,)),
    REDUCE: ("repro.core.programs.reduction", _break_gmem,
             lambda: core.cell_files(REDUCE), FAULTS),
    SERVED: ("repro.serve.launch_server", _break_shmem, _served_small,
             FAULTS),
}


@pytest.mark.parametrize("cell", [BATCH, REDUCE, SERVED])
def test_planted_faults_are_not_correct(cell, monkeypatch):
    import importlib

    where, breaker, files, faults = CASES[cell]
    module = importlib.import_module(where)
    workload, config = files()
    clean = _run(cell, workload, config, 0.5)
    assert clean["correct"], clean["checks"]
    assert clean["attempted"] > 0 and clean["failed"] == 0
    real = module.launch
    for fault in faults:
        monkeypatch.setattr(module, "launch", breaker(real, fault))
        out = _run(cell, workload, config, 0.5)
        assert out["correct"] is False, (fault, out["checks"])
        monkeypatch.setattr(module, "launch", real)
