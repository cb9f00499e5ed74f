"""Job: one 16x16 modified Gram-Schmidt QR decomposition
(spec ``{"job": "qrd16"}``).

Inputs are standard normal float32 matrices. The program's ``Q`` and ``R``
are checked in float64: ``Q @ R`` against the input, ``Q^T Q`` against the
identity and R's strict lower triangle against zero. The number compared
is ``qrd16_rel_err``, the largest over the window's matrices and the three
readings (``reference.qr_rel_err``).
"""
from __future__ import annotations

import numpy as np

from chipbench import reference

NUMBER = "qrd16_rel_err"


def inputs(rng: np.random.Generator, spec: dict, count: int) -> np.ndarray:
    return rng.standard_normal((count, 16, 16)).astype(np.float32)


def error(As: np.ndarray, outs: np.ndarray) -> np.ndarray:
    """``outs`` is (count, 2, 16, 16): Q and R of each matrix."""
    return reference.qr_rel_err(As, outs[:, 0], outs[:, 1])


def control(As: np.ndarray) -> np.ndarray:
    return np.stack(reference.qr_control(As), axis=1)


def kernel(spec: dict):
    from repro.core.programs.qrd import qrd_kernel

    return qrd_kernel()


def image(a: np.ndarray, depth: int) -> np.ndarray:
    from repro.core.programs.qrd import qrd_shmem

    return qrd_shmem(a, depth)


def decode(mem: np.ndarray, spec: dict) -> np.ndarray:
    """Q and R, as one (2, 16, 16) array, from a block's final shared
    memory (float32 view): Q is stored column-major, R row-major."""
    from repro.core.programs.qrd import Q_BASE, R_BASE

    q = mem[Q_BASE:Q_BASE + 256].reshape(16, 16).T
    r = mem[R_BASE:R_BASE + 256].reshape(16, 16)
    return np.stack([q, r])
