"""Job: one n-point complex FFT (spec ``{"job": "fft", "n": 256}``).

Inputs are standard normal complex64 signals. The program's answer is
checked against ``np.fft.fft`` in float64; the number compared is
``fft_rel_err``, the largest over the window's signals of
max |X - fft(x)| / max |fft(x)|.
"""
from __future__ import annotations

import numpy as np

from chipbench import reference

NUMBER = "fft_rel_err"


def inputs(rng: np.random.Generator, spec: dict, count: int) -> np.ndarray:
    n = int(spec["n"])
    return (rng.standard_normal((count, n))
            + 1j * rng.standard_normal((count, n))).astype(np.complex64)


def error(xs: np.ndarray, outs: np.ndarray) -> np.ndarray:
    return reference.fft_rel_err(xs, outs)


def control(xs: np.ndarray) -> np.ndarray:
    return reference.fft_control(xs)


def kernel(spec: dict):
    from repro.core.programs.fft import fft_kernel

    return fft_kernel(int(spec["n"]))


def image(x: np.ndarray, depth: int) -> np.ndarray:
    from repro.core.programs.fft import fft_shmem

    return fft_shmem(x, depth)


def decode(mem: np.ndarray, spec: dict) -> np.ndarray:
    """The FFT from a block's final shared memory (float32 view): the
    program leaves it interleaved and in bit-reversed order."""
    from repro.core.programs.fft import bitrev_indices

    n = int(spec["n"])
    out = np.empty(n, np.complex64)
    out[bitrev_indices(n)] = mem[0:2 * n:2] + 1j * mem[1:2 * n:2]
    return out
