"""Job: the float32 sum of an n-word vector (spec
``{"job": "reduce", "n": 7680}``).

Inputs are standard normal float32 vectors. The program's total is checked
against the float64 sum; the number compared is ``reduce_rel_err``, the
largest over the window's vectors of |total - sum(x)| / sum |x|.
"""
from __future__ import annotations

import numpy as np

from chipbench import reference

NUMBER = "reduce_rel_err"


def inputs(rng: np.random.Generator, spec: dict, count: int) -> np.ndarray:
    return rng.standard_normal((count, int(spec["n"]))).astype(np.float32)


def error(xs: np.ndarray, totals: np.ndarray) -> np.ndarray:
    return reference.sum_rel_err(xs, totals)


def control(xs: np.ndarray) -> np.ndarray:
    return reference.sum_control(xs)
