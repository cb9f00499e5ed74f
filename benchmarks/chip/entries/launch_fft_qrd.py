"""Entry: one mixed grid of FFTs and 16x16 QRDs through
``repro.core.programs.mixed.launch_fft_qrd`` (and so ``device.launch``).

The traffic's ``jobs`` are the FFT job first and the QRD job second, each
with the ``count`` of blocks it puts in every launch. A launch returns when
X, Q and R are on the host.
"""
from __future__ import annotations

import numpy as np

from chipbench import core


def setup(config: dict, traffic: dict) -> dict:
    fft, qrd = traffic["jobs"]
    if fft["job"] != "fft" or qrd["job"] != "qrd16":
        raise ValueError("launch_fft_qrd takes an fft job, then a qrd16 job")
    return {"device": core.device_config(config),
            "jobs": [(core.load_module("jobs", j["job"]), j)
                     for j in traffic["jobs"]]}


def inputs(ctx: dict, rng: np.random.Generator) -> list[np.ndarray]:
    return [job.inputs(rng, spec, int(spec["count"]))
            for job, spec in ctx["jobs"]]


def call(ctx: dict, inputs: list[np.ndarray]):
    from repro.core.programs.mixed import launch_fft_qrd

    X, Q, R, res = launch_fft_qrd(inputs[0], inputs[1], device=ctx["device"])
    return [X, np.stack([Q, R], axis=1)], res


def stats(res) -> dict:
    return {"cycles": int(res.cycles), "instructions": int(res.steps)}


def control(ctx: dict, inputs: list[np.ndarray]) -> list[np.ndarray]:
    return [job.control(x) for (job, _), x in zip(ctx["jobs"], inputs)]
