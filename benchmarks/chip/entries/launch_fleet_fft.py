"""Entry: one uniform grid of FFT blocks across a fleet of simulated
eGPUs through ``repro.core.launch_fleet``, one device on each chip (the
configuration's ``fleet`` block, ``core.fleet_config``).

The traffic has one ``fft`` job with the ``count`` of blocks in every
launch. A launch returns when X is on the host.
"""
from __future__ import annotations

import numpy as np

from chipbench import core


def setup(config: dict, traffic: dict) -> dict:
    (spec,) = traffic["jobs"]
    if spec["job"] != "fft":
        raise ValueError("launch_fleet_fft takes one fft job")
    return {"fleet": core.fleet_config(config),
            "jobs": [(core.load_module("jobs", "fft"), spec)]}


def inputs(ctx: dict, rng: np.random.Generator) -> list[np.ndarray]:
    ((job, spec),) = ctx["jobs"]
    return [job.inputs(rng, spec, int(spec["count"]))]


def call(ctx: dict, inputs: list[np.ndarray]):
    import repro.core

    ((job, spec),) = ctx["jobs"]
    fleet = ctx["fleet"]
    xs = inputs[0]
    images = np.stack([job.image(x, fleet.device.sm.shmem_depth)
                       for x in xs])
    res = repro.core.launch_fleet(fleet, programs=[job.kernel(spec)],
                                  grid_map=[0] * len(xs), shmem=[images])
    mem = np.asarray(res.shmem).view(np.float32)
    return [np.stack([job.decode(m, spec) for m in mem])], res


def stats(res) -> dict:
    return {"cycles": int(res.cycles), "instructions": int(res.steps)}


def control(ctx: dict, inputs: list[np.ndarray]) -> list[np.ndarray]:
    ((job, _),) = ctx["jobs"]
    return [job.control(inputs[0])]
