"""Entry: one fused two-level grid reduction through
``repro.core.programs.launch_reduction(fused=True)``: the stage-1 blocks,
a barrier, then the one stage-2 block, all in one launch over global
memory. The traffic has one ``reduce`` job; a launch returns when the
total is on the host.
"""
from __future__ import annotations

import numpy as np

from chipbench import core


def setup(config: dict, traffic: dict) -> dict:
    (spec,) = traffic["jobs"]
    if spec["job"] != "reduce":
        raise ValueError("launch_reduction takes one reduce job")
    return {"device": core.device_config(config),
            "jobs": [(core.load_module("jobs", "reduce"), spec)]}


def inputs(ctx: dict, rng: np.random.Generator) -> list[np.ndarray]:
    ((job, spec),) = ctx["jobs"]
    return [job.inputs(rng, spec, 1)]


def call(ctx: dict, inputs: list[np.ndarray]):
    from repro.core.programs import launch_reduction

    total, res = launch_reduction(inputs[0][0], device=ctx["device"],
                                  fused=True)
    return [np.array([total], np.float32)], res


def stats(res) -> dict:
    return {"cycles": int(res.cycles), "instructions": int(res.steps)}


def control(ctx: dict, inputs: list[np.ndarray]) -> list[np.ndarray]:
    ((job, _),) = ctx["jobs"]
    return [job.control(inputs[0])]
