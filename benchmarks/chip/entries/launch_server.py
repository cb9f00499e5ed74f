"""Entry: single-block requests through ``repro.serve.LaunchServer`` with
its batcher thread running.

The configuration's ``server`` block sets the server (``max_batch``,
``schedule``, ``admission``, ``max_queue``); the traffic's ``jobs`` are the
request kinds. A request is one block of its job's kernel on its own
shared-memory image; it is done when its future has resolved and its
block's shared memory is on the host.

Set-up plays every batch the window can form, once: each batch size up to
``max_batch``, each mix of kinds in each wave of ``n_sms`` blocks, and each
kind leading the batch (the merged launch lists its programs in the order
they first appear), so that no merged launch meets its first compile in
the window.
"""
from __future__ import annotations

import itertools

import numpy as np

from chipbench import core


def setup(config: dict, traffic: dict) -> dict:
    from repro.serve import LaunchServer

    dev = core.device_config(config)
    jobs = [(core.load_module("jobs", j["job"]), j) for j in traffic["jobs"]]
    server = LaunchServer(dev, **config["server"])
    return {"device": dev, "server": server, "jobs": jobs,
            "kernels": [job.kernel(spec) for job, spec in jobs]}


def request(ctx: dict, j: int, x: np.ndarray):
    from repro.serve import LaunchRequest

    job, _spec = ctx["jobs"][j]
    return LaunchRequest(kernel=ctx["kernels"][j],
                         shmem=job.image(x, ctx["device"].sm.shmem_depth),
                         tag=j)


def batch_kinds(n_kinds: int, max_batch: int, width: int) -> list[list[int]]:
    """Every batch that differs in what the merged launch compiles: its
    size, the kinds in each wave of ``width`` blocks, and its leading
    kind."""
    out = []
    for n in range(1, max_batch + 1):
        sizes = [width] * (n // width) + ([n % width] if n % width else [])
        waves = [list(itertools.combinations_with_replacement(
            range(n_kinds), s)) for s in sizes]
        for combo in itertools.product(*waves):
            rest = [k for wave in combo[1:] for k in wave]
            for lead in sorted(set(combo[0])):
                first = list(combo[0])
                first.remove(lead)
                out.append([lead] + first + rest)
    return out


def warm(ctx: dict, rng: np.random.Generator) -> None:
    server = ctx["server"]
    kinds = batch_kinds(len(ctx["jobs"]), server.max_batch,
                        ctx["device"].n_sms)
    for batch in kinds:
        futs = []
        for j in batch:
            job, spec = ctx["jobs"][j]
            futs.append(server.submit(request(ctx, j, job.inputs(rng, spec,
                                                                 1)[0])))
        server.drain()
        for f in futs:
            finish(ctx, f.result())


def start(ctx: dict) -> None:
    ctx["server"].start()


def submit(ctx: dict, req):
    return ctx["server"].submit(req)


def finish(ctx: dict, res) -> tuple[np.ndarray, dict]:
    """A resolved request's answer on the host, and the simulated
    statistics of the batch it rode in."""
    if res.finish_reason != "ok":
        raise RuntimeError(f"request {res.rid}: {res.finish_reason}")
    job, spec = ctx["jobs"][res.tag]
    out = job.decode(np.asarray(res.shmem_f32())[0], spec)
    prof = res.profile
    return out, {"batch_id": res.batch_id, "batch_size": res.batch_size,
                 "instructions": int(prof["instructions"]),
                 "busy": {name: (p["blocks"], p["busy_cycles"])
                          for name, p in prof["per_program"].items()}}


def stop(ctx: dict, drain: bool) -> None:
    ctx["server"].stop(drain=drain)


def stat_devs(stats: list[dict], pinned: dict) -> dict[str, float]:
    """Per batch, the instructions issued and each program's busy cycles
    against the pinned per-block counts; the largest deviation of each."""
    inst = busy = 0
    for st in {s["batch_id"]: s for s in stats}.values():
        want = sum(blocks * int(pinned[name]["instructions"])
                   for name, (blocks, _) in st["busy"].items())
        inst = max(inst, abs(st["instructions"] - want))
        for name, (blocks, cycles) in st["busy"].items():
            busy = max(busy, abs(cycles
                                 - blocks * int(pinned[name]["busy_cycles"])))
    if not stats:
        return {"instructions_dev": float("nan"),
                "busy_cycles_dev": float("nan")}
    return {"instructions_dev": float(inst), "busy_cycles_dev": float(busy)}


def control(ctx: dict, j: int, xs: np.ndarray) -> np.ndarray:
    job, _spec = ctx["jobs"][j]
    return job.control(xs)
