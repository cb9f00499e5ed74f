"""Traffic kind ``closed_loop``: one caller, each launch after the last.

The caller draws a launch's inputs from the seed, calls the entry, and goes
on once the results are on the host, until ``--seconds`` have passed.
Set-up makes ``warm_launches`` launches of the same shapes first. Every
launch of the window is timed and compared: each job's answers against the
plain reference, and the launch's simulated statistics against the cell's
``pinned`` values, which a change made for speed may not move.
"""
from __future__ import annotations

import gc
import sys
import time
import traceback

import jax
import numpy as np


class Run:
    def __init__(self, entry, config: dict, workload: dict, seed: int,
                 seconds: float):
        self.entry = entry
        self.seconds = float(seconds)
        self.pinned = workload["pinned"]
        self.ctx = entry.setup(config, workload["traffic"])
        self.rng = np.random.default_rng(seed)
        self.done: list = []            # (inputs, outputs) per launch
        self.stats: list[dict] = []     # simulated statistics per launch
        self.last = None
        for _ in range(int(workload["traffic"]["warm_launches"])):
            entry.call(self.ctx, entry.inputs(self.ctx, self.rng))

    def window(self, rec, seconds: float | None = None) -> None:
        """Launch until ``seconds`` (the run's) have passed; every launch
        joins those that ``compare`` checks."""
        entry, ctx = self.entry, self.ctx
        seconds = self.seconds if seconds is None else seconds
        done = 0
        t0 = t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            while t1 - t0 < seconds:
                inputs = entry.inputs(ctx, self.rng)
                rec.attempted += 1
                ts = time.perf_counter()
                try:
                    with jax.profiler.TraceAnnotation("bench.launch"):
                        outputs, res = entry.call(ctx, inputs)
                except Exception:
                    if not rec.failed:
                        traceback.print_exc(file=sys.stderr)
                    rec.failed += 1
                    t1 = time.perf_counter()
                    continue
                t1 = time.perf_counter()
                rec.latencies_ms.append((t1 - ts) * 1e3)
                self.done.append((inputs, outputs))
                self.stats.append(entry.stats(res))
                self.last = res
                done += 1
        rec.window_s = t1 - t0
        rec.instructions = done * int(self.pinned["instructions"])
        if self.last is not None:
            rec.profile = self.last.profile()

    def _errors(self, outputs_of) -> dict[str, float]:
        numbers = {}
        for j, (job, _spec) in enumerate(self.ctx["jobs"]):
            if not self.done:
                numbers[job.NUMBER] = float("nan")
                continue
            xs = np.concatenate([inp[j] for inp, _ in self.done])
            outs = np.concatenate([outputs_of(inp, out)[j]
                                   for inp, out in self.done])
            numbers[job.NUMBER] = float(job.error(xs, outs).max())
        return numbers

    def compare(self, rec) -> dict[str, float]:
        """The numbers that decide ``correct``, once the program's device
        state is freed."""
        self.last = None
        gc.collect()
        numbers = self._errors(lambda _inp, out: out)
        for key, want in self.pinned.items():
            numbers[f"{key}_dev"] = float(max(
                (abs(s[key] - int(want)) for s in self.stats),
                default=float("nan")))
        return numbers

    def control_numbers(self) -> dict[str, float]:
        """The same comparison with the control in the program's place,
        on the inputs the window drew."""
        return self._errors(
            lambda inp, _out: self.entry.control(self.ctx, inp))


def prepare(entry, config: dict, workload: dict, seed: int,
            seconds: float) -> Run:
    return Run(entry, config, workload, seed, seconds)
