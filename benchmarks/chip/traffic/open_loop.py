"""Traffic kind ``open_loop``: independent clients on the wall clock.

Requests arrive as a Poisson stream at the cell's fixed ``rate_per_s``,
for ``--seconds``. Every seed gets the same work: the same set of
exponential gaps (its quantiles at ``(i + 0.5) / n``) and the same number
of each job kind (in the traffic's ``share``), in the seed's own order,
with inputs drawn from the seed. Requests are built before the window.

One thread submits each request when it is due; a second thread takes
each resolved future and brings its answer to the host. A request's
latency runs from when it was due to when its answer was on the host, so
a submit that blocks delays every later request and shows. A request that
fails, is refused or is not done a minute after the last one was due
counts as failed, with the latency of the wait.
"""
from __future__ import annotations

import gc
import queue
import sys
import threading
import time
import traceback

import jax
import numpy as np

GRACE_S = 60.0


def schedule(rate: float, seconds: float, shares: list[float],
             rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    """(due offsets in seconds, job index) of each request."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    due = np.cumsum(rng.permutation(gaps))
    want = np.asarray(shares, np.float64) / np.sum(shares) * n
    counts = np.floor(want).astype(int)
    for j in np.argsort(counts - want)[:n - counts.sum()]:
        counts[j] += 1
    kinds = rng.permutation(np.repeat(np.arange(len(shares)), counts))
    return due, kinds


class Run:
    def __init__(self, entry, config: dict, workload: dict, seed: int,
                 seconds: float, rate: float | None = None):
        self.entry = entry
        self.pinned = workload["pinned"]
        self.ctx = entry.setup(config, workload["traffic"])
        rng = np.random.default_rng(seed)
        jobs = self.ctx["jobs"]
        self.due, self.kinds = schedule(
            rate if rate is not None else float(workload["rate_per_s"]),
            seconds, [spec["share"] for _, spec in jobs], rng)
        self.inputs: list = [None] * len(self.kinds)
        for j, (job, spec) in enumerate(jobs):
            idx = np.flatnonzero(self.kinds == j)
            for i, x in zip(idx, job.inputs(rng, spec, idx.size)):
                self.inputs[i] = x
        self.requests = [entry.request(self.ctx, int(j), x)
                         for j, x in zip(self.kinds, self.inputs)]
        self.answers: list = []         # (request, answer) of every window
        self.stats: list = []
        entry.warm(self.ctx, np.random.default_rng([seed, 1]))

    def window(self, rec, seconds: float | None = None) -> None:
        """Offer the requests due in the first ``seconds`` (all of them
        by default); their answers join any earlier window's."""
        entry, ctx = self.entry, self.ctx
        n = len(self.requests)
        if seconds is not None:
            n = int(np.searchsorted(self.due, seconds))
        done_at = np.full(n, np.nan)
        k0 = len(self.stats)
        resolved: queue.Queue = queue.Queue()
        all_done = threading.Event()
        errors: list = []

        def collect():
            left = n
            while True:
                item = resolved.get()
                if item is None:
                    return
                i, fut = item
                try:
                    if fut is None:         # the submit itself failed
                        raise RuntimeError(f"request {i} was not submitted")
                    out, st = entry.finish(ctx, fut.result())
                    done_at[i] = time.perf_counter()
                    self.answers.append((i, out))
                    self.stats.append(st)
                except Exception:
                    errors.append(traceback.format_exc())
                left -= 1
                if left == 0:
                    all_done.set()

        collector = threading.Thread(target=collect, name="bench-collect")
        collector.start()
        entry.start(ctx)
        late = []
        try:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.window"):
                for i in range(n):
                    due = t0 + self.due[i]
                    wait = due - time.perf_counter()
                    if wait > 0:
                        time.sleep(wait)
                    try:
                        with jax.profiler.TraceAnnotation("bench.submit"):
                            fut = entry.submit(ctx, self.requests[i])
                    except Exception:
                        errors.append(traceback.format_exc())
                        resolved.put((i, None))
                        continue
                    late.append((time.perf_counter() - due) * 1e3)
                    fut.add_done_callback(
                        lambda f, i=i: resolved.put((i, f)))
                if n:
                    all_done.wait(timeout=t0 + self.due[n - 1] + GRACE_S
                                  - time.perf_counter())
            t_end = time.perf_counter()
        finally:
            entry.stop(ctx, drain=False)
            resolved.put(None)
            collector.join()
        if errors:
            print(errors[0], file=sys.stderr)
        due_at = t0 + self.due[:n]
        ok = np.isfinite(done_at)
        rec.attempted = n
        rec.failed = int(n - ok.sum())
        rec.latencies_ms = list((np.where(ok, done_at, t_end) - due_at)
                                * 1e3)
        rec.window_s = float(np.nanmax(np.where(ok, done_at, t_end)) - t0)
        rec.gen_late_ms = late
        rec.batch_sizes = [s["batch_size"] for s in self.stats[k0:]]

    def _errors(self, outputs_of) -> dict[str, float]:
        numbers = {}
        for j, (job, _spec) in enumerate(self.ctx["jobs"]):
            got = [(i, out) for i, out in self.answers if self.kinds[i] == j]
            if not got:
                numbers[job.NUMBER] = float("nan")
                continue
            xs = np.stack([self.inputs[i] for i, _ in got])
            outs = outputs_of(j, [out for _, out in got], xs)
            numbers[job.NUMBER] = float(job.error(xs, outs).max())
        return numbers

    def compare(self, rec) -> dict[str, float]:
        """The numbers that decide ``correct``: every finished request's
        answer against the reference, and every batch's simulated
        statistics against the pinned per-block counts."""
        gc.collect()
        numbers = self._errors(lambda _j, outs, _xs: np.stack(outs))
        numbers.update(self.entry.stat_devs(self.stats, self.pinned))
        return numbers

    def control_numbers(self) -> dict[str, float]:
        return self._errors(
            lambda j, _outs, xs: self.entry.control(self.ctx, j, xs))


def prepare(entry, config: dict, workload: dict, seed: int,
            seconds: float) -> Run:
    return Run(entry, config, workload, seed, seconds)
