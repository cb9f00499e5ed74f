"""The JAX profiler's trace of a window, reduced to busy time and gaps.

``capture()`` traces the window into a temporary directory, and ``read``
keeps three things of it: the ``bench.window`` span that bounds the window,
the benchmark's other host spans (``bench.launch``, ``bench.submit``), and
each device's operations (the ``XLA Ops`` line of every TPU plane). The
reductions below work on those plain intervals, so a small recorded trace
(``tests/data``) checks them.

- busy: the union of a device's operation intervals inside the window,
  averaged over the devices;
- idle gaps: the window's stretches outside that union, each labelled by
  the name of the benchmark's host spans that overlap it most
  (``host.other`` where more of it lies outside every span), summed per
  label;
- device ops: the time each operation took inside the window, by the XLA
  module it ran in and its HLO opcode.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re
import shutil
import tempfile
import types

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OTHER = "host.other"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")     # not its SparseCores
OPCODE = re.compile(r"\s([a-z][a-z0-9_-]*)\(")
MODULE_ID = re.compile(r"\(\d+\)$")


def merge(intervals) -> list[tuple[int, int]]:
    """Sorted, disjoint union of (start, end) intervals."""
    out: list[list[int]] = []
    for s, e in sorted((int(s), int(e)) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(merged, lo: int, hi: int) -> list[tuple[int, int]]:
    """The stretches of [lo, hi) that no merged interval covers."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


class Spans:
    """Host spans, sorted by start, for labelling gaps."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda sp: sp[1])
        self.starts = [sp[1] for sp in self.spans]
        self.longest = max((e - s for _, s, e in self.spans), default=0)

    def label(self, gap: tuple[int, int]) -> str:
        """The name of the spans overlapping ``gap`` most, summed per
        name; ``OTHER`` where the part that no span covers is larger."""
        lo = bisect.bisect_left(self.starts, gap[0] - self.longest)
        hi = bisect.bisect_left(self.starts, gap[1])
        near = self.spans[lo:hi]
        tot: dict[str, int] = {}
        for n, s, e in near:
            ov = min(e, gap[1]) - max(s, gap[0])
            if ov > 0:
                tot[n] = tot.get(n, 0) + ov
        covered = sum(e - s for s, e in merge(clip(
            [(s, e) for _, s, e in near], *gap)))
        tot[OTHER] = gap[1] - gap[0] - covered
        return max(sorted(tot), key=tot.get)


@dataclasses.dataclass
class Trace:
    window: tuple[int, int]                 # ns, the bench.window span
    device_ops: list[list[tuple[str, int, int]]]   # per device
    host_spans: list[tuple[str, int, int]]

    def _busy(self, ops) -> list[tuple[int, int]]:
        return merge(clip([(s, e) for _, s, e in ops], *self.window))

    def busy_s(self) -> float:
        if not self.device_ops:
            return 0.0
        per = [sum(e - s for s, e in self._busy(ops))
               for ops in self.device_ops]
        return sum(per) / len(per) / 1e9

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def top_ops(self, k: int = 10) -> list[list]:
        tot: dict[str, int] = {}
        for ops in self.device_ops:
            for name, s, e in ops:
                c = clip([(s, e)], *self.window)
                if c:
                    tot[name] = tot.get(name, 0) + c[0][1] - c[0][0]
        n = max(len(self.device_ops), 1)
        top = sorted(tot.items(), key=lambda kv: (-kv[1], kv[0]))[:k]
        return [[name, ns / n / 1e9] for name, ns in top]

    def idle_by_label(self, k: int = 10) -> list[list]:
        """Idle seconds of the first device, summed per host label."""
        ops = self.device_ops[0] if self.device_ops else []
        spans = Spans(self.host_spans)
        tot: dict[str, int] = {}
        for g in gaps(self._busy(ops), *self.window):
            name = spans.label(g)
            tot[name] = tot.get(name, 0) + g[1] - g[0]
        top = sorted(tot.items(), key=lambda kv: (-kv[1], kv[0]))[:k]
        return [[name, ns / 1e9] for name, ns in top]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps":
                self.idle_by_label()}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(window=tuple(d["window"]),
                   device_ops=[[tuple(o) for o in ops]
                               for ops in d["device_ops"]],
                   host_spans=[tuple(s) for s in d["host_spans"]])


def opcode(hlo: str) -> str:
    """The HLO opcode of an op's trace name (``%fusion.3 = u32[8]{0}
    fusion(...)`` gives ``fusion``); a name that is no HLO text stays."""
    m = OPCODE.search(hlo.partition(" = ")[2])
    return m.group(1) if m else hlo


def _device_ops(plane) -> list[tuple[str, int, int]]:
    """A device plane's ops, each named ``<module>:<opcode>`` by the XLA
    module (``XLA Modules`` line) it ran in."""
    mods = sorted((int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                   MODULE_ID.sub("", ev.name))
                  for line in plane.lines if line.name == "XLA Modules"
                  for ev in line.events)
    starts = [m[0] for m in mods]
    ops = []
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            s = int(ev.start_ns)
            e = s + int(ev.duration_ns)
            i = bisect.bisect_right(starts, s) - 1
            name = opcode(ev.name)
            if i >= 0 and mods[i][1] >= e:
                name = f"{mods[i][2]}:{name}"
            ops.append((name, s, e))
    return ops


def read(directory: str) -> Trace:
    """The window, host spans and device ops of the trace in
    ``directory``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                        recursive=True)
    data = ProfileData.from_file(path)
    window, spans, devices = None, [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(_device_ops(plane))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith(SPAN_PREFIX):
                        continue
                    iv = (int(ev.start_ns),
                          int(ev.start_ns + ev.duration_ns))
                    if ev.name == WINDOW_SPAN:
                        window = iv
                    else:
                        spans.append((ev.name, *iv))
    if window is None:
        raise RuntimeError(f"no {WINDOW_SPAN} span in the trace")
    return Trace(window=window, device_ops=devices, host_spans=spans)


def options():
    """The profiler's options: the device trace, and on the host only the
    annotated spans (``TraceAnnotation``, host tracer level 1). JAX's
    default also traces every Python call (Python tracer level 1), which
    slows the host so far that a served cell near its knee would be
    measured past it."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    return opts


@contextlib.contextmanager
def capture():
    """Trace the body; ``.trace`` holds the reduced trace afterwards."""
    import jax

    holder = types.SimpleNamespace(trace=None)
    directory = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        jax.profiler.start_trace(directory, profiler_options=options())
        try:
            yield holder
        finally:
            jax.profiler.stop_trace()
        holder.trace = read(directory)
    finally:
        shutil.rmtree(directory, ignore_errors=True)
