"""The program's own spans of a traced window, per call and per phase.

The program under test keeps its finished spans in memory
(``repro.core.tracing``): one root span per call into it, each layer
boundary inside it a child. A traced window is a closed loop of
``rec.attempted`` calls, each inside one ``bench.launch`` span of the
profiler's trace, and nothing calls the program between that window and
the readers; so the program's last ``rec.attempted`` roots are the
window's calls, in order. Each call's time is split into the launch
phases below by self time (a span's duration less its children's), and
``other`` is the rest of its ``bench.launch`` span: time that no launch
phase names.

A reader of another span family (a fleet's, say) takes its names to
``median_ms_of`` and leaves ``PHASES`` as it is: it reports that family's
own time, which stays inside ``other``.

A program that keeps no spans, or a window whose roots and calls do not
pair up one for one, reads nothing.
"""
from __future__ import annotations

import collections
import statistics

# phase -> names of the program's spans whose self time it sums; a name
# ending in "." takes every span under that prefix
PHASES = {
    "plan": ("egpu.launch.plan", "egpu.plan."),
    "schedule": ("egpu.launch.schedule",),
    "stage": ("egpu.inputs", "egpu.launch.stage"),
    "dispatch": ("egpu.launch.dispatch",),
    "unpack": ("egpu.launch.unpack",),
    "readback": ("egpu.readback",),
}
CALL_SPAN = "bench.launch"


def _matches(name: str, names) -> bool:
    return any(name == n or (n.endswith(".") and name.startswith(n))
               for n in names)


def phase_of(name: str) -> str | None:
    for phase, names in PHASES.items():
        if _matches(name, names):
            return phase
    return None


def _tracing():
    """The program's ``repro.core.tracing``; None where it has none."""
    try:
        from repro.core import tracing
    except ImportError:
        return None
    return tracing


def _paired(rec) -> list[tuple[float, list[tuple[str, float]]]] | None:
    """Per call of the traced window, in order: its ``bench.launch`` span
    in ms, and each of its spans' name and self time in ms; None where the
    window's calls and the program's roots do not pair up one for one, or
    a root outlasts the ``bench.launch`` span it pairs with."""
    tracing = _tracing()
    if tracing is None or rec.trace is None or rec.attempted < 1:
        return None
    spans = tracing.recent()
    launches = sorted((s, e) for name, s, e in rec.trace.host_spans
                      if name == CALL_SPAN)
    roots = [sp for sp in spans if sp.parent is None]
    if len(roots) < rec.attempted or len(launches) != rec.attempted:
        return None
    roots = roots[-rec.attempted:]
    if any(r.end_ns - r.start_ns > e - s
           for r, (s, e) in zip(roots, launches)):
        return None
    ids = {r.id for r in roots}
    tree = collections.defaultdict(list)        # root id -> its spans
    child_ns = collections.Counter()            # span id -> children's ns
    for sp in spans:
        if sp.root in ids:
            tree[sp.root].append(sp)
            if sp.parent is not None:
                child_ns[sp.parent] += sp.end_ns - sp.start_ns
    return [((e - s) / 1e6,
             [(sp.name, (sp.end_ns - sp.start_ns - child_ns[sp.id]) / 1e6)
              for sp in tree[r.id]])
            for r, (s, e) in zip(roots, launches)]


def calls(rec) -> list[dict[str, float]] | None:
    """Per call of the traced window, in order: each phase's summed self
    time in ms, and ``other``; None where ``_paired`` finds no pairing."""
    per = _paired(rec)
    if per is None:
        return None
    out = []
    for launch_ms, selfs in per:
        ms = dict.fromkeys(PHASES, 0.0)
        for name, self_ms in selfs:
            phase = phase_of(name)
            if phase is not None:
                ms[phase] += self_ms
        ms["other"] = launch_ms - sum(ms.values())
        out.append(ms)
    return out


def median_ms(rec, phase: str) -> float | None:
    """The median over the window's calls of ``phase``'s milliseconds
    (a phase of ``PHASES``, or ``other``)."""
    if phase != "other":
        return median_ms_of(rec, PHASES[phase])
    per = calls(rec)
    if per is None:
        return None
    return statistics.median(c["other"] for c in per)


def median_ms_of(rec, names) -> float | None:
    """The median over the window's calls of the self time, in ms, summed
    over the call's spans whose names match ``names`` (a name ending in
    "." takes every span under that prefix); None where the calls do not
    pair up, or no span of the window matches."""
    per = _paired(rec)
    if per is None:
        return None
    sums, seen = [], False
    for _launch_ms, selfs in per:
        got = [ms for name, ms in selfs if _matches(name, names)]
        seen = seen or bool(got)
        sums.append(sum(got, 0.0))
    return statistics.median(sums) if seen else None


def total_s(prefix: str) -> float | None:
    """Seconds of self time the program has spent, since it started, in
    spans (or JAX compile-path events) whose names start with ``prefix``;
    None where it keeps no totals or has none under that prefix."""
    tracing = _tracing()
    if tracing is None:
        return None
    got = [s for name, (_n, s) in tracing.totals().items()
           if name.startswith(prefix)]
    return sum(got) if got else None
