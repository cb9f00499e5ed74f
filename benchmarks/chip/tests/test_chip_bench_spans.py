"""The readers of the program's own spans (``chipbench.program_spans`` and
the ``launch.*`` and ``setup.*`` metrics) on a hand-made traced window and
recorded spans, and each case in which they read nothing."""
from __future__ import annotations

import statistics
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))

from chipbench import core, program_spans  # noqa: E402
from chipbench.profile_trace import Trace  # noqa: E402
from repro.core import tracing  # noqa: E402
from repro.core.tracing import Span  # noqa: E402

LAUNCH = ("launch.plan_ms", "launch.schedule_ms", "launch.stage_ms",
          "launch.dispatch_ms", "launch.unpack_ms", "launch.readback_ms",
          "launch.other_ms")
MS = 1_000_000


def _call(first_id: int, t0: int, scale: int) -> list[Span]:
    """One call's spans, children before their parent, as the program
    keeps them: a root of 20 ms x ``scale`` with one wave."""
    r, i = first_id, first_id

    def sp(name, a, b, parent):
        nonlocal i
        i += 1
        return Span(name, t0 + a * scale, t0 + b * scale, parent, r, i)

    launch = r + 100
    spans = [
        sp("egpu.inputs", 0, 1 * MS, r),
        sp("egpu.plan.lower", 2 * MS, 3 * MS, launch + 1),
        sp("egpu.launch.schedule", 3 * MS, 4 * MS, launch + 1),
        Span("egpu.launch.plan", t0 + 1 * MS * scale, t0 + 5 * MS * scale,
             launch, r, launch + 1),
        sp("egpu.launch.stage", 5 * MS, 6 * MS, launch),
        sp("egpu.launch.dispatch", 6 * MS, 9 * MS, launch),
        sp("egpu.launch.unpack", 9 * MS, 13 * MS, launch),
        Span("egpu.launch", t0 + 1 * MS * scale, t0 + 14 * MS * scale, r, r,
             launch),
        sp("egpu.readback", 14 * MS, 19 * MS, r),
        Span("egpu.launch_fft_qrd", t0, t0 + 20 * MS * scale, None, r, r),
    ]
    return spans


def _window(scales=(1, 2), bench_ms=(21, 41)):
    """Spans of an earlier call, then one call per scale; the traced
    window's ``bench.launch`` spans, on the profiler's own clock."""
    spans = _call(1, 0, 1)
    host = [("bench.window", 0, 10**12)]
    for k, (scale, ms) in enumerate(zip(scales, bench_ms)):
        t0 = (k + 1) * 10**9
        spans += _call(1000 * (k + 1), t0, scale)
        host.append(("bench.launch", 5 * 10**9 + t0, 5 * 10**9 + t0 + ms * MS))
    rec = core.Record()
    rec.attempted = len(scales)
    rec.trace = Trace(window=(0, 10**12), device_ops=[], host_spans=host)
    return rec, spans


@pytest.fixture
def recorded(monkeypatch):
    """The traced window's record, with the program's ring holding the
    window's spans."""
    rec, spans = _window()
    monkeypatch.setattr(tracing, "recent", lambda: list(spans))
    return rec


def _read(name, rec):
    return core.load_module("metrics", name).read(rec)


def test_phases_of_each_call(recorded):
    assert program_spans.calls(recorded) == [
        {"plan": 3.0, "schedule": 1.0, "stage": 2.0, "dispatch": 3.0,
         "unpack": 4.0, "readback": 5.0, "other": 3.0},
        {"plan": 6.0, "schedule": 2.0, "stage": 4.0, "dispatch": 6.0,
         "unpack": 8.0, "readback": 10.0, "other": 5.0},
    ]


def test_launch_readers_take_the_median(recorded):
    want = {"launch.plan_ms": 4.5, "launch.schedule_ms": 1.5,
            "launch.stage_ms": 3.0, "launch.dispatch_ms": 4.5,
            "launch.unpack_ms": 6.0, "launch.readback_ms": 7.5,
            "launch.other_ms": 4.0}
    got = {name: _read(name, recorded) for name in LAUNCH}
    assert got == pytest.approx(want)
    # the phases and the rest make up the median call
    assert sum(got.values()) == pytest.approx(31.0)


@pytest.mark.parametrize("phase", list(program_spans.PHASES))
def test_median_ms_of_a_phase_reads_as_its_calls(recorded, phase):
    """``median_ms_of`` over a phase's names gives the median of that
    phase in ``calls``, the split the ``launch.*`` readers took before it
    existed, to the bit."""
    got = program_spans.median_ms_of(recorded, program_spans.PHASES[phase])
    per = program_spans.calls(recorded)
    assert got == statistics.median(c[phase] for c in per)
    assert got == program_spans.median_ms(recorded, phase)


def test_median_ms_of_reads_a_new_span_family(monkeypatch):
    """A span family that ``PHASES`` does not name is read by its own
    names, and its time stays inside ``other``."""
    rec, spans = _window()
    monkeypatch.setattr(tracing, "recent", lambda: list(spans))
    before = program_spans.calls(rec)
    # each call's egpu.launch ends with 1 ms x scale of a fleet's merge
    merges = [Span("egpu.fleet.merge", sp.end_ns - (sp.end_ns - sp.start_ns)
                   // 13, sp.end_ns, sp.id, sp.root, sp.id + 100)
              for sp in spans if sp.name == "egpu.launch"]
    spans += merges
    assert program_spans.phase_of("egpu.fleet.merge") is None
    assert program_spans.median_ms_of(rec, ("egpu.fleet.merge",)) == 1.5
    assert program_spans.median_ms_of(rec, ("egpu.fleet.",)) == 1.5
    assert program_spans.median_ms_of(rec, ("egpu.fleet.route",)) is None
    assert program_spans.calls(rec) == before
    rec.trace = None
    assert program_spans.median_ms_of(rec, ("egpu.fleet.",)) is None


def test_setup_readers_sum_the_totals(monkeypatch):
    monkeypatch.setattr(tracing, "totals", lambda: {
        "egpu.plan.lower": (2, 1.5), "egpu.plan.partial_eval": (2, 4.0),
        "egpu.launch.plan": (9, 0.25), "jax.trace": (40, 2.0),
        "jax.compile": (3, 6.5), "jax.cache_load": (3, 0.5)})
    assert _read("setup.plan_s", core.Record()) == pytest.approx(5.5)
    assert _read("setup.jax_s", core.Record()) == pytest.approx(9.0)
    monkeypatch.setattr(tracing, "totals", lambda: {"egpu.launch": (1, 1.0)})
    assert _read("setup.plan_s", core.Record()) is None
    assert _read("setup.jax_s", core.Record()) is None


@pytest.mark.parametrize("case", ["no_trace", "no_attempts", "few_roots",
                                  "more_calls", "root_outlasts_call"])
def test_a_window_that_does_not_pair_up_reads_nothing(case, monkeypatch):
    rec, spans = _window()
    if case == "no_trace":
        rec.trace = None
    elif case == "no_attempts":
        rec.attempted = 0
    elif case == "few_roots":
        spans = [sp for sp in spans if sp.root >= 2000]    # one call left
    elif case == "more_calls":
        rec.trace.host_spans.append(("bench.launch", 9 * 10**9,
                                     9 * 10**9 + 30 * MS))
    else:
        rec, spans = _window(bench_ms=(21, 39))     # 40 ms root in 39
    monkeypatch.setattr(tracing, "recent", lambda: list(spans))
    assert program_spans.calls(rec) is None
    for name in LAUNCH:
        assert _read(name, rec) is None, name


def test_a_program_without_spans_reads_nothing(recorded, monkeypatch):
    # a program that keeps no spans has no repro.core.tracing to import
    import repro.core

    monkeypatch.delattr(repro.core, "tracing")
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    for name in LAUNCH + ("setup.plan_s", "setup.jax_s"):
        assert _read(name, recorded) is None, name
