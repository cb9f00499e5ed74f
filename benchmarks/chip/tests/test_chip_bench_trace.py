"""The trace reductions (busy union, idle share, top ops, gap labels) and
the metric readers that use them, against numbers worked out by hand."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import core, profile_trace  # noqa: E402
from chipbench.profile_trace import Trace  # noqa: E402

# window [0, 100) ns; ops a [10,30) b [20,40) a [60,70) c [95,120);
# launches [4,50) and [55,80)
HAND = Trace(window=(0, 100),
             device_ops=[[("a", 10, 30), ("b", 20, 40), ("a", 60, 70),
                          ("c", 95, 120)]],
             host_spans=[("bench.launch", 4, 50),
                         ("bench.launch", 55, 80)])


def test_merge_clip_gaps():
    ops = [(s, e) for _, s, e in HAND.device_ops[0]]
    merged = profile_trace.merge(profile_trace.clip(ops, 0, 100))
    assert merged == [(10, 40), (60, 70), (95, 100)]
    assert profile_trace.gaps(merged, 0, 100) == [(0, 10), (40, 60),
                                                  (70, 95)]
    assert profile_trace.gaps([], 0, 5) == [(0, 5)]
    assert profile_trace.gaps([(0, 5)], 0, 5) == []


def test_labels():
    spans = profile_trace.Spans(HAND.host_spans)
    assert spans.label((0, 10)) == "bench.launch"     # 6 ns in, 4 out
    assert spans.label((40, 60)) == "bench.launch"    # 10 + 5 in, 5 out
    assert spans.label((70, 95)) == "host.other"      # 10 in, 15 out
    assert profile_trace.Spans([]).label((0, 1)) == "host.other"


def test_busy_idle_top_ops():
    assert HAND.busy_s() == pytest.approx(45e-9)       # 30 + 10 + 5
    assert HAND.window_s() == pytest.approx(100e-9)
    b = HAND.breakdown()
    assert b["device_ops"] == [["a", 30e-9], ["b", 20e-9], ["c", 5e-9]]
    assert b["idle_gaps"] == [["bench.launch", 30e-9],
                              ["host.other", 25e-9]]


def test_busy_averages_over_devices():
    two = Trace(window=(0, 100), device_ops=[[("a", 0, 40)], [("a", 0, 20)]],
                host_spans=[])
    assert two.busy_s() == pytest.approx(30e-9)


def test_metric_readers_on_the_hand_trace():
    rec = core.Record()
    rec.trace = HAND
    rec.latencies_ms = [1.0, 2.0, 3.0]
    idle = core.load_module("metrics", "device.idle_share.batch").read(rec)
    assert idle == pytest.approx(55.0)
    busy = core.load_module("metrics", "device.busy_ms.batch").read(rec)
    assert busy == pytest.approx(45e-9 * 1e3 / 3)
    rec.trace = None
    assert core.load_module("metrics",
                            "device.idle_share.serve").read(rec) is None


def test_recorded_excerpt():
    """16 ms of a chip trace (tests/data): 13 ops, none overlapping, so
    the busy union is the sum of their durations, worked out by hand."""
    import json

    data = json.loads((BENCH / "tests" / "data" /
                       "trace_excerpt.json").read_text())
    tr = Trace.from_json(data)
    # copy 599+827+604+607, concatenate 2032+368, bitcast-convert
    # 1128+847+718, broadcast 277, copy-start 5, copy-done 3, fusion 231
    assert tr.busy_s() == pytest.approx(8246e-9, abs=1e-15)
    assert tr.window_s() == pytest.approx(0.016)
    assert tr.top_ops() == [["bitcast-convert", 2693e-9], ["copy", 2637e-9],
                            ["concatenate", 2400e-9], ["broadcast", 277e-9],
                            ["fusion", 231e-9], ["copy-start", 5e-9],
                            ["copy-done", 3e-9]]
    # every gap lies mostly inside one of the two launches
    assert tr.idle_by_label() == [["bench.launch", (16e6 - 8246) / 1e9]]
    spans = profile_trace.Spans(tr.host_spans)
    assert spans.label((133552744, 133556764)) == "host.other"
    # the longest gap, 131075270..136512637, spans the launch boundary:
    # 2477474 ns in the first launch and 2955873 in the second
    assert spans.label((131075270, 136512637)) == "bench.launch"
    rec = core.Record()
    rec.trace = tr
    idle = core.load_module("metrics", "device.idle_share.batch").read(rec)
    assert idle == pytest.approx(100 * (1 - 8246 / 16e6))
