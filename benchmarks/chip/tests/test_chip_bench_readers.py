"""Metric readers on a hand-filled record, and the traffic generators'
fixed work: every seed gets the same gaps and kinds, in its own order."""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))

from chipbench import core  # noqa: E402


def _read(name, rec):
    return core.load_module("metrics", name).read(rec)


def test_readers_on_a_record():
    rec = core.Record()
    rec.setup_s, rec.window_s, rec.instructions = 12.5, 2.0, 6508 * 10
    rec.latencies_ms = list(range(1, 101))          # 1..100 ms
    rec.gen_late_ms = [0.0] * 19 + [40.0]
    rec.batch_sizes = [1, 2, 3, 6]
    assert _read("setup_s", rec) == 12.5
    assert _read("sim_instr_per_s", rec) == pytest.approx(32540.0)
    assert _read("launch_p95_ms", rec) == pytest.approx(95.05)
    assert _read("request_p95_ms", rec) == pytest.approx(95.05)
    assert _read("serve.gen_late_ms", rec) == pytest.approx(2.0)
    assert _read("serve.batch_size", rec) == 3.0
    assert _read("compiles.batch", rec) == 0
    assert _read("engine.device_rows", rec) is None
    rec.profile = {"trace_merge": {"fusion": {
        "fused_rows": 1238, "folded_rows": 340, "gmem_rows": 0}}}
    assert _read("engine.device_rows", rec) == 898


def test_empty_record_reads_nothing():
    rec = core.Record()
    for name in ("sim_instr_per_s", "launch_p95_ms", "request_p95_ms",
                 "serve.gen_late_ms", "serve.batch_size",
                 "device.busy_ms.batch", "device.idle_share.batch"):
        assert _read(name, rec) is None, name


def test_open_loop_work_is_the_same_for_every_seed():
    gen = core.load_module("traffic", "open_loop")
    a = gen.schedule(200.0, 10.0, [2, 1], np.random.default_rng(2**31 + 1))
    b = gen.schedule(200.0, 10.0, [2, 1], np.random.default_rng(7))
    assert a[0].size == b[0].size == 2000
    gaps_a, gaps_b = np.diff(a[0], prepend=0.0), np.diff(b[0], prepend=0.0)
    assert np.allclose(np.sort(gaps_a), np.sort(gaps_b))
    assert not np.allclose(gaps_a, gaps_b)          # in another order
    assert np.isclose(a[0][-1], b[0][-1])           # same total span
    assert np.isclose(a[0][-1], 10.0, rtol=0.01)
    assert np.bincount(a[1]).tolist() == np.bincount(b[1]).tolist() \
        == [1333, 667]
    assert not np.array_equal(a[1], b[1])


def test_served_warm_up_covers_every_batch_mix():
    entry = core.load_module("entries", "launch_server")
    kinds = entry.batch_kinds(2, 8, 4)
    assert len(kinds) == 132 == len({tuple(k) for k in kinds})
    assert {len(k) for k in kinds} == set(range(1, 9))
    # every (leading kind, kinds of each wave) appears
    keys = {(k[0], tuple(sorted(k[:4])), tuple(sorted(k[4:])))
            for k in kinds}
    assert len(keys) == 132


def test_settle_freezes_and_pauses_are_seen():
    import gc

    try:
        ms = core.settle()
        assert ms >= 0.0 and gc.get_freeze_count() > 0
        with core.GcPauses() as watch:
            gc.collect()
        assert [g for g, _ in watch.pauses] == [2]
        assert watch.pauses[0][1] >= 0.0
        assert watch._on_gc not in gc.callbacks
    finally:
        gc.unfreeze()
