"""In-program spans and counters (``repro.core.tracing``): nesting and ids,
the ring's bound, self-time totals, JAX's compile-path durations, the span
tree of one mixed launch on the paper's 4-SM sector, and the same spans in
a ``jax.profiler`` capture."""
from __future__ import annotations

import collections
import glob
import os
import statistics
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DeviceConfig, SMConfig, tracing
from repro.core.programs.mixed import launch_fft_qrd

# the benchmark's sector4 deployment: 4 SMs x 512 threads, 3072-word
# shared memory, 1024-word I-MEM
SECTOR4 = DeviceConfig(n_sms=4, global_mem_depth=8192, engine="auto",
                       backend="inline", schedule="auto",
                       sm=SMConfig(n_threads=512, dim_x=16,
                                   shmem_depth=3072, imem_depth=1024,
                                   max_steps=200_000))
# the phases the benchmark's launch.* metrics name
PHASES = ("egpu.inputs", "egpu.launch.plan", "egpu.launch.schedule",
          "egpu.launch.stage", "egpu.launch.dispatch", "egpu.launch.unpack",
          "egpu.readback")


@pytest.fixture
def clean():
    tracing.reset()
    yield
    tracing.reset()


def _dur(sp) -> int:
    return sp.end_ns - sp.start_ns


def test_spans_nest_with_parent_and_root_ids(clean):
    with tracing.span("a") as a:
        with tracing.span("b") as b:
            with tracing.span("c"):
                pass
        with tracing.span("d"):
            pass
    with tracing.span("e") as e:
        pass
    got = {sp.name: sp for sp in tracing.recent()}
    assert [sp.name for sp in tracing.recent()] == ["c", "b", "d", "a", "e"]
    assert got["a"].parent is None and got["a"].root == a.id == got["a"].id
    assert got["b"].parent == a.id and got["d"].parent == a.id
    assert got["c"].parent == b.id
    assert {got[n].root for n in "abcd"} == {a.id}
    assert got["e"].parent is None and got["e"].root == e.id != a.id
    assert got["a"].start_ns <= got["b"].start_ns <= got["c"].start_ns
    assert got["c"].end_ns <= got["b"].end_ns <= got["d"].start_ns
    assert got["d"].end_ns <= got["a"].end_ns


def test_a_span_on_another_thread_is_its_own_root(clean):
    seen = {}

    def work():
        with tracing.span("worker") as w:
            with tracing.span("worker.inner"):
                pass
        seen["id"] = w.id

    with tracing.span("main") as m:
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=30)
    assert not t.is_alive()
    got = {sp.name: sp for sp in tracing.recent()}
    assert got["worker"].parent is None
    assert got["worker"].root == seen["id"] != m.id
    assert got["worker.inner"].root == seen["id"]
    assert got["worker.inner"].parent == seen["id"]
    assert got["main"].root == m.id


def test_the_ring_keeps_the_last_spans_only(clean):
    for _ in range(tracing.RING + 10):
        with tracing.span("s"):
            pass
    spans = tracing.recent()
    assert len(spans) == tracing.RING
    assert spans[-1].id - spans[0].id == tracing.RING - 1
    assert tracing.totals()["s"][0] == tracing.RING + 10


def test_totals_count_self_time(clean):
    for _ in range(3):
        with tracing.span("outer"):
            with tracing.span("inner"):
                sum(range(20000))
    spans = tracing.recent()
    tot = tracing.totals()
    assert tot["outer"][0] == 3 and tot["inner"][0] == 3
    inner_s = sum(_dur(sp) for sp in spans if sp.name == "inner") / 1e9
    outer_s = sum(_dur(sp) for sp in spans if sp.name == "outer") / 1e9
    assert tot["inner"][1] == pytest.approx(inner_s)
    assert tot["outer"][1] == pytest.approx(outer_s - inner_s)
    tracing.reset()
    assert tracing.totals() == {} and tracing.recent() == []


def test_jax_compile_path_is_counted_once(clean):
    # a function no other test compiles, so its trace, lowering and
    # compile happen here
    salt = float(np.random.default_rng().integers(1, 2**30))

    @jax.jit
    def fresh(x):
        return jnp.sin(x) * salt + jnp.cos(x)

    with tracing.span("call") as call:
        fresh(jnp.arange(8.0)).block_until_ready()
    tot = tracing.totals()
    for name in ("jax.trace", "jax.lower", "jax.compile"):
        assert tot[name][0] >= 1 and tot[name][1] > 0, name
    (sp,) = [s for s in tracing.recent() if s.id == call.id]
    jax_s = sum(s for name, (_n, s) in tot.items()
                if name.startswith("jax."))
    # nested events (a cache load inside a compile) count once, and the
    # span's self time leaves them out: together they are the span
    assert jax_s <= _dur(sp) / 1e9
    assert jax_s + tot["call"][1] == pytest.approx(_dur(sp) / 1e9)


def _inputs(rng):
    xs = rng.standard_normal((8, 256)) + 1j * rng.standard_normal((8, 256))
    return xs, rng.standard_normal((4, 16, 16))


def _tree(spans, root_id):
    """The call's spans as (name, parent name, start) in start order."""
    mine = [sp for sp in spans if sp.root == root_id]
    name_of = {sp.id: sp.name for sp in mine}
    return [(sp.name, name_of.get(sp.parent), sp.start_ns)
            for sp in sorted(mine, key=lambda sp: sp.start_ns)]


@pytest.fixture(scope="module")
def warm():
    """One mixed launch of 8 FFT-256 and 4 QRD-16 blocks, compiled."""
    rng = np.random.default_rng(2**31 + 13)
    launch_fft_qrd(*_inputs(rng), device=SECTOR4)
    return rng


def test_one_launch_records_the_layer_tree(warm):
    tracing.reset()
    covers = []
    for _ in range(3):
        *_, res = launch_fft_qrd(*_inputs(warm), device=SECTOR4)
        spans = tracing.recent()
        (root,) = [sp for sp in spans if sp.parent is None]
        tracing.reset()
        waves = len(res.trace_merge["per_wave"])
        assert waves == 3
        tree = [(n, p) for n, p, _ in _tree(spans, root.id)]
        assert tree == [
            ("egpu.launch_fft_qrd", None),
            ("egpu.inputs", "egpu.launch_fft_qrd"),
            ("egpu.launch", "egpu.launch_fft_qrd"),
            ("egpu.launch.plan", "egpu.launch"),
            ("egpu.launch.schedule", "egpu.launch.plan"),
            ("egpu.launch.stage", "egpu.launch"),
            *[(n, "egpu.launch") for _ in range(waves)
              for n in ("egpu.launch.stage", "egpu.launch.dispatch",
                        "egpu.launch.unpack")],
            ("egpu.launch.unpack", "egpu.launch"),
            ("egpu.readback", "egpu.launch_fft_qrd"),
        ]
        assert len(spans) == 8 + 3 * waves
        child_ns = collections.Counter()
        for sp in spans:
            if sp.parent is not None:
                child_ns[sp.parent] += _dur(sp)
        named = sum(_dur(sp) - child_ns[sp.id] for sp in spans
                    if sp.name in PHASES)
        covers.append(named / _dur(root))
    assert statistics.median(covers) >= 0.95, covers


def test_a_profiler_capture_holds_the_spans(warm, tmp_path):
    from jax.profiler import ProfileData

    tracing.reset()
    with jax.profiler.trace(str(tmp_path)):
        launch_fft_qrd(*_inputs(warm), device=SECTOR4)
    spans = tracing.recent()
    (root,) = [sp for sp in spans if sp.parent is None]
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    # start order, a parent before a child that starts with it
    events = sorted(
        (int(ev.start_ns), -int(ev.duration_ns), ev.name)
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
        if ev.name.startswith("egpu."))
    nested = []
    for i, (s, neg_d, name) in enumerate(events):
        # the innermost earlier event that covers this one is its parent
        up = [n for s2, neg_d2, n in events[:i] if s2 - neg_d2 >= s - neg_d]
        nested.append((name, up[-1] if up else None))
    assert nested == [(n, p) for n, p, _ in _tree(spans, root.id)]
