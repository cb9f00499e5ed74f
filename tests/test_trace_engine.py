"""Trace-engine differential suite: the decode-once scan pipeline must be
bit-identical to the stepping machine on every golden program, at every SM
count, on both execute backends — plus the engine plumbing (auto
selection, compile cache), the per-Kernel imem/shmem overrides, and the
priority dispatch discipline that ride along in this layer.
"""
import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    DeviceConfig,
    Kernel,
    SMConfig,
    assemble,
    compile_program,
    launch,
    program_trace,
    schedule_blocks,
)
from repro.core.assembler import auto_nop
from repro.core.isa import Depth, Instr, Op, Typ, Width

RNG = np.random.default_rng(23)


def _dcfg(n_sms=4, gdepth=256, engine="auto", backend="inline", **sm_kw):
    sm_kw.setdefault("max_steps", 5000)
    return DeviceConfig(n_sms=n_sms, global_mem_depth=gdepth,
                        engine=engine, backend=backend, sm=SMConfig(**sm_kw))


def _assert_launches_identical(a, b):
    np.testing.assert_array_equal(np.asarray(a.regs), np.asarray(b.regs))
    np.testing.assert_array_equal(np.asarray(a.shmem), np.asarray(b.shmem))
    np.testing.assert_array_equal(np.asarray(a.gmem), np.asarray(b.gmem))
    np.testing.assert_array_equal(np.asarray(a.oob), np.asarray(b.oob))
    assert a.halted == b.halted
    assert a.cycles == b.cycles and a.steps == b.steps
    assert list(a.wave_cycles) == list(b.wave_cycles)
    assert list(np.asarray(a.cycles_by_class)) \
        == list(np.asarray(b.cycles_by_class))
    assert a.static_cycles == b.static_cycles


# ---------------------------------------------------------------------------
# golden programs: step vs trace across SM counts and backends
# ---------------------------------------------------------------------------

def _golden_launches(n_sms, backend, engine):
    """One launch per golden program on an ``n_sms`` device; returns
    {name: LaunchResult}. Sizes kept small enough for the Pallas
    interpreter to sweep the whole set."""
    from repro.core.programs import launch_fft_qrd, launch_reduction
    from repro.core.programs.fft import run_fft_batch
    from repro.core.programs.qrd import run_qrd_batch
    from repro.core.programs.saxpy import launch_saxpy

    out = {}
    x = np.arange(64, dtype=np.float32)
    dev = DeviceConfig(n_sms=n_sms, global_mem_depth=1024, engine=engine,
                       backend=backend, sm=SMConfig(max_steps=10_000))
    _, out["saxpy"] = launch_saxpy(2.0, x, np.ones_like(x), device=dev,
                                   block=16)
    dev = DeviceConfig(n_sms=n_sms, global_mem_depth=2048, engine=engine,
                       backend=backend, sm=SMConfig(max_steps=50_000))
    _, out["reduction"] = launch_reduction(np.ones(512, np.float32),
                                           device=dev, block=128,
                                           fused=True)
    dev = DeviceConfig(n_sms=n_sms, engine=engine, backend=backend,
                       sm=SMConfig(shmem_depth=192, max_steps=200_000))
    _, out["fft"] = run_fft_batch(np.ones((3, 64), np.complex64),
                                  device=dev)
    dev = DeviceConfig(n_sms=n_sms, engine=engine, backend=backend,
                       sm=SMConfig(shmem_depth=1024, imem_depth=1024,
                                   max_steps=200_000))
    As = np.stack([np.eye(16, dtype=np.float32) + 0.1 * i
                   for i in range(2)])
    _, _, out["qrd"] = run_qrd_batch(As, device=dev)
    from repro.core.programs.mixed import mixed_device

    dev = dataclasses.replace(mixed_device(64, n_sms=n_sms), engine=engine,
                              backend=backend)
    _, _, _, out["mixed"] = launch_fft_qrd(
        np.ones((3, 64), np.complex64),
        np.stack([np.eye(16, dtype=np.float32)] * 2), device=dev)
    return out


@pytest.mark.parametrize("n_sms", [1, 2, 4])
def test_trace_engine_bit_identical_golden_inline(n_sms):
    step = _golden_launches(n_sms, "inline", "step")
    trace = _golden_launches(n_sms, "inline", "trace")
    for name in step:
        assert step[name].engine == "step"
        assert trace[name].engine == "trace"
        _assert_launches_identical(step[name], trace[name])


@pytest.mark.parametrize("n_sms", [1, 2])
def test_trace_engine_bit_identical_golden_pallas(n_sms):
    step = _golden_launches(n_sms, "pallas", "step")
    trace = _golden_launches(n_sms, "pallas", "trace")
    for name in step:
        _assert_launches_identical(step[name], trace[name])


def test_trace_engine_bit_identical_golden_pallas_4sm():
    # keep the 4-SM Pallas sweep to the two kernel-heavy programs so the
    # interpreter sweep stays CI-sized; 1/2-SM cover the full set above
    step = _golden_launches(4, "pallas", "step")
    trace = _golden_launches(4, "pallas", "trace")
    for name in ("fft", "qrd"):
        _assert_launches_identical(step[name], trace[name])


# ---------------------------------------------------------------------------
# fuzz: random legal programs (loops, subroutines, every data op)
# ---------------------------------------------------------------------------

_DATA_OPS = [Op.ADD, Op.SUB, Op.MUL, Op.AND, Op.OR, Op.XOR, Op.LSL,
             Op.LSR, Op.LODI, Op.TDX, Op.TDY, Op.BID, Op.PID, Op.LOD,
             Op.STO, Op.GLD, Op.GST, Op.DOT, Op.SUM, Op.INVSQR, Op.NOP]


def _data_instr(draw):
    op = draw(st.sampled_from(_DATA_OPS))
    return Instr(op=op, typ=draw(st.sampled_from(list(Typ))),
                 rd=draw(st.integers(0, 15)), ra=draw(st.integers(0, 15)),
                 rb=draw(st.integers(0, 15)),
                 imm=draw(st.integers(0, 31)),
                 width=draw(st.sampled_from(list(Width))),
                 depth=draw(st.sampled_from(list(Depth))))


@st.composite
def _random_program(draw):
    """pre | INIT t; body; LOOP | JSR sub | STOP | sub: ...; RTS —
    terminating by construction, exercising the pre-resolved control."""
    pre = [_data_instr(draw) for _ in range(draw(st.integers(0, 4)))]
    body = [_data_instr(draw) for _ in range(draw(st.integers(1, 4)))]
    trip = draw(st.integers(1, 4))
    sub = [_data_instr(draw) for _ in range(draw(st.integers(0, 2)))]
    use_jsr = draw(st.booleans())
    prog = list(pre)
    prog.append(Instr(op=Op.INIT, imm=trip))
    body_start = len(prog)
    prog.extend(body)
    prog.append(Instr(op=Op.LOOP, imm=body_start))
    stop_at = len(prog) + (1 if use_jsr else 0)
    if use_jsr:
        prog.append(Instr(op=Op.JSR, imm=stop_at + 1))
    prog.append(Instr(op=Op.STOP))
    if use_jsr:
        prog.extend(sub)
        prog.append(Instr(op=Op.RTS))
    return np.array([i.encode() for i in prog], np.int64)


@settings(max_examples=40, deadline=None)
@given(words=_random_program(), seed=st.integers(0, 2**31 - 1),
       n_sms=st.integers(1, 3), n_blocks=st.integers(1, 5))
def test_fuzz_trace_engine_matches_step_machine(words, seed, n_sms,
                                                n_blocks):
    rng = np.random.default_rng(seed)
    gmem = rng.standard_normal(64).astype(np.float32)
    shmem = rng.standard_normal((n_blocks, 64)).astype(np.float32)
    outs = {}
    for engine in ("step", "trace"):
        dcfg = _dcfg(n_sms=n_sms, gdepth=64, engine=engine,
                     shmem_depth=64, max_steps=500)
        outs[engine] = launch(dcfg, words, grid=(n_blocks,), block=32,
                              gmem=gmem, shmem=shmem)
    _assert_launches_identical(outs["step"], outs["trace"])


# ---------------------------------------------------------------------------
# engine plumbing: auto selection, cache, runaway programs
# ---------------------------------------------------------------------------

def test_auto_engine_picks_megakernel_for_halting_programs():
    # enough fusible (non-gmem) work to clear MEGAKERNEL_MIN_FUSED_ROWS
    prog = assemble("INIT 12\ntop:\nTDX R1\nADD.INT32 R2, R1, R1\n"
                    "LOOP top\nSTO R2, (R1)+0\nSTOP")
    res = launch(_dcfg(max_steps=100), prog, grid=(2,), block=16)
    assert res.engine == "megakernel" and res.halted
    assert res.engine_fallback is None


def test_auto_engine_never_picks_megakernel_for_short_programs():
    # the BENCH_engine.json regression: on saxpy256_b64 the megakernel
    # measured 0.811x vs step, because a 7-residual-row program is all
    # dispatch glue. auto must fall back to step and say why; an
    # explicit engine choice is still honored.
    from repro.core import trace_engine
    from repro.core.programs.saxpy import saxpy_kernel

    kern = saxpy_kernel(256, block=64)
    words = kern.program.words
    dcfg = _dcfg(n_sms=2, gdepth=1024, max_steps=10_000)
    res = launch(dcfg, words, grid=(4,), block=64,
                 gmem=np.zeros(1024, np.float32))
    assert res.engine == "step"
    assert res.profile()["engine_fallback"] == "megakernel-too-small"
    # an explicit engine choice is never second-guessed — and all three
    # engines stay bit-identical on the shape
    for eng in ("megakernel", "trace"):
        forced = launch(_dcfg(n_sms=2, gdepth=1024, max_steps=10_000,
                              engine=eng), words, grid=(4,), block=64,
                        gmem=np.zeros(1024, np.float32))
        assert forced.engine == eng and forced.engine_fallback is None
        _assert_launches_identical(res, forced)


def test_auto_engine_degrades_to_trace_past_unroll_cap():
    # a schedule longer than the megakernel unroll cap would compile an
    # unboundedly large fused body — auto degrades to the scanned trace
    # engine and says why
    from repro.core import trace_engine

    trip = trace_engine.MEGAKERNEL_UNROLL_CAP // 2 + 1
    prog = assemble(f"INIT {trip}\ntop:\nTDX R1\nADD.INT32 R2, R1, R1\n"
                    f"LOOP top\nSTOP")
    res = launch(_dcfg(max_steps=3 * trip + 8), prog, grid=(1,), block=16)
    assert res.engine == "trace"
    assert res.profile()["engine_fallback"] == "megakernel-unroll-cap"


def test_auto_engine_falls_back_to_step_for_runaway_programs():
    runaway = assemble("top:\nTDX R1\nJMP top")
    res = launch(_dcfg(max_steps=50), runaway, grid=(1,), block=16)
    assert res.engine == "step"
    assert not res.halted and res.steps == 50


def test_forced_trace_engine_matches_step_on_fuel_limited_program():
    # fuel-limited (non-halting) traces still replay exactly
    runaway = assemble("top:\nTDX R1\nADD.INT32 R2, R1, R1\nSTO R2, (R1)+0\nJMP top")
    outs = {e: launch(_dcfg(max_steps=47, engine=e), runaway, grid=(3,),
                      block=16) for e in ("step", "trace")}
    _assert_launches_identical(outs["step"], outs["trace"])
    assert not outs["trace"].halted


def test_compile_cache_is_keyed_and_hit():
    prog = assemble("TDX R1\nSTO R1, (R1)+0\nSTOP")
    cfg = SMConfig(n_threads=16, dim_x=16, shmem_depth=64, max_steps=100)
    s1 = compile_program(prog, cfg)
    s2 = compile_program(prog.words, cfg)
    assert s1 is s2                       # same (program, SMConfig) key
    cfg2 = dataclasses.replace(cfg, n_threads=32, dim_x=32)
    assert compile_program(prog, cfg2) is not s1
    # NOP/control compiled out: only TDX + STO remain
    assert s1.n_steps == 2 and s1.halted


@pytest.fixture
def persistent_cache(tmp_path, monkeypatch):
    """An isolated on-disk compile cache, torn down after the test (the
    cache is opt-in: other tests must never see it)."""
    from repro.core import compile_cache
    from repro.core.cycles import _trace_cached

    cc = compile_cache.configure(str(tmp_path / "cache"))
    _trace_cached.cache_clear()                 # force disk consultation
    yield cc
    compile_cache.configure(None)
    _trace_cached.cache_clear()


def test_persistent_cache_miss_then_hit(persistent_cache):
    from repro.core.cycles import _trace_cached

    prog = assemble("TDX R1\nSTO R1, (R1)+0\nSTOP")
    tr1 = program_trace(prog, 16)
    st = persistent_cache.stats
    assert st.misses >= 1 and st.stores >= 1 and st.hits == 0
    # a fresh process is simulated by clearing the in-memory LRU: the
    # walk must now be SERVED from disk, not recomputed
    _trace_cached.cache_clear()
    tr2 = program_trace(prog, 16)
    assert persistent_cache.stats.hits >= 1
    assert tr2 == tr1                  # served artifact is the same walk
    # a different config is a different key — miss, not a stale hit
    _trace_cached.cache_clear()
    program_trace(prog, 32)
    assert persistent_cache.stats.misses >= 2


def test_persistent_cache_corrupt_entry_is_miss_and_quarantined(
        persistent_cache, tmp_path):
    import os
    import pickle

    from repro.core import compile_cache
    from repro.core.cycles import _trace_cached

    prog = assemble("TDX R1\nSTO R1, (R1)+0\nSTOP")
    program_trace(prog, 16)
    entries = [os.path.join(r, f)
               for r, _, fs in os.walk(persistent_cache.path)
               for f in fs if f.endswith(".pkl")]
    assert len(entries) == 1
    # truncated garbage: load must be a counted error->miss, the entry
    # unlinked, and the launch path never sees an exception
    with open(entries[0], "wb") as fh:
        fh.write(b"\x80\x04 truncated garbage")
    _trace_cached.cache_clear()
    tr = program_trace(prog, 16)
    assert tr.halted and tr.steps == 3
    st = persistent_cache.stats
    assert st.errors >= 1
    assert not os.path.exists(entries[0]) or \
        compile_cache.load(compile_cache.key_for(
            "trace", prog.words, (16, 512, 100_000))) is not None
    # wrong-key (foreign) entries are rejected the same way
    key = compile_cache.key_for("trace", prog.words, (16, 512, 100_000))
    f = persistent_cache._file(key)
    with open(f, "wb") as fh:
        pickle.dump({"magic": "egpu-compile-cache", "format": 1,
                     "key": "someone-else", "value": 42}, fh)
    _trace_cached.cache_clear()
    assert program_trace(prog, 16) == tr
    assert persistent_cache.stats.errors >= 2


def test_persistent_cache_disabled_without_configuration(tmp_path,
                                                         monkeypatch):
    from repro.core import compile_cache

    monkeypatch.delenv("EGPU_CACHE_DIR", raising=False)
    compile_cache.configure(None)
    assert compile_cache.active() is None
    assert compile_cache.load("deadbeef") is None
    compile_cache.store("deadbeef", 1)          # silent no-op
    assert compile_cache.stats() is None


def _run_launch_reporting_cache_dir(env):
    """Run one small launch in a fresh interpreter (JAX reads its cache
    variable once, at import) with ``env`` as the only cache setting, and
    return the cache directory it used."""
    import os
    import subprocess
    import sys

    code = ("import jax\n"
            "from repro.core import DeviceConfig, assemble, launch\n"
            "prog = assemble('TDX R1\\nSTO R1, (R1)+0\\nSTOP')\n"
            "launch(DeviceConfig(), prog, grid=(1,), block=16)\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env = {**{k: v for k, v in os.environ.items()
              if k != "JAX_COMPILATION_CACHE_DIR"},
           "PYTHONPATH": os.path.abspath(src), **env}
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True).stdout
    return out.strip().splitlines()[-1]


def test_jax_cache_honours_env_dir(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, a launch leaves JAX's cache
    there, and the compiled programs are written into it."""
    cache = tmp_path / "jax-cache"
    used = _run_launch_reporting_cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": str(cache),
         "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"})
    assert used == str(cache)
    assert any(cache.iterdir())


def test_jax_cache_defaults_to_fixed_checkout_dir():
    """Without the variable the launch path puts the cache in one fixed
    directory of the checkout, the same in every process."""
    from pathlib import Path

    from repro.core import compile_cache

    used = _run_launch_reporting_cache_dir({})
    root = Path(__file__).resolve().parents[1]
    assert used == compile_cache.DEFAULT_JAX_CACHE == str(root / ".jax_cache")


def test_bogus_engine_rejected():
    with pytest.raises(ValueError, match="engine"):
        DeviceConfig(engine="warp")
    prog = assemble("STOP")
    with pytest.raises(ValueError, match="engine"):
        launch(_dcfg(), prog, grid=(1,), block=16, engine="warp")


# ---------------------------------------------------------------------------
# per-Kernel imem/shmem overrides
# ---------------------------------------------------------------------------

def test_kernel_override_exceeding_device_ceiling_rejected():
    prog = assemble("STOP").words
    for field in ("imem_depth", "shmem_depth"):
        kern = Kernel(prog, block=16, **{field: 1 << 20})
        with pytest.raises(ValueError, match="exceeds the device ceiling"):
            launch(_dcfg(), programs=[kern], grid_map=[0])
        with pytest.raises(ValueError, match="must be >= 1"):
            launch(_dcfg(), programs=[Kernel(prog, block=16, **{field: 0})],
                   grid_map=[0])


def test_kernel_imem_override_bounds_program_length():
    long_prog = assemble("\n".join(["NOP"] * 40 + ["STOP"])).words
    with pytest.raises(ValueError, match="exceeds I-MEM depth"):
        launch(_dcfg(), programs=[Kernel(long_prog, block=16,
                                         imem_depth=32)], grid_map=[0])
    # fits the override: runs normally
    res = launch(_dcfg(), programs=[Kernel(long_prog, block=16,
                                           imem_depth=64)], grid_map=[0])
    assert res.halted


@pytest.mark.parametrize("engine", ["step", "trace"])
def test_kernel_overrides_per_program_in_mixed_grid(engine):
    # BOTH programs of one heterogeneous launch carry their own override:
    # each block is bounds-checked at ITS program's depth even when the
    # merged trace path stacks them into one device-depth wave batch
    prog = assemble("TDX R1\nSTO R1, (R1)+0\nSTOP").words
    kerns = [Kernel(prog, block=64, name="a", shmem_depth=16),
             Kernel(prog, block=64, name="b", shmem_depth=48,
                    imem_depth=32)]
    res = launch(_dcfg(n_sms=2, engine=engine, shmem_depth=64),
                 programs=kerns, grid_map=[0, 1, 1, 0])
    if engine == "trace":
        assert res.trace_merge is not None     # the merged path ran
    oob = np.asarray(res.oob)
    assert oob.all()                 # 64 threads overflow both overrides
    sh = np.asarray(res.shmem)
    assert sh.shape[1] == 64         # padded back to the device depth
    for b, depth in zip(range(4), (16, 48, 48, 16)):
        np.testing.assert_array_equal(sh[b, :depth], np.arange(depth))
        np.testing.assert_array_equal(sh[b, depth:], 0)


def test_kernel_override_ceiling_rejected_in_mixed_grid():
    # the ceiling check runs per program of a heterogeneous launch too
    prog = assemble("STOP").words
    kerns = [Kernel(prog, block=16),
             Kernel(prog, block=16, shmem_depth=1 << 20)]
    with pytest.raises(ValueError, match="program 1 exceeds the device "
                                         "ceiling"):
        launch(_dcfg(), programs=kerns, grid_map=[0, 1])


@pytest.mark.parametrize("engine", ["step", "trace"])
def test_kernel_shmem_override_tightens_oob_and_pads_result(engine):
    # thread t stores to address t: legal at the device depth (64), but
    # threads >= 32 are out of range under a shmem_depth=32 override
    prog = assemble("TDX R1\nSTO R1, (R1)+0\nSTOP").words
    kerns = [Kernel(prog, block=64, name="small", shmem_depth=32),
             Kernel(prog, block=64, name="full")]
    res = launch(_dcfg(engine=engine, shmem_depth=64),
                 programs=kerns, grid_map=[0, 1])
    assert bool(np.asarray(res.oob)[0]) and not bool(np.asarray(res.oob)[1])
    sh = np.asarray(res.shmem)
    assert sh.shape[1] == 64              # padded back to the device depth
    np.testing.assert_array_equal(sh[0, :32], np.arange(32))
    np.testing.assert_array_equal(sh[0, 32:], 0)   # dropped + padding
    np.testing.assert_array_equal(sh[1], np.arange(64))


# ---------------------------------------------------------------------------
# priority dispatch
# ---------------------------------------------------------------------------

def _prio_traces():
    long_p = assemble("INIT 60\ntop:\nSTO R1, (R0)+0\nLOOP top\nSTOP").words
    short_p = assemble("STO R1, (R0)+0\nSTOP").words
    return (program_trace(long_p, 256), program_trace(short_p, 64))


def test_priority_zero_is_bit_identical_to_fifo():
    long_t, short_t = _prio_traces()
    traces = [short_t] * 5 + [long_t] + [short_t] * 3
    base = schedule_blocks(traces, 2, "dynamic")
    prio = schedule_blocks(traces, 2, "dynamic",
                           priority_of=[0] * len(traces))
    for f in ("block_sm", "block_start", "block_finish", "block_wait"):
        np.testing.assert_array_equal(getattr(base, f), getattr(prio, f))
    assert base.makespan == prio.makespan


def test_priority_pulls_high_priority_blocks_first():
    long_t, short_t = _prio_traces()
    # back-loaded queue: the long block sits LAST in grid order
    traces = [short_t] * 6 + [long_t]
    prio = [0] * 6 + [5]
    fifo = schedule_blocks(traces, 2, "dynamic")
    sched = schedule_blocks(traces, 2, "dynamic", priority_of=prio)
    assert int(sched.block_start[6]) == 0     # pulled immediately
    assert sched.makespan < fifo.makespan
    # every block still runs exactly once
    assert int(sched.sm_blocks.sum()) == len(traces)


@pytest.mark.parametrize("engine", ["step", "trace"])
def test_priority_is_timing_only(engine):
    # functional state must be invariant to the priority discipline
    prog = assemble(auto_nop("""
        PID R1
        BID R2
        LOD R3, #16
        MUL.INT32 R4, R1, R3
        ADD.INT32 R5, R4, R2
        GST R5, (R5)+0 {w1,d1}
        STOP
    """, 16)).words
    gmap = [0, 0, 1, 0, 1]
    outs = {}
    for pri in (0, 7):
        kerns = [Kernel(prog, block=16, name="a"),
                 Kernel(prog, block=16, name="b", priority=pri)]
        outs[pri] = launch(_dcfg(n_sms=2, engine=engine), programs=kerns,
                           grid_map=gmap, schedule="dynamic")
    np.testing.assert_array_equal(np.asarray(outs[0].gmem),
                                  np.asarray(outs[7].gmem))
    np.testing.assert_array_equal(np.asarray(outs[0].regs),
                                  np.asarray(outs[7].regs))


def test_prioritized_mixed_launch_beats_backloaded_fifo():
    from repro.core.programs import launch_fft_qrd

    xs = np.ones((6, 64), np.complex64)
    As = np.stack([np.eye(16, dtype=np.float32)] * 3)
    _, _, _, fifo = launch_fft_qrd(xs, As, schedule="dynamic",
                                   interleave=False)
    _, _, _, prio = launch_fft_qrd(xs, As, schedule="dynamic",
                                   interleave=False, priorities=(0, 1))
    assert prio.cycles < fifo.cycles
    np.testing.assert_array_equal(np.asarray(fifo.shmem),
                                  np.asarray(prio.shmem))
