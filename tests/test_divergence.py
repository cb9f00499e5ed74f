"""SIMT divergence suite: predicated execution, proven three ways.

1. ISA surface: SETP/SELP encode/decode round-trips, the predication
   extension byte (bits 40-45) stays zero on legacy words, control ops
   reject guards at both the ``Instr`` and assembler layers.
2. Semantics vs a numpy oracle: every SETP condition x type, SELP's
   guard-as-selector rule, and the core masking contract — a
   predicated-off lane never mutates registers, shared memory, global
   memory, or the OOB flag (masked global lanes generate no port
   traffic, so even an out-of-range address on a masked lane is
   invisible).
3. Differential fuzz: random predicated programs (all-off / all-on /
   alternating / data-dependent masks) run through step, trace and
   megakernel engines and compared bit-identically against the
   inline-step oracle; plus the property fuzz that an all-off guard is
   architecturally a NOP and an all-on guard is bit-identical (cycles
   included — predication never changes timing) to the unguarded
   program.

Run standalone with ``pytest -m divergence``.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import DeviceConfig, SMConfig, launch
from repro.core.assembler import AsmError, assemble, auto_nop, disassemble
from repro.core.isa import Cond, Depth, Instr, Op, Typ, Width

from engine_conformance import assert_arch_identical, assert_bit_identical

pytestmark = pytest.mark.divergence


# ---------------------------------------------------------------------------
# ISA surface
# ---------------------------------------------------------------------------

def test_predicated_encode_decode_roundtrip():
    for op in (Op.ADD, Op.LOD, Op.STO, Op.GLD, Op.GST, Op.SELP, Op.SETP,
               Op.DOT, Op.INVSQR, Op.LODI, Op.TDX):
        ins = Instr(op=op, typ=Typ.INT32, rd=3, ra=1, rb=2,
                    imm=int(Cond.LT) if op == Op.SETP else 5,
                    pen=1, preg=9, pneg=1)
        back = Instr.decode(ins.encode())
        assert (back.pen, back.preg, back.pneg) == (1, 9, 1), op
        assert back.op == op


def test_legacy_words_carry_no_predication():
    # every pre-predication program encodes below bit 40; decode must see
    # pen=0 (predication is opt-in per instruction)
    from repro.core.programs.qrd import qrd_program

    for w in qrd_program().words:
        assert int(w) < (1 << 40)
        ins = Instr.decode(int(w))
        assert ins.pen == 0 and ins.preg == 0 and ins.pneg == 0


def test_control_ops_reject_predication():
    for op in (Op.JMP, Op.JSR, Op.LOOP, Op.INIT):
        with pytest.raises(ValueError):
            Instr(op=op, imm=1, pen=1, preg=2).encode()
    for op in (Op.RTS, Op.STOP, Op.NOP):
        with pytest.raises(ValueError):
            Instr(op=op, pen=1, preg=2).encode()
    with pytest.raises(AsmError):
        assemble("@R3 STOP")
    with pytest.raises(AsmError):
        assemble("top:\n@!R2 JMP top")


def test_predicated_disassembly_roundtrip():
    src = ("TDX R1\n"
           "SETP.LT.INT32 R3, R1, R2\n"
           "@R3 ADD.INT32 R4, R1, R1\n"
           "@!R3 SELP R5, R1, R2\n"
           "@R3 GST R4, (R1)+8")
    prog = assemble(src)
    texts = [disassemble(int(w)) for w in prog.words]
    assert texts[1] == "SETP.LT.INT32 R3, R1, R2"
    assert texts[2].startswith("@R3 ")
    assert texts[3].startswith("@!R3 SELP")
    # disassembled text re-assembles to the same words
    again = assemble("\n".join(texts))
    np.testing.assert_array_equal(prog.words, again.words)


# ---------------------------------------------------------------------------
# semantics vs numpy
# ---------------------------------------------------------------------------

def _run_block(src: str, *, block=16, gmem=None, depth=64, n_sms=1,
               grid=1, engine=None, backend=None):
    dev = DeviceConfig(n_sms=n_sms, global_mem_depth=depth,
                       sm=SMConfig(shmem_depth=64, max_steps=5_000),
                       engine=engine or "auto", backend=backend or "inline")
    return launch(dev, assemble(auto_nop(src, block)), grid=grid,
                  block=block, gmem=gmem)


_CONDS = {
    Cond.EQ: lambda a, b: a == b, Cond.NE: lambda a, b: a != b,
    Cond.LT: lambda a, b: a < b, Cond.LE: lambda a, b: a <= b,
    Cond.GT: lambda a, b: a > b, Cond.GE: lambda a, b: a >= b,
}


@pytest.mark.parametrize("cond", list(Cond))
@pytest.mark.parametrize("typ", [Typ.INT32, Typ.UINT32, Typ.FP32])
def test_setp_conditions_match_numpy(cond, typ):
    rng = np.random.default_rng(int(cond) * 8 + int(typ))
    if typ == Typ.FP32:
        vals = rng.standard_normal(16).astype(np.float32)
        a = np.float32(0.1)
        gmem = np.concatenate([vals, np.full(16, a, np.float32)])
        av, bv = np.full(16, a), vals
    else:
        bits = rng.integers(0, 1 << 32, 16, dtype=np.uint64).astype(np.uint32)
        bits[0] = 0x80000001          # sign-significant either way
        a = np.uint32(0x80000001)
        gmem = np.concatenate([bits, np.full(16, a, np.uint32)])
        if typ == Typ.INT32:
            av, bv = np.full(16, a).astype(np.int32), bits.view(np.int32)
        else:
            av, bv = np.full(16, a), bits
    src = (f"    TDX R1\n"
           f"    GLD R2, (R1)+16\n"
           f"    GLD R3, (R1)+0\n"
           f"    SETP.{cond.name}.{typ.name} R4, R2, R3\n"
           f"    STOP")
    res = _run_block(src, gmem=gmem)
    got = np.asarray(res.regs)[0, :16, 4]
    np.testing.assert_array_equal(got, _CONDS[cond](av, bv).astype(np.uint32))


def test_selp_guard_is_selector_not_write_mask():
    # SELP writes on EVERY active lane; the @-guard picks the arm. With
    # no guard (pen=0) it selects Ra.
    src = ("    TDX R1\n"
           "    LOD R2, #100\n"
           "    LOD R7, #1\n"
           "    AND R3, R1, R7\n"            # P = tid odd
           "    @R3 SELP R4, R2, R1\n"       # odd -> 100, even -> tid
           "    @!R3 SELP R5, R2, R1\n"      # odd -> tid, even -> 100
           "    SELP R6, R2, R1\n"           # pen=0 -> Ra everywhere
           "    STOP")
    regs = np.asarray(_run_block(src).regs)[0, :16]
    tid = np.arange(16, dtype=np.uint32)
    np.testing.assert_array_equal(regs[:, 4], np.where(tid % 2, 100, tid))
    np.testing.assert_array_equal(regs[:, 5], np.where(tid % 2, tid, 100))
    np.testing.assert_array_equal(regs[:, 6], np.full(16, 100, np.uint32))


def test_masked_lanes_mutate_nothing():
    # every masked structure at once: guarded ALU / LOD / STO / GLD / GST
    # on an alternating mask. Off lanes must keep registers, shared and
    # global words bit-exact.
    sentinel = np.arange(100, 164, dtype=np.uint32)
    src = ("    TDX R1\n"
           "    LOD R7, #1\n"
           "    AND R3, R1, R7\n"            # P = tid odd
           "    LOD R4, #7\n"                # R4 = 7 on all lanes first
           "    @R3 ADD.INT32 R4, R1, R1\n"  # odd lanes overwrite with 2*tid
           "    @R3 LOD R5, (R1)+0\n"        # shared load (shmem zeros)
           "    @R3 GLD R6, (R1)+16\n"       # global load of sentinel
           "    @R3 STO R4, (R1)+32\n"
           "    @R3 GST R4, (R1)+32\n"
           "    STOP")
    res = _run_block(src, gmem=sentinel)
    tid = np.arange(16, dtype=np.uint32)
    odd = (tid % 2).astype(bool)
    regs = np.asarray(res.regs)[0, :16]
    np.testing.assert_array_equal(regs[:, 4], np.where(odd, 2 * tid, 7))
    np.testing.assert_array_equal(regs[:, 6],
                                  np.where(odd, sentinel[16:32], 0))
    shmem = np.asarray(res.shmem)[0, 32:48]
    np.testing.assert_array_equal(shmem, np.where(odd, 2 * tid, 0))
    gmem = np.asarray(res.gmem)
    np.testing.assert_array_equal(gmem[32:48],
                                  np.where(odd, 2 * tid, sentinel[32:48]))
    # untouched global words keep their sentinel bits
    np.testing.assert_array_equal(gmem[48:], sentinel[48:])


def test_masked_global_lanes_generate_no_port_traffic():
    # off lanes with OUT-OF-RANGE global addresses: no write, no OOB —
    # a masked lane never reaches the port
    src = ("    TDX R1\n"
           "    LOD R7, #1\n"
           "    AND R3, R1, R7\n"
           "    LOD R2, #4000\n"             # far out of range (depth 64)
           "    @!R3 SELP R4, R2, R1\n"      # odd lanes: tid (valid addr)
           "    @R3 GST R1, (R4)+0\n"        # odd lanes store tid -> gmem[tid]
           "    STOP")
    res = _run_block(src)
    assert not bool(np.asarray(res.oob).any())
    tid = np.arange(16, dtype=np.uint32)
    np.testing.assert_array_equal(np.asarray(res.gmem)[:16],
                                  np.where(tid % 2, tid, 0))
    # flip the guard: now unmasked lanes DO address out of range -> OOB
    bad = src.replace("@R3 GST", "@!R3 GST")
    assert bool(np.asarray(_run_block(bad).oob).any())


def test_predicated_reduction_empty_wavefront_keeps_partial():
    # a wavefront whose lanes are all masked off leaves its lane-0
    # partial untouched (the masked_reduction kernel leans on this)
    src = ("    TDX R1\n"
           "    LOD R2, #5\n"
           "    LOD R3, #0\n"
           "    SETP.LT.INT32 R4, R1, R3\n"  # all-off mask (tid < 0)
           "    @R4 SUM.FP32 R5, R2, R0\n"
           "    STOP")
    regs = np.asarray(_run_block(src, block=32).regs)
    assert (regs[0, :32, 5] == 0).all()


def test_timing_is_mask_independent():
    # all-off, all-on and alternating guards on the same program must
    # report IDENTICAL cycle totals: predicated-off lanes still occupy
    # their issue/drain slots (cycles.py's predication rule)
    def prog(k):
        return ("    TDX R1\n"
                f"    LOD R7, #{k}\n"
                "    SETP.LT.INT32 R3, R1, R7\n"  # P = tid < k
                "    @R3 ADD.INT32 R4, R1, R1\n"
                "    @R3 STO R4, (R1)+0\n"
                "    @R3 GST R4, (R1)+16\n"
                "    @!R3 GST R1, (R1)+32\n"
                "    STOP")
    # k=0: all off; k=16: all on; k=8: divergent half-wavefront
    runs = [_run_block(prog(k), n_sms=2, grid=2) for k in (0, 16, 8)]
    assert len({r.cycles for r in runs}) == 1
    assert len({r.steps for r in runs}) == 1
    for r in runs[1:]:
        assert list(np.asarray(r.cycles_by_class)) \
            == list(np.asarray(runs[0].cycles_by_class))


def test_predicated_programs_launch_through_fleet():
    # the new program library must ride the fleet front door unchanged:
    # same blocks, two devices, bit-identical architectural state
    from repro.core.fleet import FleetConfig, launch_fleet
    from repro.core.programs.masked_reduction import launch_masked_reduction

    x = np.linspace(-2.0, 2.0, 96, dtype=np.float32)
    dev = DeviceConfig(n_sms=2, global_mem_depth=512,
                       sm=SMConfig(max_steps=50_000))
    s_dev, c_dev, res_dev = launch_masked_reduction(x, 0.5, clip=(-1.5, 1.5),
                                                    device=dev, block=32)
    fcfg = FleetConfig(n_devices=2, device=DeviceConfig(
        n_sms=1, global_mem_depth=512, sm=SMConfig(max_steps=50_000)))
    from repro.core.programs import masked_reduction as mr

    # rebuild the same two-stage grid against the fleet front door
    x_pad = np.zeros(96, np.float32)
    x_pad[:96] = x
    buffers = {"x": x_pad,
               "params": np.array([0.5, -1.5, 1.5], np.float32),
               "meta": np.array([96], np.int32),
               "partials": np.zeros(32, np.float32),
               "result": np.zeros(16, np.float32)}
    from repro.core import Kernel
    from repro.core.device import buffer_layout
    from repro.core.programs.reduction import reduction_grid_asm

    layout = buffer_layout(buffers)
    src, prm, meta, par, res_off = (
        layout[k][0] for k in ("x", "params", "meta", "partials", "result"))
    stage1 = mr.masked_reduction_program(32, src, par, prm, meta, 16)
    stage2 = assemble(reduction_grid_asm(16, par, res_off, True))
    res_fleet = launch_fleet(
        fcfg, programs=[Kernel(stage1, block=32, name="masked.stage1"),
                        Kernel(stage2, block=16, name="masked.stage2",
                               barrier=True)],
        grid_map=[0, 0, 0] + [1, 1], buffers=buffers)
    out = np.asarray(res_fleet.buffer("result"))
    assert float(out[0]) == pytest.approx(s_dev)
    assert int(round(float(out[1]))) == c_dev
    assert res_fleet.fleet["n_devices"] == 2


# ---------------------------------------------------------------------------
# differential fuzz vs the inline-step oracle
# ---------------------------------------------------------------------------

# predicable data ops (no GST: fuzz grids run 2 concurrent blocks that
# would race; the deterministic tests above cover predicated GST)
_PRED_OPS = [Op.ADD, Op.SUB, Op.MUL, Op.AND, Op.OR, Op.XOR, Op.LSL,
             Op.LSR, Op.LODI, Op.TDX, Op.TDY, Op.BID, Op.LOD, Op.STO,
             Op.GLD, Op.DOT, Op.SUM, Op.INVSQR, Op.SELP, Op.SETP]

# R14 carries the fuzz mask, R15 stays all-zero (never a destination)
_MASK_PROLOGUES = {
    "all_off": [Instr(op=Op.LODI, rd=14, imm=0)],
    "all_on": [Instr(op=Op.LODI, rd=14, imm=1)],
    "alternating": [Instr(op=Op.TDX, rd=14)],       # LSB of tid
    "data": [Instr(op=Op.TDX, rd=14),
             Instr(op=Op.LOD, rd=14, ra=14, imm=0)],  # LSB of shmem[tid]
}


def _pred_instr(draw, pen):
    op = draw(st.sampled_from(_PRED_OPS))
    imm = draw(st.integers(0, 5)) if op == Op.SETP \
        else draw(st.integers(0, 31))
    return Instr(op=op, typ=draw(st.sampled_from(list(Typ))),
                 rd=draw(st.integers(0, 13)), ra=draw(st.integers(0, 14)),
                 rb=draw(st.integers(0, 14)), imm=imm,
                 width=draw(st.sampled_from(list(Width))),
                 depth=draw(st.sampled_from(list(Depth))),
                 pen=pen,
                 preg=draw(st.integers(0, 14)) if pen else 0,
                 pneg=draw(st.integers(0, 1)) if pen else 0)


@st.composite
def _random_predicated_program(draw):
    """mask prologue | pre | INIT t; body; LOOP | STOP."""
    mask = draw(st.sampled_from(sorted(_MASK_PROLOGUES)))
    prog = list(_MASK_PROLOGUES[mask])
    prog += [_pred_instr(draw, draw(st.integers(0, 1)))
             for _ in range(draw(st.integers(0, 3)))]
    body = [_pred_instr(draw, draw(st.integers(0, 1)))
            for _ in range(draw(st.integers(1, 4)))]
    prog.append(Instr(op=Op.INIT, imm=draw(st.integers(1, 4))))
    body_start = len(prog)
    prog.extend(body)
    prog.append(Instr(op=Op.LOOP, imm=body_start))
    prog.append(Instr(op=Op.STOP))
    return np.array([i.encode() for i in prog], np.int64)


@settings(max_examples=30, deadline=None)
@given(prog=_random_predicated_program(), seed=st.integers(0, 2**31 - 1),
       n_sms=st.integers(1, 2),
       schedule=st.sampled_from(["static", "dynamic"]),
       block=st.sampled_from([16, 32]))
def test_fuzz_predicated_programs_conform(prog, seed, n_sms, schedule,
                                          block):
    rng = np.random.default_rng(seed)
    gmem = rng.standard_normal(64).astype(np.float32)
    shmem = rng.standard_normal((2, 64)).astype(np.float32)
    outs = {}
    for engine in ("step", "trace", "megakernel"):
        dcfg = DeviceConfig(n_sms=n_sms, global_mem_depth=64, engine=engine,
                            sm=SMConfig(shmem_depth=64, max_steps=500))
        outs[engine] = launch(dcfg, prog, grid=2, block=block, gmem=gmem,
                              shmem=shmem, schedule=schedule)
    assert_bit_identical(outs["step"], outs["trace"])
    assert_bit_identical(outs["step"], outs["megakernel"])


def test_megakernel_flushes_a_denormal_sum_of_known_values():
    """A guarded FP add of plan-time constants runs in the fused segment
    as a residual op; it must flush its denormal result to zero like the
    device does, not keep it as a compile-time fold would (a case the
    fuzz above found)."""
    prog = np.array([Instr(op=Op.LODI, rd=14, imm=1).encode(),
                     Instr(op=Op.ADD, rd=0, ra=0, rb=14).encode(),
                     Instr(op=Op.INIT, imm=1).encode(),
                     Instr(op=Op.ADD, width=Width.HALF).encode(),
                     Instr(op=Op.ADD, typ=Typ.FP32, pen=1).encode(),
                     Instr(op=Op.LOOP, imm=3).encode(),
                     Instr(op=Op.STOP).encode()], np.int64)
    rng = np.random.default_rng(0)
    gmem = rng.standard_normal(64).astype(np.float32)
    shmem = rng.standard_normal((2, 64)).astype(np.float32)
    outs = {}
    for engine in ("step", "megakernel"):
        dcfg = DeviceConfig(n_sms=1, global_mem_depth=64, engine=engine,
                            sm=SMConfig(shmem_depth=64, max_steps=500))
        outs[engine] = launch(dcfg, prog, grid=2, block=16, gmem=gmem,
                              shmem=shmem, schedule="static")
    # lanes 8-15 hold 1 (a denormal's bits) and add it to itself
    assert np.asarray(outs["step"].regs)[0, 8:16, 0].tolist() == [0] * 8
    assert_bit_identical(outs["step"], outs["megakernel"])


@st.composite
def _guarded_program(draw):
    """Every body instr guarded by R15 (all-zero): (guarded, nop_swapped,
    unguarded) word arrays with IDENTICAL instruction counts. SELP is
    excluded — its guard selects an arm instead of gating the write, so
    it is never architecturally a no-op."""
    from dataclasses import replace as dc_replace
    body = []
    for _ in range(draw(st.integers(1, 5))):
        i = _pred_instr(draw, 1)
        while i.op == Op.SELP:
            i = _pred_instr(draw, 1)
        body.append(dc_replace(i, preg=15))

    guarded = body + [Instr(op=Op.STOP)]
    nops = [Instr(op=Op.NOP) for _ in body] + [Instr(op=Op.STOP)]
    bare = [dc_replace(i, pen=0, preg=0, pneg=0) for i in body] \
        + [Instr(op=Op.STOP)]
    enc = lambda p: np.array([i.encode() for i in p], np.int64)  # noqa: E731
    pneg_any = any(i.pneg for i in body)
    return enc(guarded), enc(nops), enc(bare), pneg_any


@settings(max_examples=30, deadline=None)
@given(progs=_guarded_program(), seed=st.integers(0, 2**31 - 1))
def test_fuzz_all_off_guard_is_architectural_nop(progs, seed):
    guarded, nops, bare, pneg_any = progs
    rng = np.random.default_rng(seed)
    gmem = rng.standard_normal(64).astype(np.float32)
    shmem = rng.standard_normal((1, 64)).astype(np.float32)

    def go(words):
        dcfg = DeviceConfig(n_sms=1, global_mem_depth=64,
                            sm=SMConfig(shmem_depth=64, max_steps=200))
        return launch(dcfg, words, grid=1, block=32, gmem=gmem, shmem=shmem)

    res = go(guarded)
    if pneg_any:
        # mixed-polarity guards: at least each @R15 (all-off) instr is
        # dead, but the @!R15 ones are live -> only compare vs bare when
        # ALL polarities are negated
        if all(Instr.decode(int(w)).pneg for w in guarded[:-1]):
            assert_bit_identical(res, go(bare))   # all-ON: cycles too
    else:
        # masked lanes never mutate registers, shmem, or gmem
        assert_arch_identical(res, go(nops))


def test_all_on_guard_is_bit_identical_to_unguarded():
    # deterministic witness of the fuzz property's all-on arm, cycles
    # included: predication is free when every lane passes
    body = [Instr(op=Op.TDX, rd=1),
            Instr(op=Op.ADD, typ=Typ.INT32, rd=2, ra=1, rb=1),
            Instr(op=Op.STO, rd=2, ra=1, imm=0),
            Instr(op=Op.GST, rd=2, ra=1, imm=16)]
    guarded = [Instr(**{**i.__dict__, "pen": 1, "preg": 15, "pneg": 1})
               for i in body] + [Instr(op=Op.STOP)]
    bare = body + [Instr(op=Op.STOP)]
    enc = lambda p: np.array([i.encode() for i in p], np.int64)  # noqa: E731

    def go(words):
        dcfg = DeviceConfig(n_sms=2, global_mem_depth=64,
                            sm=SMConfig(shmem_depth=64, max_steps=200))
        return launch(dcfg, words, grid=2, block=16)

    assert_bit_identical(go(enc(guarded)), go(enc(bare)))


# ---------------------------------------------------------------------------
# the new program library, numerically
# ---------------------------------------------------------------------------

def test_cholesky_factors_spd_and_solves():
    rng = np.random.default_rng(0)
    g = rng.standard_normal((16, 16)).astype(np.float32)
    a = (g @ g.T + 16 * np.eye(16)).astype(np.float32)
    b = rng.standard_normal(16).astype(np.float32)
    from repro.core.programs.cholesky import run_cholesky

    el, y, _ = run_cholesky(a, b)
    ref = np.linalg.cholesky(a.astype(np.float64))
    assert np.abs(el - ref).max() < 1e-4
    assert np.all(el[np.triu_indices(16, 1)] == 0.0)  # masked stores: exact
    assert np.abs(el @ y - b).max() < 1e-4


def test_cholesky_skips_singular_pivot():
    rng = np.random.default_rng(1)
    g = rng.standard_normal((16, 16)).astype(np.float32)
    a = (g @ g.T + 16 * np.eye(16)).astype(np.float32)
    a[5, :] = 0.0
    a[:, 5] = 0.0                      # exactly singular pivot 5
    from repro.core.programs.cholesky import run_cholesky

    el, _, _ = run_cholesky(a)
    assert np.all(el[:, 5] == 0.0)     # the guarded column folded to zero
    keep = np.ones(16, bool)
    keep[5] = False
    r = (el @ el.T - a)[np.ix_(keep, keep)]
    assert np.abs(r).max() < 1e-4      # the rest factored normally


def test_masked_reduction_matches_numpy():
    rng = np.random.default_rng(2)
    x = (rng.standard_normal(300) * 3).astype(np.float32)
    from repro.core.programs.masked_reduction import launch_masked_reduction

    for t, clip in [(0.0, (-np.inf, np.inf)), (1.0, (-2.0, 2.0)),
                    (99.0, (-2.0, 2.0)), (-99.0, (-1.0, 1.0))]:
        s, c, _ = launch_masked_reduction(x, t, clip=clip, block=64)
        y = np.clip(x, clip[0], clip[1])
        m = y > t
        assert c == int(m.sum()), (t, clip)
        ref = float(np.sum(y[m], dtype=np.float64))
        assert s == pytest.approx(ref, abs=2e-3 * max(1.0, abs(ref)))
