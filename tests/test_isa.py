"""ISA encoding/decoding + assembler unit & property tests."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import assemble, check_hazards, disassemble
from repro.core.assembler import AsmError, assemble_line
from repro.core.isa import (
    Cond,
    CONTROL_IMM_OPS,
    NUM_CLASSES,
    Depth,
    Instr,
    Op,
    Typ,
    Width,
    instr_class,
)


def test_encode_decode_roundtrip_basic():
    ins = Instr(op=Op.ADD, typ=Typ.FP32, rd=1, ra=2, rb=3,
                width=Width.HALF, depth=Depth.SINGLE)
    assert Instr.decode(ins.encode()) == ins


def test_word_is_40_bits():
    ins = Instr(op=Op.STOP, width=Width.SINGLE, depth=Depth.SINGLE,
                typ=Typ.FP32, rd=15, ra=15, rb=15, imm=-1)
    w = ins.encode()
    assert 0 <= w < (1 << 40)


@settings(max_examples=300, deadline=None)
@given(
    op=st.sampled_from(list(Op)),
    typ=st.sampled_from(list(Typ)),
    rd=st.integers(0, 15), ra=st.integers(0, 15), rb=st.integers(0, 15),
    imm=st.integers(-(1 << 14), (1 << 14) - 1),
    width=st.sampled_from(list(Width)),
    depth=st.sampled_from(list(Depth)),
)
def test_encode_decode_roundtrip_property(op, typ, rd, ra, rb, imm, width, depth):
    if op in (Op.JMP, Op.JSR, Op.LOOP, Op.INIT):
        imm = abs(imm)  # control addresses are unsigned
    if op == Op.SETP:
        imm %= len(Cond)  # SETP's immediate is its condition code
    ins = Instr(op=op, typ=typ, rd=rd, ra=ra, rb=rb, imm=imm,
                width=width, depth=depth)
    dec = Instr.decode(ins.encode())
    assert dec == ins


@settings(max_examples=200, deadline=None)
@given(rd=st.integers(0, 15), ra=st.integers(0, 15), rb=st.integers(0, 15),
       ea=st.integers(0, 31), eb=st.integers(0, 31))
def test_snoop_roundtrip_property(rd, ra, rb, ea, eb):
    ins = Instr(op=Op.ADD, typ=Typ.FP32, rd=rd, ra=ra, rb=rb, x=1,
                ext_a=ea, ext_b=eb)
    assert Instr.decode(ins.encode()) == ins


def test_snoop_excludes_immediate():
    with pytest.raises(ValueError):
        Instr(op=Op.ADD, x=1, ext_a=1, imm=5).encode()


def test_imm_range_checked():
    with pytest.raises(ValueError):
        Instr(op=Op.LODI, imm=1 << 15).encode()


def test_signed_imm_rejects_sign_extension_range():
    # regression: encode used to accept [2^14, 2^15) for signed-immediate
    # ops, but decode sign-extends bit 14, so those values round-tripped
    # negative. The encode-time check now matches decode.
    for op in (Op.LODI, Op.LOD, Op.STO, Op.GLD, Op.GST, Op.ADD):
        with pytest.raises(ValueError):
            Instr(op=op, imm=1 << 14).encode()
        with pytest.raises(ValueError):
            Instr(op=op, imm=(1 << 15) - 1).encode()
        # the boundary values round-trip exactly
        for imm in (-(1 << 14), (1 << 14) - 1, -1, 0):
            assert Instr.decode(Instr(op=op, imm=imm).encode()).imm == imm


def test_control_imm_full_unsigned_range():
    for op in CONTROL_IMM_OPS:
        assert Instr.decode(Instr(op=op, imm=(1 << 15) - 1).encode()).imm \
            == (1 << 15) - 1
        with pytest.raises(ValueError):
            Instr(op=op, imm=1 << 15).encode()
        with pytest.raises(ValueError):
            Instr(op=op, imm=-1).encode()


def test_new_device_ops_roundtrip():
    for op in (Op.GLD, Op.GST):
        ins = Instr(op=op, rd=3, ra=5, imm=-17, width=Width.SINGLE,
                    depth=Depth.SINGLE)
        assert Instr.decode(ins.encode()) == ins
    ins = Instr(op=Op.BID, rd=9)
    assert Instr.decode(ins.encode()) == ins


def test_assemble_basic_program():
    prog = assemble("""
        TDX R1
        LOD R2, (R1)+0
        ADD.FP32 R3, R2, R2 {w8,dhalf}
        STO R3, (R1)+16
        STOP
    """)
    assert len(prog) == 5
    assert prog.instrs[2].width == Width.HALF
    assert prog.instrs[2].depth == Depth.HALF


def test_assemble_labels_and_loops():
    prog = assemble("""
        INIT 4
    top:
        NOP
        LOOP top
        JMP end
        NOP
    end:
        STOP
    """)
    assert prog.labels["top"] == 1
    assert prog.instrs[2].imm == 1
    assert prog.instrs[3].imm == 5


def test_assemble_snoop_syntax():
    prog = assemble("ADD.FP32 R1, R2@3, R4@7 {d1}")
    ins = prog.instrs[0]
    assert ins.x == 1 and ins.ext_a == 3 and ins.ext_b == 7


def test_assembler_errors():
    for bad in ["FROB R1, R2, R3", "ADD.FP32 R1, R2", "LOD R99, #1",
                "STO R1, #5", "JMP nowhere", "ADD.FP32 R1, R2@99, R3"]:
        with pytest.raises(AsmError):
            assemble(bad)


def test_disassemble_smoke():
    src = ["ADD.FP32 R1, R2, R3", "LOD R2, (R1)+5", "STO R2, (R3)+0",
           "LOD R4, #-7", "DOT.FP32 R1, R2, R3", "STOP"]
    for s in src:
        prog = assemble(s)
        d = disassemble(int(prog.words[0]))
        prog2 = assemble(d)
        assert prog2.words[0] == prog.words[0], (s, d)


@settings(max_examples=100, deadline=None)
@given(op=st.sampled_from(list(Op)), typ=st.sampled_from(list(Typ)))
def test_instr_class_total(op, typ):
    assert 0 <= instr_class(op, typ) < NUM_CLASSES


def test_hazard_checker_flags_raw():
    prog = assemble("""
        TDX R1
        ADD.INT32 R2, R1, R1
        STOP
    """)
    warns = check_hazards(prog, n_threads=16)  # 1 wavefront: gap 1 < 9
    assert warns
    prog2 = assemble("TDX R1\n" + "NOP\n" * 8 + "ADD.INT32 R2, R1, R1\nSTOP")
    assert not check_hazards(prog2, n_threads=16)


def test_auto_nop_converges_and_clean():
    from repro.core.assembler import auto_nop

    text = """
        TDX R1
        ADD.INT32 R2, R1, R1
        MUL.FP32 R3, R2, R2
        STO R3, (R1)+0
        LOD R4, (R1)+0
        STOP
    """
    padded = auto_nop(text, n_threads=16)
    assert not check_hazards(assemble(padded), n_threads=16)
