"""eGPU instruction-set simulator: decode machinery + execute backends.

Faithful to the paper's SM microarchitecture:

  * 16 SPs; thread ``t`` runs on SP ``t % 16`` (its *lane*), in wavefront
    ``t // 16``. SP ``l``'s register file (two M20Ks, 512x32 each as 2R1W)
    holds registers for threads ``{l, 16+l, 32+l, ...}``.
  * Flexible ISA: per-instruction WIDTH/DEPTH resize the active thread
    block with no flush — implemented as an active-thread mask.
  * Thread snooping (X=1): source operands read ``regs[ext*16 + lane]``,
    letting wavefront-0 threads address any register in their lane.
  * DOT/SUM extension units reduce each active wavefront and write lane 0;
    INVSQR is a single-lane SFU on wavefront 0 / lane 0.
  * Shared memory: quad read port (cycle model: 4 threads/clock on LOD),
    single write port (1 thread/clock on STO; writeback is sequential in
    thread order, so the *last* active thread wins on address collisions —
    we reproduce that determinism exactly).
  * Zero-overhead loops (INIT/LOOP), JSR/RTS return stack, STOP flag.
  * No hardware interlocks: the ISS executes architecturally (every read
    sees the latest architectural write). Timing hazards are a *static*
    property checked by ``assembler.check_hazards``; the paper's NOP
    mitigation is reproduced in the benchmark programs.

Since the multi-SM refactor the stepping loop itself lives in
``device.py`` and operates on a whole SM *batch* in lockstep; this module
owns the pieces every step needs:

  * ``pack_imem`` / ``_decode`` — the 40-bit I-word field extraction;
  * the opcode -> handler-group and opcode -> profile-class tables;
  * the **shared execute stage** (``make_data_handlers``): the data-path
    handlers of every instruction group, dispatched by BOTH engines — the
    stepping machine (``device._device_step``) and the trace-compiled
    scan (``core.trace_engine``) — so the two are bit-identical by
    construction;
  * the **pluggable execute backends** (``ExecBackend``). Since the
    trace-engine refactor the seam covers the whole execute stage: the
    ALU column plus the LOD/STO quad-read/single-write-port
    gather/scatter and the GLD/GST global accesses. Two implementations
    ship:

      - ``"inline"``  — straight jnp (the ``kernels.ref`` oracle + the
        scatter-max port-serialization trick);
      - ``"pallas"``  — the ``kernels.simt_alu`` ALU kernel and the
        ``kernels.simt_step`` gather/scatter kernels, so a multi-SM
        step's data path executes as Pallas grids over the SM batch
        (interpreted on CPU, compiled on TPU).

    Both are bit-exact by construction and selected per run via
    ``run(..., backend=...)`` / ``DeviceConfig.backend``.

``run`` and ``run_many`` are preserved as single-wave shims over the
device layer (always on the step machine); new code should use
``device.launch``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import isa
from .isa import Op
from .machine import MAX_THREADS, MAX_WAVES, N_SP, MachineState, SMConfig

_U32 = jnp.uint32
_I32 = jnp.int32
_F32 = jnp.float32


def pack_imem(words: np.ndarray, depth: int) -> tuple[np.ndarray, np.ndarray]:
    """Split I-words into (lo32, hi) uint32 arrays of ``depth``.

    ``hi`` carries the architectural bits [39:32] plus the predication
    extension byte [45:40] (pen/preg/pneg — zero on every legacy word)."""
    w = np.asarray(words, dtype=np.int64)
    if w.shape[0] > depth:
        raise ValueError(f"program of {w.shape[0]} words exceeds I-MEM depth {depth}")
    lo = (w & 0xFFFFFFFF).astype(np.uint32)
    hi = ((w >> 32) & 0x3FFF).astype(np.uint32)
    pad = depth - w.shape[0]
    # pad with STOP so runaway PCs halt
    stop_word = isa.Instr(op=Op.STOP).encode()
    lo = np.concatenate([lo, np.full((pad,), stop_word & 0xFFFFFFFF, np.uint32)])
    hi = np.concatenate([hi, np.full((pad,), (stop_word >> 32) & 0x3FFF, np.uint32)])
    return lo, hi


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _decode(lo: jax.Array, hi: jax.Array) -> dict[str, jax.Array]:
    imm_raw = (lo & 0x7FFF).astype(_I32)
    imm_sext = jnp.where(imm_raw & 0x4000, imm_raw - (1 << 15), imm_raw)
    return dict(
        imm_raw=imm_raw,
        imm=imm_sext,
        x=((lo >> 15) & 1).astype(_I32),
        rb=((lo >> 16) & 0xF).astype(_I32),
        ra=((lo >> 20) & 0xF).astype(_I32),
        rd=((lo >> 24) & 0xF).astype(_I32),
        typ=((lo >> 28) & 0x3).astype(_I32),
        opcode=(((lo >> 30) & 0x3) | ((hi & 0xF) << 2)).astype(_I32),
        depth=((hi >> 4) & 0x3).astype(_I32),
        width=((hi >> 6) & 0x3).astype(_I32),
        ext_a=((lo >> 10) & 0x1F).astype(_I32),
        ext_b=((lo >> 5) & 0x1F).astype(_I32),
        # predication extension byte (word bits [45:40] = hi bits [13:8])
        preg=((hi >> 8) & 0xF).astype(_I32),
        pen=((hi >> 12) & 0x1).astype(_I32),
        pneg=((hi >> 13) & 0x1).astype(_I32),
    )


# opcode -> handler group
(_G_NOP, _G_ALU, _G_LOD, _G_STO, _G_LODI, _G_TD, _G_RED, _G_SFU, _G_CTL,
 _G_GLD, _G_GST, _G_SETP, _G_SELP) = range(13)
_GROUP_OF_OP = np.zeros((64,), np.int32)
for _op, _g in {
    Op.NOP: _G_NOP,
    Op.ADD: _G_ALU, Op.SUB: _G_ALU, Op.MUL: _G_ALU, Op.AND: _G_ALU,
    Op.OR: _G_ALU, Op.XOR: _G_ALU, Op.NOT: _G_ALU, Op.LSL: _G_ALU,
    Op.LSR: _G_ALU,
    Op.LOD: _G_LOD, Op.STO: _G_STO, Op.LODI: _G_LODI,
    Op.TDX: _G_TD, Op.TDY: _G_TD, Op.BID: _G_TD, Op.PID: _G_TD,
    Op.DOT: _G_RED, Op.SUM: _G_RED, Op.INVSQR: _G_SFU,
    Op.JMP: _G_CTL, Op.JSR: _G_CTL, Op.RTS: _G_CTL, Op.LOOP: _G_CTL,
    Op.INIT: _G_CTL, Op.STOP: _G_CTL,
    Op.GLD: _G_GLD, Op.GST: _G_GST,
    Op.SETP: _G_SETP, Op.SELP: _G_SELP,
}.items():
    _GROUP_OF_OP[int(_op)] = _g

# opcode -> profile class, per operand type (rows of Tables III/IV + GMEM)
_CLASS_OF = np.zeros((64, 3), np.int32)
for _op in Op:
    for _t in isa.Typ:
        _CLASS_OF[int(_op), int(_t)] = isa.instr_class(_op, _t)


def _setp_compare(cond, typ, a_u, b_u) -> jax.Array:
    """Per-lane SETP compare -> bool tile.

    ``cond``/``typ`` may be traced i32 scalars (step/trace engines) or
    Python ints (megakernel fused rows): every comparison is exact, so
    the traced select chain and the host-constant branch compute
    identical bits. NaN note: FP32 ordered compares are all-false on
    NaN operands, so GT/GE are computed directly (never as ~LE/~LT)."""
    a_i = jax.lax.bitcast_convert_type(a_u, _I32)
    b_i = jax.lax.bitcast_convert_type(b_u, _I32)
    a_f = jax.lax.bitcast_convert_type(a_u, _F32)
    b_f = jax.lax.bitcast_convert_type(b_u, _F32)
    if isinstance(cond, int) and isinstance(typ, int):
        # host-constant fields: only the taken compare (Pallas-friendly)
        a, b = ((a_f, b_f) if typ == int(isa.Typ.FP32) else
                (a_i, b_i) if typ == int(isa.Typ.INT32) else (a_u, b_u))
        return _SETP_CMPS.get(cond, _setp_ge)(a, b)
    is_fp = typ == int(isa.Typ.FP32)
    is_int = typ == int(isa.Typ.INT32)

    def pick(f):
        return jnp.where(is_fp, f(a_f, b_f),
                         jnp.where(is_int, f(a_i, b_i), f(a_u, b_u)))

    res = pick(_setp_ge)
    for code, f in _SETP_CMPS.items():
        res = jnp.where(cond == code, pick(f), res)
    return res


def _setp_ge(a, b):
    return a >= b


# SETP's compare per condition code; any other code is GE
_SETP_CMPS = {
    int(isa.Cond.EQ): lambda a, b: a == b,
    int(isa.Cond.NE): lambda a, b: ~(a == b),
    int(isa.Cond.LT): lambda a, b: a < b,
    int(isa.Cond.LE): lambda a, b: a <= b,
    int(isa.Cond.GT): lambda a, b: a > b,
}


# ---------------------------------------------------------------------------
# pluggable execute backends (the whole per-step execute stage)
# ---------------------------------------------------------------------------
#
# A backend implements the data-path operations of one instruction over an
# SM batch. Since the trace-engine refactor the seam covers the WHOLE
# execute stage, not just the ALU:
#
#   alu(op, typ, a, b, mask, old)   -> (n_sms, 512) destination column
#   lod(shmem, addr, mask, old)     -> (n_sms, 512) quad-port gather
#   sto(shmem, addr, vals, do)      -> (n_sms, depth) single-port scatter
#                                      (last active thread wins)
#   gld(gmem, addr, mask, old)      -> (n_sms, 512) global gather
#   gst(gmem, addr, vals, do)       -> (gdepth,) device-wide scatter
#                                      (last (sm, thread) writer wins)
#
# ``op``/``typ`` are traced i32 scalars (decoded fields), ``a``/``b``
# pre-gathered source-operand tiles, ``mask``/``do`` the flexible-ISA
# active-thread mask (with out-of-range lanes already dropped), ``addr``
# pre-clipped to the memory depth for the gathers and raw for the scatters.
# All five ops must be bit-exact across backends; the stepping machine
# and the trace engine drive them through ``make_data_handlers`` below,
# and the megakernel engine's fused rows (``_apply_row_cols``) are
# decoded from the same tables — so functional semantics are shared by
# construction.

ExecuteOp = Callable[..., jax.Array]


# ---------------------------------------------------------------------------
# fused segments (the megakernel engine's unit of work)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FusedRow:
    """One pre-decoded data instruction, fully resolved on the host.

    Unlike the trace engine's scanned schedule — where the decoded fields
    are traced i32 scalars selected per step — a fused row carries its
    fields as HOST constants (``sel`` the data-switch branch, ``d`` numpy
    i32 scalars, ``active`` the (512,) numpy thread mask). Constant
    fields let XLA fold the per-row dispatch, masks and operand selects
    at trace time, which is the megakernel speedup: the 10-way
    ``lax.switch`` and the mask/branch arithmetic disappear from the
    compiled body entirely.
    """

    sel: int                   # data-switch branch (never 0/8/9 in a
                               # fused run: identity rows are dropped and
                               # global-port rows break segments)
    d: dict                    # decoded fields as np.int32 scalars
    active: np.ndarray         # (512,) bool flexible-ISA thread mask
    act_waves: int             # flexible-ISA depth (active wavefronts) —
    act_wthreads: int          # ... and width; `active` is derived from
                               # these, but the fused body rebuilds the
                               # traced mask from iota comparisons so a
                               # Pallas kernel never captures a constant
                               # array (Pallas rejects captured consts)


class XlaLanes:
    """Lane-level steps of the execute stage, as XLA ops over
    ``(n_sms, 512)`` tiles.

    The fused rows (``_apply_row_cols``) and the DOT/SUM handler reach
    across lanes only through these helpers. A Pallas kernel body passes
    its own set (``kernels.simt_step.KernelLanes``) built from lane
    rotations, which Mosaic lowers; both sets compute the same values in
    the same order, so the results are equal bit for bit.
    """

    @staticmethod
    def snoop(col, ext: int):
        """Snooped operand: lane ``t`` reads ``col[ext*16 + t % 16]``."""
        lane = jnp.arange(MAX_THREADS, dtype=_I32) % N_SP
        return jnp.take(col, ext * N_SP + lane, axis=1)

    @staticmethod
    def trap(oob, bad):
        """Fold a lane trap mask into the per-SM OOB flag."""
        return oob | bad.any(axis=1)

    @staticmethod
    def wave_sum(x):
        """Per-wavefront FP32 sum, accumulated from 0.0 in lane order.
        Lane 0 of each wavefront holds its sum; other lanes are 0."""
        x3 = x.reshape(x.shape[0], MAX_WAVES, N_SP)
        acc = jnp.zeros(x3.shape[:2], x.dtype)
        for j in range(N_SP):
            acc = acc + x3[:, :, j]
        return jnp.pad(acc[:, :, None], ((0, 0), (0, 0), (0, N_SP - 1))
                       ).reshape(x.shape)

    @staticmethod
    def wave_any(m):
        """Per-wavefront OR, held in lane 0 of each wavefront."""
        a = m.reshape(m.shape[0], MAX_WAVES, N_SP).any(axis=2)
        return jnp.pad(a[:, :, None], ((0, 0), (0, 0), (0, N_SP - 1))
                       ).reshape(m.shape)

    @staticmethod
    def lane0(col, src: int):
        """A tile whose lane 0 holds ``col[:, src]``."""
        return col[:, src:src + 1]


def rounding_fence(block_idx):
    """A (n_sms, 1) uint32 zero the compiler cannot see through.

    Each eGPU FP instruction rounds its own result, but XLA's CPU backend
    contracts a multiply feeding an add into one fused multiply-add once
    both sit in one fusion (the fused rows' constant masks fold away
    every select in between), skipping the multiply's rounding. OR-ing an
    FP result's bits with this zero ends that: block ids are below 2**31,
    so ``bid >> 31`` is 0, a fact no compiler can derive. (XLA's
    optimization barrier is removed before fusion and does not help.)"""
    return jnp.asarray(block_idx).astype(_U32).reshape(-1, 1) >> 31


def _fenced(x_f32, zero):
    return jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(x_f32, _U32) | zero, _F32)


def sfu_rsqrt(x_u, zero):
    """The INVSQR unit: 1/sqrt of FP32 bits ``x_u``, as uint32 bits.

    Built from integer ops, multiplies and subtracts only: a bit-level
    first guess and three Newton steps, each rounded on its own through
    ``zero`` (``rounding_fence``). Every backend computes exactly these
    IEEE operations, so the result is the same bits in XLA on any
    device and inside a Pallas kernel, where a library ``rsqrt`` (or a
    divide) is implemented differently by each compiler and even by
    the shape it is vectorised at. Within 1 ulp of 1/sqrt(x) on normal
    inputs; +-0 (and subnormals, which both the CPU and the TPU flush)
    -> +-inf, +inf -> 0, NaN or negative -> NaN."""
    x = jax.lax.bitcast_convert_type(x_u, _F32)
    y = jax.lax.bitcast_convert_type(
        np.int32(0x5F375A86)
        - (jax.lax.bitcast_convert_type(x, _I32) >> 1), _F32)
    half = _fenced(x * np.float32(0.5), zero)
    for _ in range(2):
        t = _fenced(_fenced(half * y, zero) * y, zero)
        y = _fenced(y * _fenced(np.float32(1.5) - t, zero), zero)
    # last step as a small correction, so its rounding error is small
    e = _fenced(np.float32(0.5) - _fenced(_fenced(half * y, zero) * y, zero),
                zero)
    y = _fenced(y + _fenced(y * e, zero), zero)
    y = jnp.where(x == np.float32(np.inf), np.float32(0.0), y)
    y = jnp.where(x == 0, jnp.where(x_u >> 31 == 0, np.float32(np.inf),
                                    np.float32(-np.inf)), y)
    y = jnp.where((x < 0) | (x != x), np.float32(np.nan), y)
    return jax.lax.bitcast_convert_type(y, _U32)


def _wave_terms(is_dot, a_u, b_u, zero):
    """The per-lane DOT (``a*b``) or SUM (``a+b``) terms, each rounded
    on its own before the wavefront sum. ``is_dot`` may be traced."""
    a_f = jax.lax.bitcast_convert_type(a_u, _F32)
    b_f = jax.lax.bitcast_convert_type(b_u, _F32)
    if isinstance(is_dot, bool):
        return _fenced(a_f * b_f if is_dot else a_f + b_f, zero)
    return jnp.where(is_dot, _fenced(a_f * b_f, zero),
                     _fenced(a_f + b_f, zero))


def _wave_reduce(lanes, prod, lane_eff, cur):
    """DOT/SUM over one tile: each wavefront's enabled terms reduce into
    its lane 0 in lane order; a wavefront with no enabled lane keeps its
    old lane-0 value. Every other lane keeps ``cur``."""
    red = lanes.wave_sum(jnp.where(lane_eff, prod, 0.0))
    head = (jax.lax.broadcasted_iota(_I32, cur.shape, 1) % N_SP) == 0
    write = head & lanes.wave_any(lane_eff)
    return jnp.where(write, jax.lax.bitcast_convert_type(red, _U32), cur)


def _apply_row_cols(cfg, backend: "ExecBackend", row: FusedRow, cols,
                    shmem, oob, block_idx, prog_idx,
                    shmem_depth: int | None, lanes=XlaLanes):
    """One fused row over UNPACKED register columns.

    ``cols`` is the mutable list of 16 per-register (n_sms, 512) tiles;
    ``block_idx``/``prog_idx`` are (n_sms, 1) uint32 columns. This is the
    same data path as the matching ``make_data_handlers`` handler — same
    backend seam ops (``backend.alu``/``lod``/``sto``), same
    mask/clip/trap formulas — specialized for host-constant fields: a
    register write is a zero-copy column rebinding instead of a
    (n_sms, 512, 16) scatter, a no-snoop operand read is the column
    itself instead of a dynamic gather, and the select chains collapse
    to the one taken branch (which computes the identical values).
    ``lanes`` supplies the cross-lane steps (``XlaLanes`` here, the
    rotation-based set inside a Pallas kernel). Bit-identity vs the
    packed handlers is pinned by the engine conformance matrix.
    """
    from .isa import Typ

    d = row.d
    sel = row.sel
    op, typ = int(d["opcode"]), int(d["typ"])
    rd, ra, rb = int(d["rd"]), int(d["ra"]), int(d["rb"])
    imm = int(d["imm"])
    snoop = int(d["x"]) == 1
    n_sms = cols[0].shape[0]
    # the traced mask is rebuilt from iota comparisons against Python-int
    # fields: XLA folds them to constants at compile time, and a Pallas
    # kernel tracing this body captures no constant arrays (which
    # pallas_call rejects). The iota is 2-D: Mosaic has no 1-D vectors.
    tid_t = jax.lax.broadcasted_iota(_I32, (1, MAX_THREADS), 1)
    lane_t = tid_t % N_SP
    active = ((lane_t < row.act_wthreads)
              & (tid_t // N_SP < row.act_waves)
              & (tid_t < cfg.n_threads))

    # SIMT predication: PEN is a HOST constant here (legacy rows pay
    # nothing), but the predicate VALUE is runtime — read from the guard
    # register column, never captured as a constant array (Pallas-safe).
    # ``eff`` replaces ``active`` in every write/port mask; ``psel`` is
    # the raw predicate (SELP's selector). Same formulas as the
    # ``make_data_handlers`` handlers, so bit-identity is preserved.
    pen = int(d.get("pen", 0))
    if pen:
        psel = (cols[int(d["preg"])] & 1) != 0             # (n_sms, 512)
        if int(d.get("pneg", 0)):
            psel = ~psel
        eff = active & psel
    else:
        psel = None
        eff = active

    def read(r, ext):
        # snoop (X=1) gathers regs[ext*16 + lane]; without it the
        # operand IS the register column — no gather at all
        if snoop:
            return lanes.snoop(cols[r], int(ext))
        return cols[r]

    def addr_of():
        a_u = read(ra, d["ext_a"])
        return jax.lax.bitcast_convert_type(a_u, _I32) + imm

    if sel == 1:                                           # ALU
        a_u, b_u = read(ra, d["ext_a"]), read(rb, d["ext_b"])
        old = cols[rd]
        mask = jnp.broadcast_to(eff, old.shape)
        cols[rd] = backend.alu(d["opcode"], d["typ"], a_u, b_u, mask, old)
        if typ == int(Typ.FP32):
            cols[rd] = cols[rd] | rounding_fence(block_idx)
    elif sel == 2:                                         # LOD
        depth = shmem_depth if shmem_depth is not None else shmem.shape[1]
        addr = addr_of()
        bad = eff & ((addr < 0) | (addr >= depth))
        safe = jnp.clip(addr, 0, depth - 1)
        mask = eff & ~bad
        cols[rd] = backend.lod(shmem, safe, mask, cols[rd])
        oob = lanes.trap(oob, bad)
    elif sel == 3:                                         # STO
        depth = shmem_depth if shmem_depth is not None else shmem.shape[1]
        addr = addr_of()
        bad = eff & ((addr < 0) | (addr >= depth))
        shmem = backend.sto(shmem, addr, cols[rd], eff & ~bad)
        oob = lanes.trap(oob, bad)
    elif sel == 4:                                         # LODI
        if typ == int(Typ.FP32):
            val = int(np.float32(imm).view(np.uint32))     # host bitcast
        else:
            val = imm & 0xFFFFFFFF
        vals = jnp.full((n_sms, MAX_THREADS), val, _U32)
        cols[rd] = jnp.where(eff, vals, cols[rd])
    elif sel == 5:                                         # TDX/TDY/BID/PID
        if op == int(Op.TDX):
            vals = (tid_t % cfg.dim_x).astype(_U32)
        elif op == int(Op.TDY):
            vals = (tid_t // cfg.dim_x).astype(_U32)
        elif op == int(Op.BID):
            vals = block_idx
        else:
            vals = prog_idx
        cols[rd] = jnp.where(eff, jnp.broadcast_to(vals, cols[rd].shape),
                             cols[rd])
    elif sel == 6:                                         # DOT/SUM
        # predicated-off lanes contribute nothing; a wavefront with no
        # enabled lane keeps its old lane-0 value
        a_u, b_u = read(ra, d["ext_a"]), read(rb, d["ext_b"])
        lane_eff = jnp.broadcast_to(eff, a_u.shape)
        prod = _wave_terms(op == int(Op.DOT), a_u, b_u,
                           rounding_fence(block_idx))
        cols[rd] = _wave_reduce(lanes, prod, lane_eff, cols[rd])
    elif sel == 7:                                         # SFU (INVSQR)
        src = int(d["ext_a"]) * N_SP if snoop else 0
        new = sfu_rsqrt(lanes.lane0(cols[ra], src), rounding_fence(block_idx))
        write = tid_t == 0
        if pen:
            # the SFU issues from thread 0: its predicate gates the write
            write = write & psel
        cols[rd] = jnp.where(write, jnp.broadcast_to(new, cols[rd].shape),
                             cols[rd])
    elif sel == 10:                                        # SETP
        a_u, b_u = read(ra, d["ext_a"]), read(rb, d["ext_b"])
        res = _setp_compare(imm, typ, a_u, b_u)
        cols[rd] = jnp.where(eff, res.astype(_U32), cols[rd])
    elif sel == 11:                                        # SELP
        a_u, b_u = read(ra, d["ext_a"]), read(rb, d["ext_b"])
        vals = jnp.where(psel, a_u, b_u) if pen else a_u
        cols[rd] = jnp.where(active, vals, cols[rd])
    else:
        raise AssertionError(
            f"fused row with non-SM-local handler sel={sel}")
    return cols, shmem, oob


def apply_segment_rows(cfg, backend: "ExecBackend", rows, block_idx,
                       prog_idx, regs, shmem, oob, *,
                       shmem_depth: int | None = None):
    """Unroll one fused segment body-to-body over an SM batch.

    ``rows`` is a tuple of ``FusedRow`` containing only SM-local data ops
    (ALU/LOD/STO/LODI/TD/RED/SFU — global-port rows delimit segments, so
    GLD/GST never appear here). The register file is unpacked into 16
    per-register columns for the whole segment, every row executes the
    shared backend seam ops with host-constant fields via
    ``_apply_row_cols``, and the file repacks once at the segment end —
    so a K-row segment pays 2 register-file copies instead of K.

    Both megakernel backends stage this one helper: "inline" (and any
    backend without a fused implementation, via ``exec_segment``) calls
    it directly; "pallas" runs it inside a single ``pallas_call`` that
    keeps the batch's registers/shmem resident across the fused steps
    (``kernels.simt_step.simt_segment``).
    """
    cols = [regs[:, :, r] for r in range(regs.shape[2])]
    bid, pid = _sm_column(block_idx), _sm_column(prog_idx)
    for r in rows:
        cols, shmem, oob = _apply_row_cols(cfg, backend, r, cols, shmem,
                                           oob, bid, pid, shmem_depth)
    return jnp.stack(cols, axis=2), shmem, oob


def _sm_column(idx):
    """A per-SM index vector as the (n_sms, 1) uint32 column the fused
    rows broadcast across lanes."""
    return jnp.asarray(idx).astype(_U32)[:, None]


# ---------------------------------------------------------------------------
# plan-time partial evaluation (the megakernel's compile-time optimizer)
# ---------------------------------------------------------------------------
#
# Every wave starts from the architecturally-defined init state
# (``device.init_device_state``: all registers zero), and the flexible
# ISA has no data-dependent control flow — so at PLAN time (on the host,
# outside jit) the evaluator can thread exact register-column values
# through the fused rows. A column stays "known" (a concrete (512,)
# value) until a shared/global-memory load or a mixed write makes it
# runtime. Three rewrites fall out:
#
#   * rows whose operands and destination are all known FOLD AWAY —
#     evaluated eagerly at plan time by the SAME ``_apply_row_cols``
#     body (same jax ops, run eagerly: bit-identical by construction).
#     TDX/TDY/LODI chains and all address arithmetic vanish from the
#     compiled kernel.
#   * LOD rows with a known address column become STATIC GATHERS —
#     clip/trap/mask all resolved on the host, leaving one constant-
#     index gather plus a masked select.
#   * STO rows with a known address column become STATIC SCATTERS —
#     the single-port last-writer-wins arbitration resolves on the host
#     (the winning thread per address is a plan-time constant), leaving
#     one sorted unique-index set instead of a runtime scatter-max.
#
# The residual program assumes the zero-init contract: it is only valid
# for waves starting from ``init_device_state`` (which is how the device
# layer always launches). Backends opt in with ``fold_constants`` — only
# the reference "inline" backend does; custom backends keep the generic
# per-op seam (they must observe every ``alu``/``lod``/``sto`` call),
# and the Pallas backend runs its own fused kernel over the raw rows.

@dataclasses.dataclass(frozen=True)
class FusedSegment:
    """One fused segment: the raw row run plus its partial evaluation.

    ``rows`` feeds the generic and Pallas paths; ``residual`` (the ops
    left after plan-time constant folding, with host-resolved gather/
    scatter plans) feeds ``apply_segment_residual`` on fold-capable
    backends; ``final_consts`` are the register columns whose value is
    fully known at segment end (materialized once at repack).
    """

    rows: tuple                # FusedRow run (generic/Pallas path)
    residual: tuple            # (kind, row, data, consts) residual ops
    final_consts: tuple        # ((reg, (512,) np.uint32), ...)
    n_folded: int              # rows evaluated away entirely at plan time


# register indices each handler reads (operands + read-modify-write dest)
_ROW_READS = {1: ("ra", "rb", "rd"), 2: ("ra", "rd"), 3: ("ra", "rd"),
              4: ("rd",), 5: ("rd",), 6: ("ra", "rb", "rd"),
              7: ("ra", "rd"), 10: ("ra", "rb", "rd"),
              11: ("ra", "rb", "rd")}


def _fold_row(cfg, row: FusedRow, const_cols, depth: int) -> np.ndarray:
    """Evaluate one fully-known row eagerly (host): run the SAME
    ``_apply_row_cols`` body on (1, 512) tiles of the known columns and
    return the new destination column. Eager jax == jitted jax for
    these elementwise/reduce ops, so folding is bit-exact."""
    cols = [jnp.asarray(c)[None] if c is not None
            else jnp.zeros((1, MAX_THREADS), _U32) for c in const_cols]
    z = jnp.zeros((1, 1), _U32)
    cols, _, _ = _apply_row_cols(
        cfg, get_execute_backend("inline"), row, cols,
        jnp.zeros((1, 1), _U32), jnp.zeros((1,), jnp.bool_), z, z, depth)
    return np.asarray(cols[int(row.d["rd"])][0])


def _fold_addr(row: FusedRow, a_col: np.ndarray, depth: int):
    """Resolve a LOD/STO address column on the host: (clipped addresses,
    enabled-thread mask, any-trap flag) — the same clip/trap/mask
    formulas as the runtime handlers, on the known column."""
    a_u = np.asarray(a_col)
    if int(row.d["x"]) == 1:                       # snoop gather
        lane = np.arange(MAX_THREADS) % N_SP
        a_u = a_u[int(row.d["ext_a"]) * N_SP + lane]
    addr = a_u.astype(np.int32) + int(row.d["imm"])
    active = np.asarray(row.active)
    bad = active & ((addr < 0) | (addr >= depth))
    safe = np.clip(addr, 0, depth - 1).astype(np.int32)
    return safe, (active & ~bad), bool(bad.any())


def eval_segment_rows(cfg, rows, const_cols, depth: int):
    """Partially evaluate one fused segment (host, plan time).

    ``const_cols`` is the per-register known-value state entering the
    segment (list of (512,) np.uint32 or None = runtime). Returns
    ``(FusedSegment, const_cols_out)``; the evaluator folds what it can
    and annotates every residual op with the known columns it touches
    that changed since segment entry (``dirty``), so the trace-time
    executor can materialize exactly those as literals.
    """
    from .isa import Op as _Op

    const_cols = list(const_cols)
    dirty: set[int] = set()
    residual = []
    n_folded = 0

    def consts_for(regs):
        return tuple((r, const_cols[r]) for r in sorted(set(regs))
                     if const_cols[r] is not None and r in dirty)

    # every write mask includes ``tid < n_threads`` and registers start
    # zeroed, so lanes >= n_threads stay zero through the whole run — a
    # row whose mask covers ALL of [0, n_threads) therefore fully
    # determines its destination even when the old column is runtime
    # (``_fold_row`` substitutes the invariant zeros for unknown lanes)
    full_mask = np.arange(MAX_THREADS) < cfg.n_threads

    for row in rows:
        sel, d = row.sel, row.d
        rd, ra, rb = int(d["rd"]), int(d["ra"]), int(d["rb"])
        op = int(d["opcode"])
        pen = int(d.get("pen", 0))
        known = [const_cols[r] is not None for r in range(len(const_cols))]
        w_all = known[rd] or np.array_equal(np.asarray(row.active),
                                            full_mask)

        # a predicated row is MAY-WRITE: which lanes commit depends on a
        # runtime register, so it never folds, never becomes a static
        # gather/scatter, and its destination column goes runtime below
        foldable = not pen and (
            (sel == 1 and known[ra] and known[rb] and w_all)
            or (sel == 4 and w_all)
            or (sel == 5 and op in (int(_Op.TDX), int(_Op.TDY))
                and w_all)
            or (sel == 6 and known[ra] and known[rb] and known[rd])
            or (sel == 7 and known[ra] and known[rd])
            or (sel in (10, 11) and known[ra] and known[rb] and w_all))
        if foldable:
            const_cols[rd] = _fold_row(cfg, row, const_cols, depth)
            dirty.add(rd)
            n_folded += 1
            continue

        if sel == 2 and known[ra] and not pen:     # static-address LOD
            safe, mask, bad_any = _fold_addr(row, const_cols[ra], depth)
            residual.append(("lod", row, (safe, mask, bad_any),
                             consts_for((rd,))))
            const_cols[rd] = None
            continue

        if sel == 3 and known[ra] and not pen:     # static-address STO
            safe, do, bad_any = _fold_addr(row, const_cols[ra], depth)
            # single-port arbitration on the host: ascending thread
            # order, last enabled writer per address wins (exactly
            # ``_last_writer_write``'s order=tid rule)
            win: dict[int, int] = {}
            for t in np.flatnonzero(do):           # do-masked ⇒ in range
                win[int(safe[t])] = int(t)
            targets = np.array(sorted(win), np.int32)
            winners = np.array([win[a] for a in sorted(win)], np.int32)
            residual.append(("sto", row, (targets, winners, bad_any),
                             consts_for((rd,))))
            continue

        # generic runtime row (known operands materialize as literals)
        reads = tuple({"ra": ra, "rb": rb, "rd": rd}[f]
                      for f in _ROW_READS[sel])
        if pen:
            reads = reads + (int(d["preg"]),)      # the guard is a read
        residual.append(("exec", row, None, consts_for(reads)))
        if sel != 3:                               # STO writes no register
            const_cols[rd] = None

    final = tuple((r, const_cols[r]) for r in sorted(dirty)
                  if const_cols[r] is not None)
    return (FusedSegment(rows=tuple(rows), residual=tuple(residual),
                         final_consts=final, n_folded=n_folded),
            const_cols)


def apply_segment_residual(cfg, backend: "ExecBackend", seg: FusedSegment,
                           block_idx, prog_idx, regs, shmem, oob, *,
                           shmem_depth: int | None = None):
    """Execute one partially-evaluated segment (trace time).

    Residual ops run over unpacked columns like ``apply_segment_rows``;
    folded columns materialize as literals only where read or at the
    final repack. Valid only under the zero-init wave contract (see the
    module comment above ``FusedSegment``)."""
    n = regs.shape[0]
    cols = [regs[:, :, r] for r in range(regs.shape[2])]
    bid, pid = _sm_column(block_idx), _sm_column(prog_idx)
    # a literal the compiler can see would let it fold a residual FP op
    # on the host, keeping a denormal that the device flushes to zero
    zero = rounding_fence(block_idx)

    def mat(v):
        return jnp.broadcast_to(jnp.asarray(v)[None], (n, MAX_THREADS)) \
            | zero

    for kind, row, data, consts in seg.residual:
        for r, v in consts:
            cols[r] = mat(v)
        if kind == "exec":
            cols, shmem, oob = _apply_row_cols(
                cfg, backend, row, cols, shmem, oob, bid, pid, shmem_depth)
        elif kind == "lod":
            safe, mask, bad_any = data
            rd = int(row.d["rd"])
            vals = jnp.take(shmem, jnp.asarray(safe), axis=1)
            cols[rd] = jnp.where(jnp.asarray(mask), vals, cols[rd])
            if bad_any:
                oob = oob | jnp.bool_(True)
        else:                                      # static-address STO
            targets, winners, bad_any = data
            rd = int(row.d["rd"])
            if len(targets):
                shmem = shmem.at[:, jnp.asarray(targets)].set(
                    cols[rd][:, winners], unique_indices=True,
                    indices_are_sorted=True)
            if bad_any:
                oob = oob | jnp.bool_(True)
    for r, v in seg.final_consts:
        cols[r] = mat(v)
    return jnp.stack(cols, axis=2), shmem, oob


def exec_segment(backend: "ExecBackend", cfg, seg, block_idx, prog_idx,
                 regs, shmem, oob, *, shmem_depth: int | None = None):
    """Run one fused segment on ``backend``: its own fused implementation
    when it ships one, else the partially-evaluated residual on
    fold-capable (reference-semantics) backends, else the generic
    unrolled chain over the backend's per-op seam (so ALU-only custom
    backends keep their ALU semantics under the megakernel engine).

    ``seg`` is a ``FusedSegment``; a raw row tuple is accepted for the
    generic paths (no residual available)."""
    rows = seg.rows if isinstance(seg, FusedSegment) else tuple(seg)
    if backend.segment is not None:
        return backend.segment(cfg, rows, block_idx, prog_idx, regs,
                               shmem, oob, shmem_depth=shmem_depth)
    if backend.fold_constants and isinstance(seg, FusedSegment):
        return apply_segment_residual(cfg, backend, seg, block_idx,
                                      prog_idx, regs, shmem, oob,
                                      shmem_depth=shmem_depth)
    return apply_segment_rows(cfg, backend, rows, block_idx, prog_idx,
                              regs, shmem, oob, shmem_depth=shmem_depth)


def _pallas_segment(cfg, rows, block_idx, prog_idx, regs, shmem, oob, *,
                    shmem_depth: int | None = None):
    """Pallas fused segment: ONE kernel per segment, registers/shmem
    resident in VMEM across every fused step (no per-instruction
    round-trip)."""
    from ..kernels import ops
    from ..kernels.simt_step import simt_segment

    return simt_segment(cfg, rows, block_idx, prog_idx, regs, shmem, oob,
                        shmem_depth=shmem_depth,
                        interpret=ops.interpret_mode())


def _last_writer_write(mem, addr, vals, do, order):
    """Serialized single-port store: among enabled writers to the same
    address, the one latest in ``order`` wins (thread order within an SM;
    (sm, thread)-major order device-wide for global memory). Implemented
    with a commutative scatter-max so it is deterministic under jit."""
    depth = mem.shape[0]
    slot = jnp.where(do, addr, depth)                    # park masked writes
    winner = jnp.full((depth + 1,), -1, _I32).at[slot].max(order)
    write = do & (winner[slot] == order)
    return mem.at[jnp.where(write, addr, depth)].set(vals, mode="drop")


def _inline_alu(op, typ, a, b, mask, old) -> jax.Array:
    """Straight-jnp ALU stage (the ``kernels.ref`` oracle)."""
    from ..kernels.ref import alu_ref

    return jnp.where(mask, alu_ref(op, typ, a, b), old)


def _inline_lod(shmem, addr, mask, old) -> jax.Array:
    return jnp.where(mask, jnp.take_along_axis(shmem, addr, axis=1), old)


def _inline_sto(shmem, addr, vals, do) -> jax.Array:
    tid = jnp.arange(addr.shape[1], dtype=_I32)
    return jax.vmap(_last_writer_write, in_axes=(0, 0, 0, 0, None))(
        shmem, addr, vals, do, tid)


def _inline_gld(gmem, addr, mask, old) -> jax.Array:
    return jnp.where(mask, gmem[addr], old)


def _inline_gst(gmem, addr, vals, do) -> jax.Array:
    order = jnp.arange(addr.size, dtype=_I32)
    return _last_writer_write(gmem, addr.reshape(-1), vals.reshape(-1),
                              do.reshape(-1), order)


@dataclasses.dataclass(frozen=True)
class ExecBackend:
    """One named implementation of the execute-stage data path.

    ``segment`` is the fused-segment entry point the megakernel engine
    drives (via ``exec_segment``): a whole run of SM-local rows executed
    as one unit (``(cfg, rows, block_idx, prog_idx, regs, shmem, oob, *,
    shmem_depth) -> (regs, shmem, oob)``). None (the default) means the
    generic unrolled chain ``apply_segment_rows`` over this backend's
    own per-op seam; the Pallas backend overrides it with a single fused
    ``pallas_call`` staging the SAME chain, so fused execution is
    bit-identical across backends by construction.
    """

    name: str
    alu: ExecuteOp = _inline_alu
    lod: ExecuteOp = _inline_lod
    sto: ExecuteOp = _inline_sto
    gld: ExecuteOp = _inline_gld
    gst: ExecuteOp = _inline_gst
    segment: Callable | None = None
    # reference-semantics backends opt in to the megakernel's plan-time
    # partial evaluation (folded rows never reach the per-op seam, so a
    # backend that needs to SEE every op must leave this False)
    fold_constants: bool = False


_EXECUTE_BACKENDS: dict[str, ExecBackend] = {}


def register_backend(backend: ExecBackend) -> ExecBackend:
    _EXECUTE_BACKENDS[backend.name] = backend
    return backend


def register_execute_backend(name: str):
    """Back-compat decorator: register an ALU-only backend; the memory
    ops inherit the inline jnp implementations."""
    def deco(fn: ExecuteOp) -> ExecuteOp:
        register_backend(ExecBackend(name=name, alu=fn))
        return fn
    return deco


def get_execute_backend(name: str) -> ExecBackend:
    try:
        return _EXECUTE_BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown execute backend {name!r}; "
            f"available: {sorted(_EXECUTE_BACKENDS)}") from None


def execute_backends() -> tuple[str, ...]:
    return tuple(sorted(_EXECUTE_BACKENDS))


register_backend(ExecBackend(name="inline", fold_constants=True))


def _pallas_alu(op, typ, a, b, mask, old) -> jax.Array:
    """Pallas ALU stage: one ``simt_alu`` grid over the SM batch."""
    from ..kernels import ops
    from ..kernels.simt_alu import simt_alu

    n_sm = a.shape[0]
    # 8-SM tiles (80 KiB VMEM) where they divide the batch, else the whole
    # batch in one block (a TPU block tiles 8 sublanes or spans the array)
    block_sm = 8 if n_sm % 8 == 0 else n_sm
    return simt_alu(op.astype(_I32), typ.astype(_I32), a, b,
                    mask.astype(_U32), old,
                    interpret=ops.interpret_mode(), block_sm=block_sm)


def _pallas_lod(shmem, addr, mask, old) -> jax.Array:
    from ..kernels import ops
    from ..kernels.simt_step import simt_gather

    return simt_gather(shmem, addr, mask.astype(_U32), old,
                       interpret=ops.interpret_mode())


def _pallas_sto(shmem, addr, vals, do) -> jax.Array:
    from ..kernels import ops
    from ..kernels.simt_step import simt_scatter

    return simt_scatter(shmem, addr, vals, do.astype(_U32),
                        interpret=ops.interpret_mode())


def _pallas_gld(gmem, addr, mask, old) -> jax.Array:
    from ..kernels import ops
    from ..kernels.simt_step import simt_gather_shared

    return simt_gather_shared(gmem, addr, mask.astype(_U32), old,
                              interpret=ops.interpret_mode())


def _pallas_gst(gmem, addr, vals, do) -> jax.Array:
    from ..kernels import ops
    from ..kernels.simt_step import simt_scatter_shared

    return simt_scatter_shared(gmem, addr, vals, do.astype(_U32),
                               interpret=ops.interpret_mode())


register_backend(ExecBackend(
    name="pallas", alu=_pallas_alu, lod=_pallas_lod, sto=_pallas_sto,
    gld=_pallas_gld, gst=_pallas_gst, segment=_pallas_segment))


# ---------------------------------------------------------------------------
# the shared execute stage (step + trace engines dispatch into these
# handlers; the megakernel's fused rows replay the same semantics)
# ---------------------------------------------------------------------------
#
# The data path of one instruction over a lockstep SM batch, factored out
# of the stepping machine so the trace engine executes the IDENTICAL
# handler graph: ``device._device_step`` (decode-per-step) and
# ``trace_engine`` (decode-once ``lax.scan``) both build their dispatch
# from ``make_data_handlers``. Handler order is fixed; ``DATA_SEL_OF_GROUP``
# maps a handler group to its 1-based switch branch (0 = no data effect:
# NOP and control, whose sequencer effects the engines handle themselves).

# handler-group -> data-switch branch (0 = identity)
DATA_SEL_OF_GROUP = np.zeros((13,), np.int32)
for _g, _sel in {_G_ALU: 1, _G_LOD: 2, _G_STO: 3, _G_LODI: 4, _G_TD: 5,
                 _G_RED: 6, _G_SFU: 7, _G_GLD: 8, _G_GST: 9,
                 _G_SETP: 10, _G_SELP: 11}.items():
    DATA_SEL_OF_GROUP[_g] = _sel

# opcode -> data-switch branch
DATA_SEL_OF_OP = DATA_SEL_OF_GROUP[_GROUP_OF_OP]


def make_data_handlers(cfg, backend: ExecBackend, d: dict,
                       active: jax.Array, block_idx: jax.Array,
                       prog_idx: jax.Array, *,
                       shmem_depth: int | None = None):
    """Build the 12-way data-path switch body for one decoded instruction.

    ``d`` holds the decoded fields as traced i32 scalars (the dict from
    ``_decode`` or one step of the trace engine's pre-decoded schedule);
    ``active`` is the (512,) flexible-ISA thread mask, shared by the whole
    SM batch — every engine dispatches on lockstep batches of one program
    (the trace engine's merged heterogeneous waves slice each program's
    contiguous SM sub-batch before dispatching here). Returns a list of
    handlers over the data-state tuple ``(regs, shmem, gmem, oob)`` —
    index it with ``DATA_SEL_OF_GROUP[group]`` (branch 0 is the identity
    for NOP/control). Sequencer state (pc, stacks, halt) is each engine's
    own business.

    ``shmem_depth`` bounds LOD/STO addressing; it defaults to the shared-
    memory array's own depth and only differs in merged heterogeneous
    waves, where programs with a shallower ``Kernel(shmem_depth=)``
    override share one device-depth batch: accesses in
    ``[shmem_depth, array depth)`` still trap/drop exactly as they do when
    the program runs alone on a ``shmem_depth``-deep SM.
    """

    tid = jnp.arange(MAX_THREADS, dtype=_I32)
    lane = tid % N_SP

    snoop = d["x"] == 1
    ra_tid = jnp.where(snoop, d["ext_a"] * N_SP + lane, tid)
    rb_tid = jnp.where(snoop, d["ext_b"] * N_SP + lane, tid)
    op, typ = d["opcode"], d["typ"]
    is_fp = typ == int(isa.Typ.FP32)

    # SIMT predication. ``pgate`` is the predicate gate alone — all-true
    # on legacy PEN=0 words (the fields are traced scalars here, so the
    # gate is computed either way; the megakernel's host-constant rows
    # skip it entirely). ``eff`` replaces the flexible-ISA mask in every
    # write/port mask below: predicated-off lanes write no register/
    # shmem/gmem state and generate no port transaction (no trap, no
    # store, no last-writer slot). Cycle accounting is untouched —
    # masked lanes still occupy their issue/drain slots as bubbles, so
    # the static traces (and with them scheduler/packing/fleet pricing)
    # stay exact.
    def pgate(regs):
        p = (jnp.take(regs, d["preg"], axis=2) & 1) != 0   # (n_sms, 512)
        p = jnp.where(d["pneg"] == 1, ~p, p)
        return jnp.where(d["pen"] == 1, p, True)

    def eff(regs):
        return active[None] & pgate(regs)

    def col(regs, rd):
        return jnp.take(regs, rd, axis=2)     # (n_sms, 512)

    def set_col(regs, rd, vals):
        return regs.at[:, :, rd].set(vals)

    def write_active(regs, rd, vals, mask):
        return set_col(regs, rd, jnp.where(mask, vals, col(regs, rd)))

    def operands(regs):
        a_u = regs[:, ra_tid, d["ra"]]        # (n_sms, 512)
        b_u = regs[:, rb_tid, d["rb"]]
        return a_u, b_u

    def addr_of(regs):
        a_u, _ = operands(regs)
        return jax.lax.bitcast_convert_type(a_u, _I32) + d["imm"]

    def h_identity(s):
        return s

    def h_alu(s):
        regs, shmem, gmem, oob = s
        a_u, b_u = operands(regs)
        old = col(regs, d["rd"])
        mask = eff(regs)
        res = backend.alu(op, typ, a_u, b_u, mask, old)
        return set_col(regs, d["rd"], res), shmem, gmem, oob

    def h_lod(s):
        regs, shmem, gmem, oob = s
        depth = shmem_depth if shmem_depth is not None else shmem.shape[1]
        m = eff(regs)
        addr = addr_of(regs)
        bad = m & ((addr < 0) | (addr >= depth))
        safe = jnp.clip(addr, 0, depth - 1)
        old = col(regs, d["rd"])
        mask = m & ~bad
        vals = backend.lod(shmem, safe, mask, old)
        return (set_col(regs, d["rd"], vals), shmem, gmem,
                oob | bad.any(axis=1))

    def h_sto(s):
        regs, shmem, gmem, oob = s
        depth = shmem_depth if shmem_depth is not None else shmem.shape[1]
        m = eff(regs)
        addr = addr_of(regs)
        bad = m & ((addr < 0) | (addr >= depth))
        vals = col(regs, d["rd"])
        shmem = backend.sto(shmem, addr, vals, m & ~bad)
        return regs, shmem, gmem, oob | bad.any(axis=1)

    def h_lodi(s):
        regs, shmem, gmem, oob = s
        as_f = jax.lax.bitcast_convert_type(d["imm"].astype(_F32), _U32)
        val = jnp.where(is_fp, as_f, d["imm"].astype(_U32))
        vals = jnp.broadcast_to(val, (regs.shape[0], MAX_THREADS))
        return (write_active(regs, d["rd"], vals, eff(regs)),
                shmem, gmem, oob)

    def h_td(s):
        regs, shmem, gmem, oob = s
        n_sms = regs.shape[0]
        x = (tid % cfg.dim_x).astype(_U32)[None]            # (1, 512)
        y = (tid // cfg.dim_x).astype(_U32)[None]
        bid = jnp.broadcast_to(block_idx.astype(_U32)[:, None],
                               (n_sms, MAX_THREADS))
        pid = jnp.broadcast_to(prog_idx.astype(_U32)[:, None],
                               (n_sms, MAX_THREADS))
        vals = jnp.where(op == int(Op.TDX), x,
                         jnp.where(op == int(Op.TDY), y,
                                   jnp.where(op == int(Op.BID), bid, pid)))
        return (write_active(regs, d["rd"], vals, eff(regs)),
                shmem, gmem, oob)

    def h_red(s):
        # DOT/SUM: reduce each active wavefront across its active lanes,
        # write the result to lane 0 of that wavefront (the first SP).
        # Predicated-off lanes contribute nothing and a wavefront with no
        # enabled lane keeps its old lane-0 value.
        regs, shmem, gmem, oob = s
        a_u, b_u = operands(regs)
        lane_eff = eff(regs)
        cur = col(regs, d["rd"])
        prod = _wave_terms(op == int(Op.DOT), a_u, b_u,
                           rounding_fence(block_idx))
        new = _wave_reduce(XlaLanes, prod, lane_eff, cur)
        return set_col(regs, d["rd"], new), shmem, gmem, oob

    def h_sfu(s):
        # single-lane SFU: 1/sqrt of wavefront-0 lane-0 (snoopable source);
        # the issuing thread-0 predicate gates the write
        regs, shmem, gmem, oob = s
        src_tid = jnp.where(snoop, d["ext_a"] * N_SP, 0)
        r = sfu_rsqrt(regs[:, src_tid, d["ra"]],            # (n_sms,)
                      rounding_fence(block_idx)[:, 0])
        new = jnp.where(pgate(regs)[:, 0], r, regs[:, 0, d["rd"]])
        return regs.at[:, 0, d["rd"]].set(new), shmem, gmem, oob

    def h_gld(s):
        regs, shmem, gmem, oob = s
        gdepth = gmem.shape[0]
        m = eff(regs)
        addr = addr_of(regs)
        bad = m & ((addr < 0) | (addr >= gdepth))
        safe = jnp.clip(addr, 0, gdepth - 1)
        old = col(regs, d["rd"])
        mask = m & ~bad
        vals = backend.gld(gmem, safe, mask, old)
        return (set_col(regs, d["rd"], vals), shmem, gmem,
                oob | bad.any(axis=1))

    def h_gst(s):
        regs, shmem, gmem, oob = s
        gdepth = gmem.shape[0]
        m = eff(regs)
        addr = addr_of(regs)
        bad = m & ((addr < 0) | (addr >= gdepth))
        vals = col(regs, d["rd"])
        # the single device-wide port drains in (sm, thread) order
        gmem = backend.gst(gmem, addr, vals, m & ~bad)
        return regs, shmem, gmem, oob | bad.any(axis=1)

    def h_setp(s):
        regs, shmem, gmem, oob = s
        a_u, b_u = operands(regs)
        res = _setp_compare(d["imm"], typ, a_u, b_u)
        return (write_active(regs, d["rd"], res.astype(_U32), eff(regs)),
                shmem, gmem, oob)

    def h_selp(s):
        # Rd = P ? Ra : Rb — the @-guard is the SELECTOR here, not a
        # write gate: SELP writes on every active lane (PEN=0 selects Ra)
        regs, shmem, gmem, oob = s
        a_u, b_u = operands(regs)
        vals = jnp.where(pgate(regs), a_u, b_u)
        return (write_active(regs, d["rd"], vals,
                             jnp.broadcast_to(active, vals.shape)),
                shmem, gmem, oob)

    return [h_identity, h_alu, h_lod, h_sto, h_lodi, h_td, h_red, h_sfu,
            h_gld, h_gst, h_setp, h_selp]


# ---------------------------------------------------------------------------
# public entry points (single-wave shims over the device layer)
# ---------------------------------------------------------------------------

def run(cfg: SMConfig, program, shmem: np.ndarray | None = None,
        state: MachineState | None = None, *,
        backend: str = "inline") -> MachineState:
    """Assemble-and-run convenience wrapper: ONE SM, one thread block.

    ``program`` is a Program or an ndarray of encoded 40-bit words.
    Implemented as a single-block wave on the device layer; use
    ``device.launch`` for grids, global memory, and multi-SM runs.
    """
    from . import device

    words = program.words if hasattr(program, "words") else np.asarray(program)
    lo, hi = pack_imem(words, cfg.imem_depth)
    if state is None:
        dstate = device.init_device_state(cfg, n_sms=1, shmem=shmem)
    else:
        dstate = device.lift_machine_state(state)
    fin = device.run_wave(cfg, backend, jnp.asarray(lo), jnp.asarray(hi),
                          jnp.zeros((1,), _I32), jnp.zeros((1,), _I32),
                          dstate)
    return device.squeeze_device_state(fin)


def run_many(cfg: SMConfig, program, shmem_batch: np.ndarray, *,
             backend: str = "inline") -> MachineState:
    """Multi-SM execution: one eGPU instance per shared-memory image (the
    quad-packed sector of §III.E, generalized to N instances).

    Backward-compatibility shim over ``device.launch``: every instance runs
    the same program as one device wave, and the returned ``MachineState``
    carries a leading batch axis on every field (the historical vmapped
    layout). New code should call ``device.launch`` directly.
    """
    from . import device

    shmem_batch = jnp.asarray(shmem_batch)
    n_sms = int(shmem_batch.shape[0])
    words = program.words if hasattr(program, "words") else np.asarray(program)
    lo, hi = pack_imem(words, cfg.imem_depth)
    dstate = device.init_device_state(cfg, n_sms=n_sms, shmem=shmem_batch)
    fin = device.run_wave(cfg, backend, jnp.asarray(lo), jnp.asarray(hi),
                          jnp.arange(n_sms, dtype=_I32),
                          jnp.zeros((n_sms,), _I32), dstate)
    # historical layout: every field vmapped over the SM batch
    b = lambda x: jnp.broadcast_to(x, (n_sms,) + x.shape)
    return MachineState(
        regs=fin.regs, shmem=fin.shmem,
        pc=b(fin.pc), ret_stack=b(fin.ret_stack), ret_sp=b(fin.ret_sp),
        loop_ctr=b(fin.loop_ctr), loop_sp=b(fin.loop_sp),
        halted=b(fin.halted), oob=fin.oob,
        steps=b(fin.steps), cycles=b(fin.cycles),
        cycles_by_class=b(fin.cycles_by_class),
    )
