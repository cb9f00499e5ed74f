"""Pallas TPU kernels: the eGPU execute stage beyond the ALU.

``simt_alu`` (in ``simt_alu.py``) covers the SP array's arithmetic path;
this module extends the Pallas backend seam over the *memory* half of the
execute stage, so a trace-engine or step-machine instruction runs its
whole data path through Pallas:

  * ``simt_gather``         — LOD: the quad-read-port shared-memory gather,
    one SM's ``(depth,)`` image indexed by its 512 lanes;
  * ``simt_scatter``        — STO: the single-write-port scatter; writeback
    is sequential in thread order, so the LAST active thread wins on
    address collisions;
  * ``simt_gather_shared``  — GLD: every SM's lanes gather from the ONE
    device-wide global-memory segment;
  * ``simt_scatter_shared`` — GST: the single device-wide port drains in
    (sm, thread) order; last (sm, thread) writer wins;
  * ``simt_segment``        — a megakernel's fused run of SM-local rows in
    one kernel, registers and shared memory resident in VMEM.

TPU adaptation: Mosaic has no lane gather over thousands of words and no
scatter at all, so memory is read and written a chunk of ``CHUNK`` words
at a time through one-hot matrices on the MXU. A word is split into four
bytes, each exact in bfloat16, and the matmul against a 0/1 matrix with
at most one 1 per output sums one byte and zeros, which float32
accumulates exactly:

  * gather: ``bytes(mem chunk) @ onehot[d, t]`` with
    ``onehot[d, t] = (addr[t] == d)``;
  * scatter: ``onehot[d, t]`` keeps only the last enabled writer of each
    word ``d`` (a lane max over writer order), and
    ``bytes(vals) @ onehot^T`` gives the new words; a row of ones in the
    left operand marks which words were written at all.

Memory tiles are ``(rows, CHUNK)`` so every chunk is a row, read and
written at a dynamic sublane index. Like the ALU kernel these run under
the Pallas interpreter on the CPU (the tests) and compile on a TPU, where
the inline jnp backend must match them bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import alu_ref
from .simt_alu import N_THREADS

_I32 = jnp.int32
_U32 = jnp.uint32
_F32 = jnp.float32
_BF16 = jnp.bfloat16

# memory words per one-hot tile: (CHUNK, lanes) one-hot matrices, a row of
# the (rows, CHUNK) memory tile per step
CHUNK = 256
_N_SP = 16


def _rows(n_words: int) -> int:
    return -(-n_words // CHUNK)


def _to_rows(mem: jax.Array) -> jax.Array:
    """(..., depth) words -> (..., rows, CHUNK) int32, zero-padded."""
    depth = mem.shape[-1]
    pad = _rows(depth) * CHUNK - depth
    mem = jax.lax.bitcast_convert_type(mem.astype(_U32), _I32)
    mem = jnp.pad(mem, [(0, 0)] * (mem.ndim - 1) + [(0, pad)])
    return mem.reshape(mem.shape[:-1] + (_rows(depth), CHUNK))


def _from_rows(mem: jax.Array, depth: int) -> jax.Array:
    flat = mem.reshape(mem.shape[:-2] + (-1,))[..., :depth]
    return jax.lax.bitcast_convert_type(flat, _U32)


def _byte_rows(words: jax.Array, ones_row: bool = False) -> jax.Array:
    """(1, W) int32 words -> (8, W) bfloat16: rows 0-3 hold the bytes
    (exact in bfloat16), row 4 holds ones when ``ones_row``, the rest 0."""
    w = words.shape[1]
    sub = jax.lax.broadcasted_iota(_I32, (8, w), 0)
    b = jax.lax.shift_right_logical(jnp.broadcast_to(words, (8, w)),
                                    8 * (sub % 4)) & 0xFF
    b = jnp.where(sub < 4, b, 1 if ones_row else 0)
    if ones_row:
        b = jnp.where(sub > 4, 0, b)
    return b.astype(_F32).astype(_BF16)


def _join_bytes(acc: jax.Array) -> jax.Array:
    """(8, W) float32 byte sums -> (1, W) int32 words (rows 0-3)."""
    v = acc.astype(_I32)
    return (v[0:1] | (v[1:2] << 8) | (v[2:3] << 16) | (v[3:4] << 24))


def gather_words(read_row, n_rows: int, addr: jax.Array) -> jax.Array:
    """``out[t] = mem[addr[t]]`` for (1, W) int32 lane addresses in range.

    ``read_row(c)`` returns row ``c`` of the memory tile, (1, CHUNK)
    int32."""
    w = addr.shape[1]
    word = jax.lax.broadcasted_iota(_I32, (CHUNK, w), 0)

    def body(c, acc):
        onehot = jnp.where(addr == word + c * CHUNK, 1.0, 0.0).astype(_BF16)
        return acc + jnp.dot(_byte_rows(read_row(c)), onehot,
                             preferred_element_type=_F32)

    acc = jax.lax.fori_loop(0, n_rows, body, jnp.zeros((8, w), _F32))
    return _join_bytes(acc)


def scatter_words(read_row, write_row, n_rows: int, addr: jax.Array,
                  vals: jax.Array, do: jax.Array) -> None:
    """Serialized single-port store over (1, W) lanes: among enabled
    lanes writing one word, the highest lane wins (lane order is writer
    order). ``read_row(c)``/``write_row(c, row)`` access row ``c`` of the
    memory tile; enabled addresses must be in range."""
    w = addr.shape[1]
    word = jax.lax.broadcasted_iota(_I32, (CHUNK, w), 0)
    lane = jax.lax.broadcasted_iota(_I32, (CHUNK, w), 1)
    lhs = _byte_rows(vals, ones_row=True)

    def body(c, carry):
        hit = do & (addr == word + c * CHUNK)                 # (CHUNK, W)
        last = jnp.max(jnp.where(hit, lane, -1), axis=1, keepdims=True)
        win = jnp.where(hit & (lane == last), 1.0, 0.0).astype(_BF16)
        acc = jax.lax.dot_general(lhs, win, (((1,), (1,)), ((), ())),
                                  preferred_element_type=_F32)  # (8, CHUNK)
        write_row(c, jnp.where(acc[4:5] > 0.5, _join_bytes(acc),
                               read_row(c)))
        return carry

    jax.lax.fori_loop(0, n_rows, body, 0)


def _lane_tile(x: jax.Array) -> jax.Array:
    """(n_sm, 512) -> (n_sm, 1, 512) int32: one legal (1, 512) block per
    SM (a block's last two dims must tile (8, 128) or span the array)."""
    return jax.lax.bitcast_convert_type(x.astype(_U32), _I32)[:, None, :]


# ---------------------------------------------------------------------------
# LOD / STO: per-SM shared memory
# ---------------------------------------------------------------------------

def _gather_kernel(mem_ref, addr_ref, mask_ref, old_ref, out_ref):
    def read_row(c):
        return mem_ref[pl.ds(c, 1), :]

    vals = gather_words(read_row, mem_ref.shape[0], addr_ref[...])
    out_ref[...] = jnp.where(mask_ref[...] != 0, vals, old_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def simt_gather(mem: jax.Array, addr: jax.Array, mask: jax.Array,
                old: jax.Array, *, interpret: bool) -> jax.Array:
    """LOD gather: ``out[s, t] = mem[s, addr[s, t]]`` where masked.

    ``mem`` is the (n_sm, depth) shared-memory batch, ``addr`` pre-clipped
    lane addresses, ``old`` the destination column inactive lanes keep.
    One SM per grid step: its 3K-word image is 12 KiB of VMEM.
    """
    n_sm, depth = mem.shape
    rows = _rows(depth)
    lane_spec = pl.BlockSpec((None, 1, N_THREADS), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        _gather_kernel,
        out_shape=jax.ShapeDtypeStruct((n_sm, 1, N_THREADS), _I32),
        grid=(n_sm,),
        in_specs=[pl.BlockSpec((None, rows, CHUNK), lambda i: (i, 0, 0)),
                  lane_spec, lane_spec, lane_spec],
        out_specs=lane_spec,
        interpret=interpret,
    )(_to_rows(mem), _lane_tile(addr), _lane_tile(mask), _lane_tile(old))
    return jax.lax.bitcast_convert_type(out[:, 0, :], _U32)


def _scatter_kernel(mem_ref, addr_ref, vals_ref, do_ref, out_ref):
    def write_row(c, row):
        out_ref[pl.ds(c, 1), :] = row

    scatter_words(lambda c: mem_ref[pl.ds(c, 1), :], write_row,
                  mem_ref.shape[0], addr_ref[...], vals_ref[...],
                  do_ref[...] != 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def simt_scatter(mem: jax.Array, addr: jax.Array, vals: jax.Array,
                 do: jax.Array, *, interpret: bool) -> jax.Array:
    """STO scatter: serialized single-port writeback in thread order.

    Among enabled writers to one address the highest thread wins; masked
    and out-of-range lanes write nothing (the caller pre-masks ``do``).
    """
    n_sm, depth = mem.shape
    rows = _rows(depth)
    lane_spec = pl.BlockSpec((None, 1, N_THREADS), lambda i: (i, 0, 0))
    mem_spec = pl.BlockSpec((None, rows, CHUNK), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        _scatter_kernel,
        out_shape=jax.ShapeDtypeStruct((n_sm, rows, CHUNK), _I32),
        grid=(n_sm,),
        in_specs=[mem_spec, lane_spec, lane_spec, lane_spec],
        out_specs=mem_spec,
        interpret=interpret,
    )(_to_rows(mem), _lane_tile(addr), _lane_tile(vals), _lane_tile(do))
    return _from_rows(out, depth)


# ---------------------------------------------------------------------------
# GLD/GST: the device-wide global-memory port
# ---------------------------------------------------------------------------
#
# The SM batch's lanes are flattened to one (1, n_sm * 512) row in
# (sm, thread) order, which is also the port's drain order; the kernel
# bodies are LOD's and STO's, over the whole batch in one block.

def _flat_lanes(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x.astype(_U32), _I32).reshape(1, -1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def simt_gather_shared(mem: jax.Array, addr: jax.Array, mask: jax.Array,
                       old: jax.Array, *, interpret: bool) -> jax.Array:
    """GLD gather: every SM's lanes read the one global segment."""
    n_sm = addr.shape[0]
    out = pl.pallas_call(
        _gather_kernel,
        out_shape=jax.ShapeDtypeStruct((1, n_sm * N_THREADS), _I32),
        interpret=interpret,
    )(_to_rows(mem), _flat_lanes(addr), _flat_lanes(mask), _flat_lanes(old))
    return jax.lax.bitcast_convert_type(out, _U32).reshape(n_sm, N_THREADS)


@functools.partial(jax.jit, static_argnames=("interpret",))
def simt_scatter_shared(mem: jax.Array, addr: jax.Array, vals: jax.Array,
                        do: jax.Array, *, interpret: bool) -> jax.Array:
    """GST scatter: one port for the whole sector, (sm, thread) order."""
    (gdepth,) = mem.shape
    rows = _rows(gdepth)
    out = pl.pallas_call(
        _scatter_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, CHUNK), _I32),
        interpret=interpret,
    )(_to_rows(mem), _flat_lanes(addr), _flat_lanes(vals), _flat_lanes(do))
    return _from_rows(out, gdepth)


# ---------------------------------------------------------------------------
# fused segment: a whole run of SM-local instructions in ONE kernel
# ---------------------------------------------------------------------------

def _roll(x, shift: int):
    """``jnp.roll`` along lanes: ``out[t] = x[t - shift]``."""
    shift %= x.shape[1]
    return x if shift == 0 else pltpu.roll(x, shift, 1)


class KernelLanes:
    """``executor.XlaLanes`` for a kernel body: the same values, in the
    same order, from lane rotations Mosaic lowers (no gathers, no
    reshapes across lanes). Tiles are one SM's (1, 512) lanes."""

    @staticmethod
    def snoop(col, ext: int):
        # keep wavefront ``ext``, then copy it into every wavefront:
        # after rotations by 16, 32, ..., 256 each wave holds one copy
        ci = jax.lax.bitcast_convert_type(col, _I32)
        wave = jax.lax.broadcasted_iota(_I32, ci.shape, 1) // _N_SP
        x = jnp.where(wave == ext, ci, 0)
        for s in (16, 32, 64, 128, 256):
            x = x | _roll(x, s)
        return jax.lax.bitcast_convert_type(x, col.dtype)

    @staticmethod
    def trap(oob, bad):
        # per-lane here; the kernel folds lanes to one flag at the end
        return oob | bad

    @staticmethod
    def wave_sum(x):
        # lane 16w accumulates x[16w], x[16w+1], ... in order from 0.0
        acc = jnp.zeros_like(x) + x
        for j in range(1, _N_SP):
            acc = acc + _roll(x, -j)
        return acc

    @staticmethod
    def wave_any(m):
        x = m.astype(_I32)
        for s in (1, 2, 4, 8):
            x = x | _roll(x, -s)
        return x != 0

    @staticmethod
    def lane0(col, src: int):
        return _roll(col, -src)


def simt_segment(cfg, rows, block_idx, prog_idx, regs, shmem, oob, *,
                 shmem_depth: int | None = None,
                 interpret: bool):
    """Megakernel fused segment: unroll ``rows`` body-to-body inside one
    ``pallas_call``, keeping the SM's registers, shared memory and OOB
    flag resident across every fused step instead of round-tripping
    through HBM per instruction.

    ``rows`` is the host-constant ``executor.FusedRow`` tuple of one
    segment (SM-local ops only — the global port delimits segments). The
    kernel body runs the SAME ``executor._apply_row_cols`` row semantics
    the inline backend runs, with ``KernelLanes`` for the cross-lane
    steps and the one-hot ``gather_words``/``scatter_words`` for LOD/STO,
    over the one-SM block each grid step owns: 16 (1, 512) register rows
    (32 KiB) and the SM's (rows, CHUNK) shared-memory tile.

    Not jitted here: ``rows`` is unhashable by design (numpy masks), and
    every caller is already inside the megakernel runner's jit.
    """
    from ..core.executor import ExecBackend, _apply_row_cols

    n_sm, depth = shmem.shape
    n_regs = regs.shape[2]
    n_rows = _rows(depth)
    bound = depth if shmem_depth is None else shmem_depth
    lanes = KernelLanes()

    def lod(mem_ref, addr, mask, old):
        vals = gather_words(lambda c: mem_ref[pl.ds(c, 1), :], n_rows, addr)
        return jnp.where(mask, jax.lax.bitcast_convert_type(vals, _U32), old)

    def sto(mem_ref, addr, vals, do):
        def write_row(c, row):
            mem_ref[pl.ds(c, 1), :] = row

        scatter_words(lambda c: mem_ref[pl.ds(c, 1), :], write_row, n_rows,
                      addr, jax.lax.bitcast_convert_type(vals, _I32), do)
        return mem_ref

    backend = ExecBackend(
        name="pallas-segment", lod=lod, sto=sto,
        alu=lambda op, typ, a, b, mask, old: jnp.where(
            mask, alu_ref(op, typ, a, b), old))

    def kernel(bidx_ref, pidx_ref, regs_ref, sh_ref, regs_out, sh_out,
               oob_out):
        i = pl.program_id(0)
        sh_out[...] = sh_ref[...]
        cols = [regs_ref[r:r + 1, :] for r in range(n_regs)]
        bid = jax.lax.bitcast_convert_type(
            jnp.full((1, 1), bidx_ref[i], _I32), _U32)
        pid = jax.lax.bitcast_convert_type(
            jnp.full((1, 1), pidx_ref[i], _I32), _U32)
        trap = jnp.zeros((1, N_THREADS), jnp.bool_)
        for row in rows:
            cols, _, trap = _apply_row_cols(cfg, backend, row, cols, sh_out,
                                            trap, bid, pid, bound,
                                            lanes=lanes)
        for r in range(n_regs):
            regs_out[r:r + 1, :] = cols[r]
        oob_out[...] = trap.astype(_I32)

    regs_spec = pl.BlockSpec((None, n_regs, N_THREADS),
                             lambda i, b, p: (i, 0, 0))
    mem_spec = pl.BlockSpec((None, n_rows, CHUNK), lambda i, b, p: (i, 0, 0))
    oob_spec = pl.BlockSpec((None, 1, N_THREADS), lambda i, b, p: (i, 0, 0))
    regs_o, sh_o, trap_o = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((n_sm, n_regs, N_THREADS), _U32),
                   jax.ShapeDtypeStruct((n_sm, n_rows, CHUNK), _I32),
                   jax.ShapeDtypeStruct((n_sm, 1, N_THREADS), _I32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_sm,),
            in_specs=[regs_spec, mem_spec],
            out_specs=(regs_spec, mem_spec, oob_spec)),
        interpret=interpret,
    )(block_idx.astype(_I32), prog_idx.astype(_I32),
      jnp.swapaxes(regs, 1, 2), _to_rows(shmem))
    return (jnp.swapaxes(regs_o, 1, 2), _from_rows(sh_o, depth),
            oob | (trap_o != 0).any(axis=(1, 2)))
