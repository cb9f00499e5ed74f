"""Pallas TPU kernel: the eGPU SIMT ALU, one instruction across N SMs.

TPU adaptation of the SP array (paper Fig. 2): a wavefront-parallel ALU
operating on gathered register operands. On the FPGA, 16 SPs execute one
wavefront per cycle out of M20K register files; on TPU the natural analogue
is a VMEM-resident lane vector — we batch THREADS x SMS into (sm, 512)
tiles (512 = 4 x 128 lanes, hardware-aligned) and execute the decoded op on
the VPU, with the flexible-ISA thread mask applied in-kernel.

Operands arrive pre-gathered (register-file column reads are a gather the
XLA scatter/gather units handle better than a Pallas minor-dim dynamic
index); the kernel is the execute stage. The decoded ``op``/``typ`` ride
in SMEM as scalar-prefetch operands, and the body is ``ref.alu_ref`` —
the same formulas as the inline backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import alu_ref

N_THREADS = 512


def _alu_kernel(opv_ref, a_ref, b_ref, mask_ref, old_ref, out_ref):
    res = alu_ref(opv_ref[0], opv_ref[1], a_ref[...], b_ref[...])
    out_ref[...] = jnp.where(mask_ref[...] != 0, res, old_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret", "block_sm"))
def simt_alu(op: jax.Array, typ: jax.Array, a: jax.Array, b: jax.Array,
             mask: jax.Array, old: jax.Array, *, interpret: bool,
             block_sm: int = 8) -> jax.Array:
    """Execute one ALU instruction on (n_sm, 512) uint32 operand tiles.

    block_sm SMs per grid step: a (block_sm, 512) uint32 tile is
    block_sm * 2 KiB of VMEM per operand — 5 operands x 8 SMs = 80 KiB,
    comfortably inside a v5e core's VMEM. A block is the whole batch or a
    multiple of 8 SMs (the TPU's sublane tiling).
    """
    n_sm = a.shape[0]
    block_sm = min(block_sm, n_sm)
    if n_sm % block_sm:
        raise ValueError(f"n_sm={n_sm} must be a multiple of block_sm={block_sm}")
    if block_sm != n_sm and block_sm % 8:
        raise ValueError(f"block_sm={block_sm} must be a multiple of 8 "
                         f"or the whole batch of {n_sm} SMs")
    opv = jnp.stack([jnp.asarray(op, jnp.int32), jnp.asarray(typ, jnp.int32)])
    spec = pl.BlockSpec((block_sm, N_THREADS), lambda i, opv: (i, 0))
    return pl.pallas_call(
        _alu_kernel,
        out_shape=jax.ShapeDtypeStruct((n_sm, N_THREADS), jnp.uint32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_sm // block_sm,),
            in_specs=[spec, spec, spec, spec], out_specs=spec),
        interpret=interpret,
    )(opv, a, b, mask.astype(jnp.uint32), old)
