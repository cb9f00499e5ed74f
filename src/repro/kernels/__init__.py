"""Pallas TPU kernels for the eGPU's compute hot-spots.

Each kernel: ``<name>.py`` (pl.pallas_call + explicit BlockSpec VMEM
tiling), with ``ops.py`` as the jit'd wrapper layer and ``ref.py`` the
pure-jnp oracles. Validated in interpret mode on CPU; TPU is the target.
The ALU kernel's function is ``ops.alu`` or ``simt_alu.simt_alu``: the
package keeps the ``simt_alu`` name for its module.
"""
from . import ops, ref
from .fft_r2 import fft_r2
from .flash_attention import flash_attention, flash_attention_ref
from .mgs_qrd import mgs_qrd
from .wavefront_dot import wavefront_dot

__all__ = ["ops", "ref", "fft_r2", "flash_attention",
           "flash_attention_ref", "mgs_qrd", "wavefront_dot"]
