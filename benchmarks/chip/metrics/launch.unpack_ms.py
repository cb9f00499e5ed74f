"""launch.unpack_ms: per call of the traced window, the median of the
program's self time in unpacking results: each wave's bookkeeping of its
outputs and grid indices, the launch's summed counters, and the one
jitted call (``_assemble_blocks``) that builds ``LaunchResult``'s regs,
shmem and oob from all the waves (``egpu.launch.unpack``;
``chipbench.program_spans``)."""
from chipbench.program_spans import median_ms


def read(rec):
    return median_ms(rec, "unpack")
