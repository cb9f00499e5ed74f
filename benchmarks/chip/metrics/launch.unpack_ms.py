"""launch.unpack_ms: per call of the traced window, the median of the
program's self time in unpacking results: each wave's per-block slices
into the result slots, and the stacks that build ``LaunchResult``
(``egpu.launch.unpack``; ``chipbench.program_spans``)."""
from chipbench.program_spans import median_ms


def read(rec):
    return median_ms(rec, "unpack")
