"""launch.readback_ms: per call of the traced window, the median of the
program's self time in reading results back to the host and unpacking
them there, which holds the host's wait for the device
(``egpu.readback``; ``chipbench.program_spans``)."""
from chipbench.program_spans import median_ms


def read(rec):
    return median_ms(rec, "readback")
