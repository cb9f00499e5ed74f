"""fleet.unpack_ms: per call of the traced window, the median of the program's
self time in unpacking results: each block's registers, shared memory and
out-of-bounds flag sliced from the devices' outputs and stacked
(``egpu.fleet.unpack``, inside each ``egpu.launch_fleet``;
``chipbench.program_spans``)."""
from chipbench.program_spans import median_ms_of


def read(rec):
    return median_ms_of(rec, ("egpu.fleet.unpack",))
