"""fleet.dispatch_ms: per call of the traced window, the median of the
program's self time in the fleet's one compiled call across the chips
(``egpu.fleet.dispatch``, inside each ``egpu.launch_fleet``;
``chipbench.program_spans``)."""
from chipbench.program_spans import median_ms_of


def read(rec):
    return median_ms_of(rec, ("egpu.fleet.dispatch",))
