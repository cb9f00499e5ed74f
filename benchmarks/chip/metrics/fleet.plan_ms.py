"""fleet.plan_ms: per call of the traced window, the median of the program's
self time in the fleet's plan: normalizing the grid, lowering its programs,
routing its blocks to the devices and resolving the placement
(``egpu.fleet.plan``, inside each ``egpu.launch_fleet``;
``chipbench.program_spans``)."""
from chipbench.program_spans import median_ms_of


def read(rec):
    return median_ms_of(rec, ("egpu.fleet.plan",))
