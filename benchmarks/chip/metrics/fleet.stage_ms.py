"""fleet.stage_ms: per call of the traced window, the median of the program's
self time in staging: the global-memory image, the shared-memory images
gathered on the host in device order, and each device's slice placed on its
own chip (``egpu.fleet.stage``, inside each ``egpu.launch_fleet``;
``chipbench.program_spans``)."""
from chipbench.program_spans import median_ms_of


def read(rec):
    return median_ms_of(rec, ("egpu.fleet.stage",))
