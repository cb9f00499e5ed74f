"""fleet.schedule_ms: per call of the traced window, the median of the
program's self time in the fleet's cycle model: each device's schedule,
their merge, the static re-run and the counters (``egpu.fleet.schedule``,
inside each ``egpu.launch_fleet``; ``chipbench.program_spans``)."""
from chipbench.program_spans import median_ms_of


def read(rec):
    return median_ms_of(rec, ("egpu.fleet.schedule",))
