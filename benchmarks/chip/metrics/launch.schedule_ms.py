"""launch.schedule_ms: per call of the traced window, the median of the
program's self time in the timing schedule: wave packing and the block
schedulers (``egpu.launch.schedule``; ``chipbench.program_spans``)."""
from chipbench.program_spans import median_ms


def read(rec):
    return median_ms(rec, "schedule")
