"""launch.other_ms: per call of the traced window, the median of its
``bench.launch`` span less the self times of the program's named phases
(plan, schedule, stage, dispatch, unpack, readback): time no span names
(``chipbench.program_spans``)."""
from chipbench.program_spans import median_ms


def read(rec):
    return median_ms(rec, "other")
