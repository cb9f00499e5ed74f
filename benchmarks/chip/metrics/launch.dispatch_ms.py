"""launch.dispatch_ms: per call of the traced window, the median of the
program's self time in calling each wave's jitted program, one span a wave
(``egpu.launch.dispatch``; ``chipbench.program_spans``)."""
from chipbench.program_spans import median_ms


def read(rec):
    return median_ms(rec, "dispatch")
