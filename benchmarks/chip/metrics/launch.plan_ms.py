"""launch.plan_ms: per call of the traced window, the median of the
program's self time in planning a launch: normalizing the grid, lowering
the kernels, choosing the engine and looking the plans up
(``egpu.launch.plan``, and ``egpu.plan.*`` where a plan is built), less
the timing schedule inside it (``chipbench.program_spans``)."""
from chipbench.program_spans import median_ms


def read(rec):
    return median_ms(rec, "plan")
