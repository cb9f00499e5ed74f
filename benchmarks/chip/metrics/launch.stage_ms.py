"""launch.stage_ms: per call of the traced window, the median of the
program's self time in staging memory images: the helper's shared-memory
images (``egpu.inputs``), the global-memory image and each wave's
shared-memory batch (``egpu.launch.stage``; ``chipbench.program_spans``)."""
from chipbench.program_spans import median_ms


def read(rec):
    return median_ms(rec, "stage")
