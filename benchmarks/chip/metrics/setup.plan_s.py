"""setup.plan_s: seconds of self time the program spent building plans
on a cache miss: lowering programs, partial evaluation, merging wave plans
(``egpu.plan.*`` totals of ``repro.core.tracing``, JAX compile-path time
inside them excluded). A window that compiles nothing adds nothing, so
this is set-up's share."""
from chipbench.program_spans import total_s


def read(rec):
    return total_s("egpu.plan.")
