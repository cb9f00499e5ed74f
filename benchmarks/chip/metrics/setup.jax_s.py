"""setup.jax_s: seconds JAX spent tracing, lowering, compiling and loading
compiled programs from its persistent cache, each nested event counted
once (``jax.*`` totals of ``repro.core.tracing``, from ``jax.monitoring``).
A window that compiles nothing adds nothing, so this is set-up's share."""
from chipbench.program_spans import total_s


def read(rec):
    return total_s("jax.")
