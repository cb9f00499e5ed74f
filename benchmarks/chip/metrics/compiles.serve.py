"""Compile requests inside the window: programs that were not in the
process's memory and went to JAX's compilation cache, a persistent-cache
hit or a compile (``jax.monitoring``)."""


def read(rec):
    return rec.compiles
