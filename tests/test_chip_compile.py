"""Compile the execute stage's Pallas kernels for a TPU v5e, without a chip.

The TPU compiler ships with jaxlib, so it compiles here for a v5e that is
described, not attached. Each test compiles one kernel at the paper's
quad-packed sector shape (§III.E: 4 SMs x 512 lanes, a 3072-word shared
memory) with an 8192-word global memory, and checks that the compiled
program holds a Mosaic kernel (``tpu_custom_call``). What Mosaic refuses —
a block that does not tile, a primitive with no TPU lowering, too much
VMEM — fails here, before any chip time is spent. Nothing runs.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every pytest worker
imports every test file. Compiles run in the test's own process, with
JAX's persistent cache off (a described device's programs cannot be read
back from it).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

N_SM, DEPTH, GDEPTH = 4, 3072, 8192
U32, I32 = jnp.uint32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies

        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compile_text(topo):
    """``compile_text(fn, *shapes)`` -> the compiled program's text, for
    one described v5e chip, with Pallas compiled (not interpreted)."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from repro.core import trace_engine
    from repro.kernels import ops

    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def compile_(fn, *args):
        args = jax.tree.map(lambda a: sds(a.shape, a.dtype), args)
        return jax.jit(fn).lower(*args).compile().as_text()

    def clear():
        # nothing traced for the described chip may serve a CPU call later
        jax.clear_caches()
        trace_engine.compile_cache_clear()
        compilation_cache.reset_cache()

    enabled = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        # the code asks the default backend (the CPU here) whether to
        # interpret; the described chip compiles
        mp.setattr(ops, "interpret_mode", lambda: False)
        jax.config.update("jax_enable_compilation_cache", False)
        clear()
        try:
            yield compile_
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            clear()


def _s(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


LANES = _s((N_SM, 512), U32)


def test_simt_alu_compiles(compile_text):
    from repro.kernels import simt_alu

    text = compile_text(
        lambda op, typ, a, b, m, o: simt_alu.simt_alu(
            op, typ, a, b, m, o, interpret=False, block_sm=N_SM),
        _s((), I32), _s((), I32), LANES, LANES, LANES, LANES)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", ["simt_gather", "simt_scatter",
                                    "simt_gather_shared",
                                    "simt_scatter_shared"])
def test_memory_kernel_compiles(compile_text, kernel):
    from repro.kernels import simt_step

    fn = getattr(simt_step, kernel)
    mem = _s((GDEPTH,), U32) if kernel.endswith("shared") \
        else _s((N_SM, DEPTH), U32)
    text = compile_text(lambda m, a, x, y: fn(m, a, x, y, interpret=False),
                        mem, _s((N_SM, 512), I32), LANES, LANES)
    assert "tpu_custom_call" in text


def _longest_segment(program, cfg):
    from repro.core import trace_engine

    plan = trace_engine.compile_megakernel(program, cfg)
    segs = [payload for kind, _, payload in plan.items if kind == "fused"]
    return max(segs, key=lambda s: len(s.rows)).rows


def _program(name):
    from repro.core import SMConfig
    from repro.core.programs.cholesky import (cholesky_imem_depth,
                                              cholesky_program)
    from repro.core.programs.fft import fft_program

    if name == "fft256":
        return fft_program(256), SMConfig(imem_depth=1024)
    return cholesky_program(True), SMConfig(
        n_threads=256, dim_x=16, imem_depth=cholesky_imem_depth(True))


@pytest.mark.parametrize("name", ["fft256", "cholesky16_solve"])
def test_simt_segment_compiles(compile_text, name):
    """The megakernel's fused segment: FFT-256 (ALU, LOD, STO, snoop) and
    the predicated Cholesky solve (guards, SETP/SELP, DOT, INVSQR)."""
    from repro.kernels.simt_step import simt_segment

    program, cfg = _program(name)
    rows = _longest_segment(program, cfg)
    assert len(rows) > 100
    text = compile_text(
        lambda b, p, r, s, o: simt_segment(cfg, rows, b, p, r, s, o,
                                           interpret=False),
        _s((N_SM,), I32), _s((N_SM,), I32), _s((N_SM, 512, 16), U32),
        _s((N_SM, DEPTH), U32), _s((N_SM,), jnp.bool_))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("backend", ["inline", "pallas"])
def test_trace_scan_compiles(compile_text, backend):
    """The trace engine's scan over one FFT-256 schedule; only the Pallas
    backend puts Mosaic kernels in it."""
    from repro.core import trace_engine

    program, cfg = _program("fft256")
    sched = trace_engine.compile_program(program, cfg)
    xs = {f: _s(v.shape, v.dtype) for f, v in sched.xs.items()}
    text = compile_text(
        lambda xs, b, p, r, s, g, o: trace_engine._run_schedule(
            cfg, backend, xs, b, p, r, s, g, o),
        xs, _s((N_SM,), I32), _s((N_SM,), I32), _s((N_SM, 512, 16), U32),
        _s((N_SM, DEPTH), U32), _s((GDEPTH,), U32), _s((N_SM,), jnp.bool_))
    assert ("tpu_custom_call" in text) == (backend == "pallas")
    assert np.prod(sched.xs["sel"].shape) == sched.n_steps > 100
