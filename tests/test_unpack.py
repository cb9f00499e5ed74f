"""Result assembly of ``device.launch``: each wave's whole outputs are put
into grid order by one device call (``device._assemble_blocks``), with the
same bits as slicing them per block and stacking the slices."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DeviceConfig, Kernel, SMConfig, assemble, launch
from repro.core import device as device_mod
from repro.core.assembler import auto_nop

# stores at 8*BID + tid: under a 24-word shmem_depth, blocks 2 and up trap
_SHORT = """
    TDX R1
    BID R2
    PID R3
    LOD R7, #8
    MUL.INT32 R5, R2, R7
    ADD.INT32 R5, R5, R1
    ADD.INT32 R4, R5, R3
    STO R4, (R5)+0
    STOP
"""

_LONG = """
    TDX R1
    BID R2
    PID R3
    LOD R4, (R1)+0
    ADD.FP32 R5, R4, R4
    MUL.FP32 R6, R5, R4
    ADD.INT32 R7, R2, R3
    ADD.INT32 R8, R7, R1
    STO R6, (R1)+16
    STO R8, (R1)+32
    STOP
"""

_BLOCK = 16


def _kernels(short_depth=None):
    short = assemble(auto_nop(_SHORT, _BLOCK)).words
    long_ = assemble(auto_nop(_LONG, _BLOCK)).words
    return [Kernel(short, block=_BLOCK, name="short", shmem_depth=short_depth),
            Kernel(long_, block=_BLOCK, name="long")]


def _dev(engine):
    return DeviceConfig(n_sms=4, global_mem_depth=256, engine=engine,
                        sm=SMConfig(shmem_depth=64, max_steps=5_000))


def _images(n):
    rng = np.random.default_rng(n)
    return rng.standard_normal((n, 64)).astype(np.float32)


def _mixed(engine, gmap, packing="grid", block_ids=None):
    n_long = int(np.sum(np.asarray(gmap) == 1))
    return launch(_dev(engine), programs=_kernels(), grid_map=gmap,
                  shmem=[None, _images(n_long)], packing=packing,
                  block_ids=block_ids)


def _homogeneous(engine, n_blocks, block_ids=None):
    return launch(_dev(engine), programs=_kernels(short_depth=24)[:1],
                  grid_map=[0] * n_blocks, block_ids=block_ids)


_GMAP8 = [0, 1, 0, 1, 1, 0, 1, 0]

# (name, launch, whether the waves' rows leave grid order)
_CASES = [
    ("merged_grid", lambda: _mixed("megakernel", _GMAP8), True),
    ("merged_length", lambda: _mixed("megakernel", _GMAP8, "length"), True),
    ("merged_trace", lambda: _mixed("trace", _GMAP8), True),
    ("merged_last_wave_one_block",
     lambda: _mixed("megakernel", [0, 1, 1, 0, 1]), True),
    ("merged_block_ids",
     lambda: _mixed("megakernel", _GMAP8, block_ids=[7, 3, 0, 5, 1, 2, 6, 4]),
     True),
    ("homogeneous_shmem_pad", lambda: _homogeneous("megakernel", 9), False),
    ("homogeneous_block_ids",
     lambda: _homogeneous("trace", 5, block_ids=[9, 0, 4, 2, 11]), False),
    ("step_program_major",
     lambda: launch(_dev("step"), programs=_kernels(short_depth=24),
                    grid_map=[1, 0, 0, 1, 0], shmem=[None, _images(2)]),
     True),
]


def _per_block(waves, n_blocks, depth):
    """The assembly it replaces: one slice per block into grid-order slots,
    the short shmem padded to the device depth, then one stack a field."""
    regs, shmem, oob = [None] * n_blocks, [None] * n_blocks, [None] * n_blocks
    for blocks, r, s, o in waves:
        if s.shape[1] < depth:
            s = jnp.pad(s, ((0, 0), (0, depth - s.shape[1])))
        for i, b in enumerate(blocks):
            regs[b], shmem[b], oob[b] = r[i], s[i], o[i]
    return (jnp.stack(regs, axis=0), jnp.stack(shmem, axis=0),
            jnp.stack(oob, axis=0))


@pytest.fixture
def assembly_calls(monkeypatch):
    calls = []
    real = device_mod._assemble_blocks

    def spy(waves, shmem_depth):
        calls.append((list(waves), shmem_depth))
        return real(waves, shmem_depth)

    monkeypatch.setattr(device_mod, "_assemble_blocks", spy)
    return calls


@pytest.mark.parametrize("name,run,permuted", _CASES,
                         ids=[c[0] for c in _CASES])
def test_assembly_matches_per_block_unpack(assembly_calls, name, run,
                                           permuted):
    res = run()
    assert len(assembly_calls) == 1
    waves, depth = assembly_calls[0]
    order = np.concatenate([blocks for blocks, *_ in waves])
    assert sorted(order.tolist()) == list(range(res.n_blocks))
    assert (not np.array_equal(order, np.arange(res.n_blocks))) == permuted

    want = _per_block(waves, res.n_blocks, depth)
    for got, ref in zip((res.regs, res.shmem, res.oob), want):
        assert isinstance(got, jax.Array)
        assert got.shape == ref.shape and got.dtype == ref.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert res.shmem.shape == (res.n_blocks, 64)
    if name == "homogeneous_shmem_pad":
        # the trap lands in the assembled oob, in grid order
        np.testing.assert_array_equal(np.asarray(res.oob),
                                      np.arange(9) >= 2)


@pytest.mark.parametrize("n_blocks", [4, 12, 24])
def test_assembly_is_one_call_per_launch(assembly_calls, n_blocks):
    gmap = [b % 2 for b in range(n_blocks)]
    first = _mixed("megakernel", gmap)
    assert len(assembly_calls) == 1
    compiled = device_mod._gather_waves._cache_size()
    second = _mixed("megakernel", gmap)
    assert len(assembly_calls) == 2
    assert device_mod._gather_waves._cache_size() == compiled
    np.testing.assert_array_equal(np.asarray(first.regs),
                                  np.asarray(second.regs))
