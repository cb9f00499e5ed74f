"""Multi-SM eGPU device layer: grid/block launches over a packed sector.

The paper closes with "multiple eGPUs can also be tightly packed together
into a single Agilex FPGA logic region" (§III.E quad-packs four SMs per
sector); the scalable follow-up (arXiv 2401.04261) makes the SM count the
headline parameter. This module is that device abstraction:

  * ``DeviceConfig(n_sms, global_mem_depth, ...)`` wraps the single-SM
    ``SMConfig`` with the sector-level parameters;
  * ``launch(dcfg, program, grid=(n_blocks,), block=n_threads, ...)`` is a
    CUDA-style launch; ``launch(dcfg, programs=[...], grid_map=[...])``
    launches SEVERAL programs at once (e.g. FFT and QRD blocks mixed in
    one grid), each block tagged with its program (``PID``) and its index
    within that program's grid (``BID``);
  * blocks are dispatched under one of two disciplines (``schedule=``):
    **static** lockstep waves of ``n_sms`` blocks (the PR-1 model, exact
    fast path for single-program launches), or **dynamic** work-queue
    dispatch (``core.scheduler``) where every SM runs its own sequencer
    and pulls the next ready block as soon as it retires its current one
    — SMs no longer idle waiting for the slowest block of a wave;
  * every SM keeps its private shared memory, and all SMs reach one
    **global-memory segment** (GLD/GST in ``isa.py``) through a single
    device-wide port — under the static schedule the serialization shows
    up as an inflated instruction cost
    (``cycles.instr_cycles(..., n_sms=...)``), under the dynamic schedule
    as per-SM port-wait time in ``LaunchResult.profile()``.

Lockstep execution
------------------
The eGPU ISA has *no data-dependent control flow*: JMP/JSR/LOOP/INIT/RTS
targets and trip counts are immediates, and STOP is unconditional. Blocks
running the same program therefore execute the identical PC trace, so one
wave is simulated as a single batched machine: ONE shared sequencer state
(pc, loop/return stacks, halt flag, cycle counters) plus per-SM data state
(registers, shared memory) and the one shared global memory. This is exact
— not an approximation — and it is what lets the whole per-step execute
stage (ALU + LOD/STO/GLD/GST data path) run as one ``(n_sms, 512)`` batch
through a pluggable backend (``executor.ExecBackend``): the inline jnp
path or the Pallas ``simt_alu``/``simt_step`` kernels as grids over the
SM batch. Functional waves run on one of two bit-identical ENGINES
(``launch(..., engine=)``): the stepping machine below, or the
trace-compiled scan of ``core.trace_engine`` (decode-once schedules; the
default via ``"auto"``).

The same property makes each block's *timing* a static function of its
program (``cycles.program_trace``), which is how dynamic scheduling stays
exact: ``core.scheduler`` replays the per-block traces against per-SM
sequencers and the single global port for timing, while architectural
results are still computed by the lockstep batch machine per program in a
canonical order (program-major, block order). Functional state is
therefore invariant to the dispatch discipline; only the cycle accounting
differs.

Global-memory semantics (the packed-sector memory model):

  * reads (GLD) see the segment as of the start of the cycle;
  * writes (GST) drain through the single port sequentially in
    (sm, thread) order, so on address collisions the LAST writer — highest
    thread of the highest SM — wins, mirroring the shared-memory
    single-write-port determinism;
  * waves run back to back: a later wave sees every earlier wave's global
    writes (this is how grid-wide reductions hand partials forward).
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import compile_cache, isa, trace_engine, tracing
from .cycles import ProgramTrace, program_trace
from .isa import NUM_CLASSES, Op
from .packing import PACKINGS, WavePacking, pack_waves
from .scheduler import SCHEDULES, Schedule, schedule_blocks
from .machine import (
    LOOP_STACK_DEPTH,
    MAX_THREADS,
    N_REGS,
    N_SP,
    RET_STACK_DEPTH,
    MachineState,
    SMConfig,
    as_u32_image,
)
from .executor import (
    _CLASS_OF,
    _G_CTL,
    _G_GLD,
    _G_GST,
    _G_LOD,
    _G_NOP,
    _G_SFU,
    _G_STO,
    _GROUP_OF_OP,
    DATA_SEL_OF_OP,
    _decode,
    get_execute_backend,
    make_data_handlers,
    pack_imem,
)

_U32 = jnp.uint32
_I32 = jnp.int32
_F32 = jnp.float32


def _bitcast_f32(x):
    return jax.lax.bitcast_convert_type(x, _F32)


# ---------------------------------------------------------------------------
# configuration + state
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DeviceConfig:
    """Sector-level machine parameters wrapping the per-SM ``SMConfig``."""

    n_sms: int = 4                    # SMs packed in the sector (§III.E: 4)
    global_mem_depth: int = 4096      # words of the shared global segment
    sm: SMConfig = SMConfig()         # per-SM template (block size is set
                                      # per launch; the rest is inherited;
                                      # imem/shmem depth are the CEILING for
                                      # per-Kernel overrides)
    backend: str = "inline"           # default execute backend
    schedule: str = "auto"            # default block-dispatch discipline:
                                      # "static" waves | "dynamic" queue |
                                      # "auto" (static iff one program)
    engine: str = "auto"              # default functional engine:
                                      # "step" while-loop machine | "trace"
                                      # decode-once scan | "auto" (trace
                                      # whenever the static trace halts)
    packing: str = "grid"             # default wave-packing policy:
                                      # "grid" chunks (opt-in-stable
                                      # default) | "length" pad-minimal
                                      # waves | "auto" (length for mixed
                                      # grids — see core.packing)
    dispatch_latency: int = 0         # host cycles to dispatch one launch
                                      # (arXiv 2401.04261's host dispatch
                                      # latency; 0 = free, the pre-serving
                                      # model)
    queue_latency: int = 0            # extra host cycles per entry sitting
                                      # in the launch queue at dispatch
                                      # time (launch(queue_depth=) — the
                                      # LaunchServer wires this up)

    def __post_init__(self):
        if self.n_sms < 1:
            raise ValueError(f"n_sms={self.n_sms} must be >= 1")
        if self.global_mem_depth < 1:
            raise ValueError("global_mem_depth must be >= 1")
        if self.dispatch_latency < 0 or self.queue_latency < 0:
            raise ValueError("dispatch_latency/queue_latency must be >= 0")
        if self.schedule not in SCHEDULES + ("auto",):
            raise ValueError(f"schedule={self.schedule!r} must be one of "
                             f"{SCHEDULES + ('auto',)}")
        if self.engine not in trace_engine.ENGINES + ("auto",):
            raise ValueError(f"engine={self.engine!r} must be one of "
                             f"{trace_engine.ENGINES + ('auto',)}")
        if self.packing not in PACKINGS:
            raise ValueError(f"packing={self.packing!r} must be one of "
                             f"{PACKINGS}")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DeviceState:
    """One wave's batched machine state (a JAX pytree).

    Data state is per-SM (leading ``n_sms`` axis); sequencer state is
    shared across the lockstep batch; global memory is one segment.
    """

    regs: jax.Array        # (n_sms, MAX_THREADS, N_REGS) uint32
    shmem: jax.Array       # (n_sms, shmem_depth) uint32
    gmem: jax.Array        # (global_mem_depth,) uint32 — SHARED
    pc: jax.Array          # () int32
    ret_stack: jax.Array   # (RET_STACK_DEPTH,) int32
    ret_sp: jax.Array      # () int32
    loop_ctr: jax.Array    # (LOOP_STACK_DEPTH,) int32
    loop_sp: jax.Array     # () int32
    halted: jax.Array      # () bool
    oob: jax.Array         # (n_sms,) bool — per-SM out-of-range access
    steps: jax.Array       # () int32
    cycles: jax.Array      # () int32 — wave cycles incl. gmem contention
    cycles_by_class: jax.Array  # (NUM_CLASSES,) int32

    def replace(self, **kw) -> "DeviceState":
        return dataclasses.replace(self, **kw)

    @property
    def n_sms(self) -> int:
        return self.regs.shape[0]


def init_device_state(cfg: SMConfig, n_sms: int, gmem_depth: int = 64,
                      shmem: Any = None, gmem: Any = None) -> DeviceState:
    """Fresh wave state. ``shmem`` may be None, one image (broadcast to all
    SMs), or an (n_sms, ...) batch of per-SM images."""
    if shmem is None:
        sh = jnp.zeros((n_sms, cfg.shmem_depth), _U32)
    else:
        sh = as_u32_image(shmem, cfg.shmem_depth, "shared-memory")
        if sh.ndim == 1:
            sh = jnp.broadcast_to(sh, (n_sms, cfg.shmem_depth))
        elif sh.shape[0] != n_sms:
            raise ValueError(f"shared-memory batch of {sh.shape[0]} images "
                             f"!= n_sms={n_sms}")
    if gmem is None:
        gm = jnp.zeros((gmem_depth,), _U32)
    else:
        gm = as_u32_image(gmem, gmem_depth, "global-memory")
    return DeviceState(
        regs=jnp.zeros((n_sms, MAX_THREADS, N_REGS), _U32),
        shmem=sh,
        gmem=gm,
        pc=jnp.zeros((), _I32),
        ret_stack=jnp.zeros((RET_STACK_DEPTH,), _I32),
        ret_sp=jnp.zeros((), _I32),
        loop_ctr=jnp.zeros((LOOP_STACK_DEPTH,), _I32),
        loop_sp=jnp.zeros((), _I32),
        halted=jnp.zeros((), jnp.bool_),
        oob=jnp.zeros((n_sms,), jnp.bool_),
        steps=jnp.zeros((), _I32),
        cycles=jnp.zeros((), _I32),
        cycles_by_class=jnp.zeros((NUM_CLASSES,), _I32),
    )


def lift_machine_state(state: MachineState, gmem_depth: int = 64) -> DeviceState:
    """Wrap a legacy single-SM ``MachineState`` as a 1-SM wave."""
    return DeviceState(
        regs=state.regs[None], shmem=state.shmem[None],
        gmem=jnp.zeros((gmem_depth,), _U32),
        pc=state.pc, ret_stack=state.ret_stack, ret_sp=state.ret_sp,
        loop_ctr=state.loop_ctr, loop_sp=state.loop_sp,
        halted=state.halted, oob=jnp.reshape(state.oob, (1,)),
        steps=state.steps, cycles=state.cycles,
        cycles_by_class=state.cycles_by_class,
    )


def squeeze_device_state(s: DeviceState) -> MachineState:
    """Project a 1-SM wave back to the legacy ``MachineState`` view."""
    return MachineState(
        regs=s.regs[0], shmem=s.shmem[0], pc=s.pc,
        ret_stack=s.ret_stack, ret_sp=s.ret_sp,
        loop_ctr=s.loop_ctr, loop_sp=s.loop_sp,
        halted=s.halted, oob=s.oob[0], steps=s.steps, cycles=s.cycles,
        cycles_by_class=s.cycles_by_class,
    )


# ---------------------------------------------------------------------------
# the batched device step
# ---------------------------------------------------------------------------

def _device_step(cfg: SMConfig, backend, imem_lo, imem_hi, block_idx,
                 prog_idx, s: DeviceState) -> DeviceState:
    n_sms = s.regs.shape[0]
    d = _decode(imem_lo[s.pc], imem_hi[s.pc])
    tid = jnp.arange(MAX_THREADS, dtype=_I32)
    lane = tid % N_SP
    wave = tid // N_SP

    # ---- flexible-ISA active mask (identical across the lockstep batch) ----
    n_waves = cfg.n_waves
    depth_table = jnp.array(
        [n_waves, max(1, n_waves // 2), max(1, n_waves // 4), 1], _I32)
    width_table = jnp.array([16, 8, 4, 1], _I32)
    act_waves = depth_table[d["depth"]]
    act_wthreads = width_table[d["width"]]
    active = (lane < act_wthreads) & (wave < act_waves) & (tid < cfg.n_threads)

    op = d["opcode"]

    # ---- data path: the shared execute stage (executor.make_data_handlers) --
    handlers = make_data_handlers(cfg, backend, d, active, block_idx,
                                  prog_idx)
    sel = jnp.asarray(DATA_SEL_OF_OP)[op]
    regs, shmem, gmem, oob = jax.lax.switch(
        sel, handlers, (s.regs, s.shmem, s.gmem, s.oob))

    # ---- sequencer: control flow (unconditional scalar math — non-control
    # opcodes match none of the branches, so stacks stay put and pc += 1) ----
    imm = d["imm_raw"]
    pc1 = s.pc + 1
    # LOOP: decrement top counter; jump while > 1, pop at 1
    lsp = jnp.clip(s.loop_sp - 1, 0, LOOP_STACK_DEPTH - 1)
    top = s.loop_ctr[lsp]
    loop_taken = top > 1
    pc = jnp.select(
        [op == int(Op.JMP), op == int(Op.JSR), op == int(Op.RTS),
         op == int(Op.LOOP)],
        [imm, imm,
         s.ret_stack[jnp.clip(s.ret_sp - 1, 0, RET_STACK_DEPTH - 1)],
         jnp.where(loop_taken, imm, pc1)],
        pc1)
    ret_stack = jnp.where(
        op == int(Op.JSR),
        s.ret_stack.at[jnp.clip(s.ret_sp, 0, RET_STACK_DEPTH - 1)].set(pc1),
        s.ret_stack)
    ret_sp = s.ret_sp + jnp.where(op == int(Op.JSR), 1, 0) \
        - jnp.where(op == int(Op.RTS), 1, 0)
    loop_ctr = jnp.where(
        op == int(Op.INIT),
        s.loop_ctr.at[jnp.clip(s.loop_sp, 0, LOOP_STACK_DEPTH - 1)].set(imm),
        jnp.where(op == int(Op.LOOP),
                  s.loop_ctr.at[lsp].set(top - 1), s.loop_ctr))
    loop_sp = s.loop_sp \
        + jnp.where(op == int(Op.INIT), 1, 0) \
        - jnp.where((op == int(Op.LOOP)) & ~loop_taken, 1, 0)
    halted = s.halted | (op == int(Op.STOP))
    group = jnp.asarray(_GROUP_OF_OP)[op]

    # ---- cycle accounting ----------------------------------------------------
    # Per-SM resources (ALU, shared memory, extension units) run concurrently
    # across the lockstep batch; the single global-memory port serializes the
    # batch, so GLD/GST pay n_sms * active_threads (cycles.py).
    act_threads = act_waves * act_wthreads
    one = jnp.int32(1)
    is_gmem = (group == _G_GLD) | (group == _G_GST)
    cyc = jnp.select(
        [group == _G_LOD, group == _G_STO, is_gmem,
         (group == _G_NOP) | (group == _G_CTL) | (group == _G_SFU)],
        [jnp.maximum(one, (act_threads + 3) // 4), act_threads,
         act_threads * n_sms, one],
        act_waves)
    klass = jnp.asarray(_CLASS_OF)[op, d["typ"]]
    return DeviceState(
        regs=regs, shmem=shmem, gmem=gmem, pc=pc,
        ret_stack=ret_stack, ret_sp=ret_sp,
        loop_ctr=loop_ctr, loop_sp=loop_sp,
        halted=halted, oob=oob,
        steps=s.steps + 1,
        cycles=s.cycles + cyc,
        cycles_by_class=s.cycles_by_class.at[klass].add(cyc),
    )


@functools.partial(jax.jit, static_argnums=(0, 1))
def run_wave(cfg: SMConfig, backend: str, imem_lo, imem_hi, block_idx,
             prog_idx, state: DeviceState) -> DeviceState:
    """Run one wave of blocks to completion (jitted ``lax.while_loop``).

    This is the STEP engine: fetch/decode/dispatch per instruction. The
    trace engine (``core.trace_engine``) is the decode-once fast path;
    this machine survives as the differential oracle and the executor of
    legacy ``run``/``run_many`` shims.
    """
    execute = get_execute_backend(backend)

    def cond(s):
        return (~s.halted) & (s.steps < cfg.max_steps) \
            & (s.pc >= 0) & (s.pc < cfg.imem_depth)

    def body(s):
        return _device_step(cfg, execute, imem_lo, imem_hi, block_idx,
                            prog_idx, s)

    return jax.lax.while_loop(cond, body, state)


# ---------------------------------------------------------------------------
# buffers: named global-memory segments
# ---------------------------------------------------------------------------

def buffer_layout(buffers: Mapping[str, Any]) -> dict[str, tuple[int, int]]:
    """Deterministic gmem layout: name -> (offset, length) in 32-bit words,
    packed in insertion order from offset 0. Program builders call this to
    derive addresses; ``launch`` uses the same layout to fill gmem."""
    layout: dict[str, tuple[int, int]] = {}
    off = 0
    for name, arr in buffers.items():
        n = int(np.asarray(arr).reshape(-1).shape[0])
        layout[name] = (off, n)
        off += n
    return layout


def pack_buffers(buffers: Mapping[str, Any], depth: int
                 ) -> tuple[jax.Array, dict[str, tuple[int, int]]]:
    """Pack named host arrays into one global-memory image of ``depth``."""
    layout = buffer_layout(buffers)
    used = sum(n for _, n in layout.values())
    if used > depth:
        raise ValueError(f"buffers need {used} words but global_mem_depth "
                         f"is {depth}")
    img = jnp.zeros((depth,), _U32)
    for name, arr in buffers.items():
        off, n = layout[name]
        img = img.at[off:off + n].set(
            as_u32_image(np.asarray(arr).reshape(-1), n, name))
    return img, layout


# ---------------------------------------------------------------------------
# the launch API
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Kernel:
    """One program of a (possibly multi-program) launch.

    ``launch(..., programs=[...])`` accepts Kernels, assembled Programs, or
    raw word arrays; a bare program gets the device defaults. ``block`` is
    threads per block, ``dim_x`` the TDX/TDY x-extent (defaults to
    ``block``: flat 1-D indexing), ``name`` labels the program in
    ``LaunchResult.profile()``. ``barrier=True`` makes this program's
    blocks wait until every block of all earlier-listed programs retired
    (a device-wide dependency fence — the stream semantic for dependent
    kernels such as the two stages of a grid reduction).

    ``imem_depth``/``shmem_depth`` override the device-wide ``SMConfig``
    defaults for THIS program only (e.g. a small kernel that wants tight
    out-of-range checking, or a long unrolled one that needs the full
    I-MEM); both are validated against the device ceiling — an SM cannot
    grow memory past what the sector floorplan gives it. Blocks with a
    shallower shared memory are zero-padded back to the device depth in
    ``LaunchResult.shmem`` so mixed launches still stack.

    ``priority`` orders the DYNAMIC dispatch queue: ready blocks of a
    higher-priority program are pulled first; ties keep FIFO grid order
    (priority 0, the default, is plain FIFO — bit-identical scheduling to
    a priority-free launch). The static wave schedule ignores priority
    (waves are grid order by definition), and functional results are
    schedule-invariant either way.
    """

    program: Any                      # Program | encoded 40-bit word array
    block: int | None = None
    dim_x: int | None = None
    name: str | None = None
    barrier: bool = False
    imem_depth: int | None = None
    shmem_depth: int | None = None
    priority: int = 0


def as_kernel(p: Any) -> Kernel:
    return p if isinstance(p, Kernel) else Kernel(program=p)


@dataclasses.dataclass
class LaunchResult:
    """Per-block results + aggregate device profile of one launch."""

    grid: tuple[int, ...]
    block: int | tuple[int, ...]  # threads/block (per program if mixed)
    n_waves: int                # scheduling rounds (0 for dynamic dispatch)
    regs: jax.Array             # (n_blocks, MAX_THREADS, N_REGS) uint32
    shmem: jax.Array            # (n_blocks, shmem_depth) uint32
    gmem: jax.Array             # (global_mem_depth,) uint32 — final
    oob: jax.Array              # (n_blocks,) bool
    halted: bool                # every block ran to STOP
    steps: int                  # instructions issued (per sequencer)
    cycles: int                 # modeled device cycles for the launch
    wave_cycles: np.ndarray     # (n_waves,) per-round cycles (static only)
    cycles_by_class: np.ndarray  # (NUM_CLASSES,) sequencer occupancy
    buffer_offsets: dict[str, tuple[int, int]] | None = None
    # scheduling (None only for results built by legacy external code)
    schedule: str = "static"            # "static" | "dynamic"
    engine: str = "step"                # "step" | "trace" functional engine
    engine_fallback: str | None = None  # why "auto" degraded to "step"
    program_names: tuple[str, ...] = ("k0",)
    grid_map: np.ndarray | None = None  # (n_blocks,) block -> program idx
    timing: Schedule | None = None      # per-SM / per-block timeline
    static_cycles: int | None = None    # wave-schedule baseline makespan
    trace_merge: dict[str, Any] | None = None  # heterogeneous-wave stats
    packing: str = "grid"               # resolved wave-packing policy
    wave_packing: WavePacking | None = None  # the membership decision
    host_dispatch: dict[str, int] | None = None  # launch-queue/dispatch
                                        # latency model (non-None exactly
                                        # when the device models it)
    priority_respected: bool = True     # False iff Kernel(priority=) was
                                        # requested but the static wave
                                        # schedule ignored it
    fleet: dict[str, Any] | None = None  # multi-device fleet view
                                        # (core.fleet.launch_fleet):
                                        # per-device occupancy, routing,
                                        # placement, NUMA charges

    @property
    def n_blocks(self) -> int:
        return int(self.grid[0])

    def shmem_f32(self) -> jax.Array:
        return _bitcast_f32(self.shmem)

    def gmem_f32(self) -> jax.Array:
        return _bitcast_f32(self.gmem)

    def buffer(self, name: str, dtype=jnp.float32) -> jax.Array:
        """Final contents of a named gmem buffer (bitcast to ``dtype``)."""
        if not self.buffer_offsets or name not in self.buffer_offsets:
            raise KeyError(f"no buffer {name!r} in this launch")
        off, n = self.buffer_offsets[name]
        seg = self.gmem[off:off + n]
        if dtype in (jnp.uint32, np.uint32):
            return seg
        return jax.lax.bitcast_convert_type(seg, dtype)

    def profile(self) -> dict[str, Any]:
        """Aggregate cycle profile (Tables III/IV view + the GMEM row),
        extended with the scheduler's per-SM / per-program occupancy view.

        ``per_sm[i]``: busy (issuing), wait (stalled on the global port),
        idle (no block to run) cycles and blocks retired for SM ``i``.
        ``per_program[name]``: blocks, busy cycles, port-wait cycles, and
        the per-SM busy split — the occupancy fractions are of the
        launch's total modeled cycles. ``gmem_port`` summarizes the single
        device-wide port: occupancy, queueing, and utilization.

        ``engine_fallback`` is non-None exactly when ``engine="auto"``
        degraded to the step machine (never silently); ``packing`` is
        the resolved wave-packing policy; ``trace_merge`` appears when
        the trace engine batched heterogeneous waves and reports the
        packing policy, per-wave merge padding, and the launch-level
        ``pad_overhead_total`` aggregate.
        """
        by = np.asarray(self.cycles_by_class)
        total = int(by.sum())
        out: dict[str, Any] = {
            "total_cycles": int(self.cycles),
            "instructions": int(self.steps),
            "schedule": self.schedule,
            "engine": self.engine,
            "engine_fallback": self.engine_fallback,
            "packing": self.packing,
            "priority_respected": self.priority_respected,
            "n_waves": self.n_waves,
            "wave_cycles": [int(c) for c in self.wave_cycles],
            "by_class": {n: int(c) for n, c in zip(isa.CLASS_NAMES, by)},
            "pct_by_class": {n: (100.0 * int(c) / total if total else 0.0)
                             for n, c in zip(isa.CLASS_NAMES, by)},
        }
        if self.trace_merge is not None:
            out["trace_merge"] = self.trace_merge
        if self.host_dispatch is not None:
            out["host_dispatch"] = dict(self.host_dispatch)
        if self.fleet is not None:
            out["fleet"] = dict(self.fleet)
        t = self.timing
        if t is None:
            return out
        span = max(int(self.cycles), 1)
        busy, wait, idle = t.sm_busy, t.sm_wait, t.sm_idle
        out["per_sm"] = [
            {"busy": int(busy[i]), "wait": int(wait[i]),
             "idle": int(idle[i]), "blocks": int(t.sm_blocks[i]),
             "occupancy": int(busy[i]) / span}
            for i in range(t.n_sms)]
        gmap = np.asarray(self.grid_map)
        per_prog: dict[str, Any] = {}
        for k, name in enumerate(self.program_names):
            mine = gmap == k
            sm_busy_k = np.zeros(t.n_sms, np.int64)
            np.add.at(sm_busy_k, t.block_sm[mine], t.block_busy[mine])
            per_prog[name] = {
                "blocks": int(mine.sum()),
                "busy_cycles": int(t.block_busy[mine].sum()),
                "gmem_wait": int(t.block_wait[mine].sum()),
                "sm_busy": [int(c) for c in sm_busy_k],
                "sm_occupancy": [int(c) / span for c in sm_busy_k],
            }
        out["per_program"] = per_prog
        out["gmem_port"] = {
            "busy": t.port_busy,
            "wait": t.port_wait,
            "utilization": t.port_busy / span,
        }
        out["static_cycles"] = int(self.static_cycles) \
            if self.static_cycles is not None else int(self.cycles)
        return out


_STATIC_PRIORITY_WARNED = False


def _warn_static_priority() -> None:
    """Warn (once per process) that Kernel(priority=) was silently lost:
    the static wave schedule dispatches in grid order by definition, so a
    prioritized launch run static gets FIFO treatment. The condition is
    also surfaced per launch as profile()["priority_respected"]."""
    global _STATIC_PRIORITY_WARNED
    if _STATIC_PRIORITY_WARNED:
        return
    _STATIC_PRIORITY_WARNED = True
    warnings.warn(
        "Kernel(priority=) is ignored under schedule='static': waves "
        "dispatch in grid order. Use schedule='dynamic' (or 'auto' on a "
        "multi-program grid) for priority-aware dispatch; see "
        "LaunchResult.profile()['priority_respected'].",
        UserWarning, stacklevel=3)


def _resolve_schedule(schedule: str | None, dcfg: DeviceConfig,
                      n_programs: int) -> str:
    mode = schedule if schedule is not None else dcfg.schedule
    if mode == "auto":
        return "static" if n_programs == 1 else "dynamic"
    if mode not in SCHEDULES:
        raise ValueError(f"schedule={mode!r} must be one of "
                         f"{SCHEDULES + ('auto',)}")
    return mode


def _kernel_shmem(sh: Any, depth: int, count: int, k: int):
    """Normalize one program's shared-memory init: None, one image
    (broadcast to the program's blocks), or a (count, ...) batch indexed by
    the program-local block index."""
    if sh is None:
        return None
    batch = as_u32_image(sh, depth, f"shared-memory (program {k})")
    if batch.ndim == 1:
        return jnp.broadcast_to(batch, (count, depth))
    if batch.shape[0] != count:
        raise ValueError(f"shared-memory batch of {batch.shape[0]} images "
                         f"!= {count} blocks of program {k}")
    return batch


@functools.partial(jax.jit, static_argnames=("shmem_depth",))
def _gather_waves(waves, inv, *, shmem_depth: int):
    """Concatenate each field of the per-wave ``(regs, shmem, oob)`` along
    the block axis (shmem padded to ``shmem_depth``), then gather rows into
    grid order by ``inv`` (None: already in grid order). ``inv`` is traced,
    so grids whose waves have the same widths share one compile."""
    regs = jnp.concatenate([r for r, _, _ in waves], axis=0)
    shmem = jnp.concatenate(
        [jnp.pad(s, ((0, 0), (0, shmem_depth - s.shape[1])))
         for _, s, _ in waves], axis=0)
    oob = jnp.concatenate([o for _, _, o in waves], axis=0)
    if inv is not None:
        regs, shmem, oob = (jnp.take(x, inv, axis=0, mode="clip")
                            for x in (regs, shmem, oob))
    return regs, shmem, oob


def _assemble_blocks(waves, shmem_depth: int):
    """``LaunchResult``'s ``regs``, ``shmem`` and ``oob`` in grid order, in
    one device call. ``waves`` holds one ``(blocks, regs, shmem, oob)`` per
    wave in run order: ``blocks[i]`` is the grid index of the wave's row
    ``i``, and the waves' blocks together cover the grid once."""
    order = np.concatenate([blocks for blocks, *_ in waves])
    inv = None
    if not np.array_equal(order, np.arange(order.size)):
        inv = np.argsort(order).astype(np.int32)
    return _gather_waves([tuple(w[1:]) for w in waves], inv,
                         shmem_depth=shmem_depth)


def _normalize_grid(dcfg: DeviceConfig, program, grid, block, dim_x,
                    programs, grid_map, shmem
                    ) -> tuple[list[Kernel], np.ndarray, list[Any]]:
    """Normalize the two launch forms to ``(kernels, gmap, shmems)`` —
    shared by ``launch`` and the fleet router (``core.fleet``), so both
    front doors accept exactly the same grids."""
    if programs is not None:
        if program is not None or grid is not None or block is not None \
                or dim_x is not None:
            raise ValueError("pass either program/grid/block/dim_x or "
                             "programs=/grid_map=, not both")
        if grid_map is None:
            raise ValueError("programs= requires grid_map=")
        kernels = [as_kernel(p) for p in programs]
        gmap = np.asarray(list(grid_map), np.int64)
        if gmap.ndim != 1 or gmap.shape[0] < 1:
            raise ValueError("grid_map must be a non-empty 1-D sequence")
        if gmap.min() < 0 or gmap.max() >= len(kernels):
            raise ValueError(f"grid_map references programs outside "
                             f"[0, {len(kernels)})")
        shmems = list(shmem) if shmem is not None else [None] * len(kernels)
        if len(shmems) != len(kernels):
            raise ValueError(f"shmem sequence of {len(shmems)} != "
                             f"{len(kernels)} programs")
    else:
        if program is None or grid is None:
            raise ValueError("launch needs program+grid or programs+grid_map")
        grid = (int(grid),) if isinstance(grid, int) \
            else tuple(map(int, grid))
        if len(grid) != 1 or grid[0] < 1:
            raise ValueError(f"grid={grid} must be a positive (n_blocks,)")
        kernels = [Kernel(program=program, block=block, dim_x=dim_x)]
        gmap = np.zeros((grid[0],), np.int64)
        shmems = [shmem]
    return kernels, gmap, shmems


def _lower_kernels(dcfg: DeviceConfig, kernels: Sequence[Kernel]
                   ) -> tuple[list[str], list[SMConfig],
                              list[tuple[jax.Array, jax.Array]],
                              list[ProgramTrace], list[np.ndarray]]:
    """Per-program static resources: unique names, per-kernel SMConfigs
    (with validated imem/shmem overrides), packed I-MEM images, exact
    static traces, and the raw word arrays. Shared by ``launch`` and the
    fleet router so every device in a fleet lowers identically."""
    names: list[str] = []
    cfgs: list[SMConfig] = []
    imems: list[tuple[jax.Array, jax.Array]] = []
    traces: list[ProgramTrace] = []
    word_arrays: list[np.ndarray] = []
    for k, kern in enumerate(kernels):
        blk = int(kern.block) if kern.block is not None \
            else dcfg.sm.n_threads
        overrides = {}
        for field, ceiling in (("imem_depth", dcfg.sm.imem_depth),
                               ("shmem_depth", dcfg.sm.shmem_depth)):
            val = getattr(kern, field)
            if val is None:
                continue
            val = int(val)
            if val < 1:
                raise ValueError(f"{field}={val} of program {k} must be "
                                 f">= 1")
            if val > ceiling:
                raise ValueError(
                    f"{field}={val} of program {k} exceeds the device "
                    f"ceiling {ceiling} (DeviceConfig.sm.{field})")
            overrides[field] = val
        cfg = dataclasses.replace(
            dcfg.sm, n_threads=blk,
            dim_x=kern.dim_x if kern.dim_x is not None else blk,
            **overrides)
        words = kern.program.words if hasattr(kern.program, "words") \
            else np.asarray(kern.program)
        lo, hi = pack_imem(words, cfg.imem_depth)
        cfgs.append(cfg)
        word_arrays.append(np.asarray(words))
        imems.append((jnp.asarray(lo), jnp.asarray(hi)))
        traces.append(program_trace(words, blk, imem_depth=cfg.imem_depth,
                                    max_steps=cfg.max_steps))
        name = kern.name or f"k{k}"
        while name in names:
            name = f"{name}.{k}"
        names.append(name)
    return names, cfgs, imems, traces, word_arrays


def _resolve_engine(engine: str | None, dcfg: DeviceConfig,
                    traces: Sequence[ProgramTrace]
                    ) -> tuple[str, str | None]:
    """Resolve the functional engine; returns ``(engine, fallback)``.

    ``fallback`` is non-None exactly when ``"auto"`` degraded from its
    first-choice engine — ``"auto"`` never degrades silently; the reason
    is surfaced as ``LaunchResult.profile()["engine_fallback"]``. The
    auto ladder is megakernel (fused segments, fastest on schedules with
    real fusible runs) -> trace (scanned schedule, when a program's
    schedule exceeds the megakernel unroll cap) -> step (O(1) schedule
    memory, when a fuel-limited trace means a runaway program; ALSO the
    fallback when every program is too short for fusion to pay —
    compiled-engine dispatch glue dominates tiny schedules, see
    ``trace_engine.MEGAKERNEL_MIN_FUSED_ROWS``).
    """
    mode = engine if engine is not None else dcfg.engine
    if mode == "auto":
        # the trace/megakernel engines materialize the full issued
        # schedule; a fuel-limited (non-halting) trace means a runaway
        # program, where the step machine's O(1) schedule memory is the
        # right tool
        if not all(t.halted for t in traces):
            return "step", "fuel-limited-trace"
        if max(t.data_steps for t in traces) \
                > trace_engine.MEGAKERNEL_UNROLL_CAP:
            return "trace", "megakernel-unroll-cap"
        # plan-time cost cutoff: residual rows = data rows that are not
        # global-port accesses, i.e. what the megakernel can actually
        # fuse. When even the longest program is below the threshold
        # there is nothing to amortize the compiled-engine overhead
        # against and the step machine wins (BENCH_engine.json,
        # saxpy256_b64: megakernel 0.811x vs step)
        residual = max(t.data_steps
                       - sum(1 for i in t.instrs if i.gmem)
                       for t in traces)
        if residual < trace_engine.MEGAKERNEL_MIN_FUSED_ROWS:
            return "step", "megakernel-too-small"
        return "megakernel", None
    if mode not in trace_engine.ENGINES:
        raise ValueError(f"engine={mode!r} must be one of "
                         f"{trace_engine.ENGINES + ('auto',)}")
    return mode, None


def launch(dcfg: DeviceConfig, program=None, grid=None,
           block: int | None = None, *,
           programs: Sequence[Any] | None = None,
           grid_map: Sequence[int] | None = None,
           buffers: Mapping[str, Any] | None = None,
           shmem: Any = None, gmem: Any = None,
           backend: str | None = None, dim_x: int | None = None,
           schedule: str | None = None,
           engine: str | None = None,
           packing: str | None = None,
           queue_depth: int = 0,
           block_ids: Sequence[int] | None = None) -> LaunchResult:
    """CUDA-style kernel launch on the multi-SM device.

    Two forms:

    * single-program: ``launch(dcfg, program, grid=(n_blocks,), block=n)``
      — the PR-1 API, unchanged;
    * multi-program: ``launch(dcfg, programs=[...], grid_map=[...])`` —
      ``programs`` is a list of ``Kernel``s (or bare programs) and
      ``grid_map[b]`` names the program block ``b`` runs. Blocks are
      dispatched to the SM work queues in ``grid_map`` order; each block's
      ``BID`` is its index *within its own program's grid* and ``PID`` its
      program index, so concurrently-launched kernels address their own
      data.

    Args:
      dcfg: the device (sector) configuration.
      program: an assembled ``Program`` or encoded 40-bit word array.
      grid: number of thread blocks, as ``(n_blocks,)`` or an int.
      block: threads per block (<= 512); defaults to ``dcfg.sm.n_threads``.
      programs: the multi-program form (mutually exclusive with
        ``program``/``grid``/``block``/``dim_x``).
      grid_map: (n_blocks,) program index per block, in dispatch order.
      buffers: named host arrays packed into global memory from offset 0 in
        insertion order (layout via ``buffer_layout``); mutually exclusive
        with ``gmem``, a raw initial global-memory image.
      shmem: shared-memory initializer. Single-program: one image broadcast
        to all blocks, or an ``(n_blocks, ...)`` batch. Multi-program: a
        sequence aligned with ``programs`` whose entries are None, one
        image, or an ``(n_blocks_of_program, ...)`` batch.
      backend: execute backend ("inline" | "pallas"); default from dcfg.
      dim_x: the 2-D thread-space x extent (TDX/TDY); defaults to ``block``
        (flat 1-D indexing, the CUDA idiom).
      schedule: "static" (lockstep waves of ``n_sms`` blocks), "dynamic"
        (per-SM sequencers pulling from the block work queue), or "auto"
        (default: static when all blocks share one program — the exact
        lockstep fast path — dynamic otherwise).
      engine: functional execution engine. "step" is the classic
        fetch/decode/dispatch ``lax.while_loop`` machine; "trace" lowers
        each program once into a pre-decoded structure-of-arrays schedule
        and runs it as a single jitted ``lax.scan`` (no runtime decode, no
        dynamic pc, NOP/control steps compiled out — see
        ``core.trace_engine``); "megakernel" further fuses each segment
        between global-port accesses into one kernel with host-constant
        fields and masks (no per-row switch; the Pallas backend keeps
        registers/shmem VMEM-resident across the fused steps). On a
        heterogeneous grid both compiled engines MERGE the programs into
        shared waves: the trace engine scans one padded merged schedule
        (``profile()["trace_merge"]`` reports the padding), the
        megakernel dispatches fused segments per live slot with the gmem
        rows globally ordered (``trace_merge`` gains per-segment
        ``fusion`` stats instead — no padded rows execute). "auto"
        (default) picks "megakernel" whenever every program's static
        trace terminates and fits the unroll cap, degrading to "trace"
        above the cap and to "step" for runaway/fuel-limited programs —
        never silently: ``profile()["engine_fallback"]`` names the
        reason. All engines are bit-identical on every backend; timing
        is engine-independent.
      packing: wave-packing policy deciding WHICH blocks share a wave
        within each barrier phase (``core.packing``). "grid" (the
        default) chunks blocks in grid order — byte-identical to the
        pre-packing device. "length" stably sorts each phase by
        descending schedule length and picks pad-minimal wave
        boundaries, so a mixed grid's merged waves stop padding short
        programs to long ones. "auto" resolves to "length" exactly when
        a phase mixes schedule lengths. One packing feeds every layer:
        the merged functional waves, the static wave timing, and the
        dynamic queue's FIFO tiebreak — so ``cycles``/``wave_cycles``
        describe the waves that actually ran and dynamic-vs-static stays
        a like-for-like comparison.

    Timing comes from ``core.scheduler`` over the programs' static traces;
    architectural results are computed by exact lockstep batch machines.
    The step machine runs a canonical program-major order; the trace
    engine's merged heterogeneous waves follow the wave packing (grid
    order within each barrier phase under the default policy). The two
    coincide — and results are invariant to the dispatch discipline, to
    the packing policy, and to ``grid_map`` permutations of equal-program
    blocks — under the standard launch contract that blocks which may run
    concurrently (same phase) do not race through global memory; use
    ``Kernel(barrier=True)`` to fence cross-block dataflow. Packing
    therefore only changes which blocks share a wave (and with it the
    modeled timing and merge padding), never observable state.

    ``block_ids`` is the fleet router seam (``core.fleet``): a
    ``(n_blocks,)`` override of each block's program-local ``BID``. A
    fleet sub-launch runs only its device's slice of the grid, but every
    block must still see its FLEET-level block id — saxpy's
    ``gid = BID*block + TDX`` has to address the same global elements no
    matter which device the block landed on. Default (None): block ``b``'s
    BID is its index within its own program's grid, the single-device
    behaviour, bit-identical to the pre-fleet device.

    ``queue_depth`` is the launch-queue depth at dispatch time — how many
    launches (including this one) the host had queued when it dispatched
    this one. The launch is charged ``dcfg.dispatch_latency +
    dcfg.queue_latency * queue_depth`` host cycles before any block
    issues (``scheduler.schedule_blocks(start_cycle=)``), modeling the
    dispatch path arXiv 2401.04261 measures; the charge is surfaced as
    ``profile()["host_dispatch"]``. The serving front door
    (``serve.LaunchServer``) wires its admission-queue depth in here;
    with the default zero latencies the model is free and the profile key
    is absent — bit-identical to the pre-serving device.
    """
    with tracing.span("egpu.launch"):
        # ---- plan: normalize, lower, resolve, look the plans up ---------
        with tracing.span("egpu.launch.plan"):
            kernels, gmap, shmems = _normalize_grid(
                dcfg, program, grid, block, dim_x, programs, grid_map,
                shmem)
            n_blocks = int(gmap.shape[0])
            bids = None
            if block_ids is not None:
                bids = np.asarray(list(block_ids), np.int64)
                if bids.shape != (n_blocks,):
                    raise ValueError(f"block_ids has shape {bids.shape}, "
                                     f"want ({n_blocks},)")
                if (bids < 0).any():
                    raise ValueError("block_ids must be non-negative")
            backend = backend or dcfg.backend
            mode = _resolve_schedule(schedule, dcfg, len(kernels))
            compile_cache.configure_jax_cache()

            # host dispatch latency (the launch-queue model)
            if queue_depth < 0:
                raise ValueError(f"queue_depth={queue_depth} must be >= 0")
            host_latency = dcfg.dispatch_latency \
                + dcfg.queue_latency * queue_depth
            host_dispatch = None
            if dcfg.dispatch_latency or dcfg.queue_latency:
                host_dispatch = {
                    "queue_depth": int(queue_depth),
                    "dispatch_cycles": int(dcfg.dispatch_latency),
                    "queue_cycles": int(dcfg.queue_latency * queue_depth),
                    "latency_cycles": int(host_latency),
                }

            # priority visibility: static waves ignore Kernel(priority=)
            prioritized = any(k.priority for k in kernels)
            priority_respected = (mode == "dynamic") or not prioritized
            if prioritized and mode == "static":
                _warn_static_priority()

            # per-program static resources
            names, cfgs, imems, traces, word_arrays = _lower_kernels(
                dcfg, kernels)
            eng, eng_fallback = _resolve_engine(engine, dcfg, traces)
            present = [k for k in range(len(kernels)) if (gmap == k).any()]
            # heterogeneous grids take the MERGED path on both compiled
            # engines: blocks of different programs share one wave,
            # executed either as a single scan over the padded merged
            # schedule (trace_engine.MergedTraceSchedule) or as per-slot
            # fused segments with globally-ordered gmem rows
            # (MergedMegakernelPlan)
            use_merged = eng in ("trace", "megakernel") and len(present) > 1
            # lower only the kernels that actually own blocks in this grid
            # (the merged path lowers through the same per-program compile
            # cache)
            scheds = [trace_engine.compile_program(w, c)
                      if eng == "trace" and not use_merged and k in present
                      else None
                      for k, (w, c) in enumerate(zip(word_arrays, cfgs))]
            plans = [trace_engine.compile_megakernel(w, c)
                     if eng == "megakernel" and not use_merged
                     and k in present else None
                     for k, (w, c) in enumerate(zip(word_arrays, cfgs))]

            with tracing.span("egpu.launch.schedule"):
                # wave packing: one membership decision for every layer.
                # The packer keys on each block's pre-decoded schedule
                # length (``trace.data_steps`` — the scan rows a merged
                # wave pads to, cached on the trace so repeated launches
                # pay nothing); the SAME WavePacking then shapes the merged
                # functional waves, the static wave timing, and the dynamic
                # queue's dispatch order
                phase_of_kernel = np.cumsum([int(k.barrier)
                                             for k in kernels])
                block_phase = phase_of_kernel[gmap]
                wp = pack_waves([traces[k].data_steps for k in gmap],
                                dcfg.n_sms,
                                policy=packing if packing is not None
                                else dcfg.packing,
                                phase_of=block_phase)

                # the schedule (timing)
                block_priority = np.asarray(
                    [kernels[k].priority for k in gmap], np.int64)
                block_traces = [traces[k] for k in gmap]
                timing = schedule_blocks(block_traces, dcfg.n_sms, mode,
                                         phase_of=block_phase,
                                         priority_of=block_priority,
                                         packing=wp,
                                         start_cycle=host_latency)
                if mode == "static":
                    static_span = timing.makespan
                else:
                    static_span = schedule_blocks(
                        block_traces, dcfg.n_sms, "static",
                        phase_of=block_phase, packing=wp,
                        start_cycle=host_latency).makespan

            # one merged plan per wave SIGNATURE (the programs present),
            # looked up once per signature: the packed membership decides
            # which signatures (multisets of (program, SMConfig) pairs)
            # ever get compiled
            wave_sigs, msched_of = [], {}
            if use_merged:
                compile_merged = trace_engine.compile_merged_megakernel \
                    if eng == "megakernel" else trace_engine.compile_merged
                for wave_ids in wp.waves:
                    sig = tuple(sorted({int(gmap[b]) for b in wave_ids}))
                    if sig not in msched_of:
                        msched_of[sig] = compile_merged(
                            [word_arrays[k] for k in sig],
                            [cfgs[k] for k in sig])
                    wave_sigs.append(sig)

        # ---- stage: the global-memory image and the shmem batches -------
        with tracing.span("egpu.launch.stage"):
            offsets = None
            if buffers is not None:
                if gmem is not None:
                    raise ValueError("pass either buffers= or gmem=, not "
                                     "both")
                gm, offsets = pack_buffers(buffers, dcfg.global_mem_depth)
            elif gmem is not None:
                gm = as_u32_image(gmem, dcfg.global_mem_depth,
                                  "global-memory")
            else:
                gm = jnp.zeros((dcfg.global_mem_depth,), _U32)
            # each present program's blocks, their program-local BIDs and
            # its shared-memory batch
            pos_of = {k: np.flatnonzero(gmap == k) for k in present}
            local_bid = np.zeros(n_blocks, np.int64)
            for k, pos in pos_of.items():
                local_bid[pos] = np.arange(pos.size)
            sh_batches = {k: _kernel_shmem(shmems[k], cfgs[k].shmem_depth,
                                           pos.size, k)
                          for k, pos in pos_of.items()}

        # ---- functional execution (exact lockstep batches) ---------------
        # each wave's (grid indices of its rows, regs, shmem, oob), in run
        # order; put into grid order once, after the last wave
        wave_outs: list[tuple[Any, ...]] = []
        wave_cycles, wave_steps = [], []
        machine_by = np.zeros((NUM_CLASSES,), np.int64)
        halted = True
        shmem_pad = dcfg.sm.shmem_depth
        merge_stats: dict[str, Any] | None = None
        if use_merged:
            # Heterogeneous waves: the wave packing decides which blocks
            # share a wave (grid order within each barrier phase under the
            # default policy; pad-minimal membership under "length" — a
            # merged wave never spans a fence either way) and each wave
            # runs as ONE merged scan. Cross-program global-memory
            # interactions inside a wave resolve in device order
            # (per-step, program-slot then (sm, thread) drain); as on real
            # hardware, blocks that may run concurrently must not race
            # through global memory — Kernel(barrier=True) is the fence
            # for cross-block dataflow, and under that contract results
            # are bit-identical to the step machine's canonical
            # program-major order for EVERY packing (pinned by
            # tests/test_conformance.py).
            run_merged = trace_engine.run_wave_merged_megakernel \
                if eng == "megakernel" else trace_engine.run_wave_merged
            engine_bid = bids if bids is not None else local_bid
            per_wave: list[dict[str, Any]] = []
            for wave_ids, sig in zip(wp.waves, wave_sigs):
                msched = msched_of[sig]
                with tracing.span("egpu.launch.stage"):
                    wave = np.asarray(wave_ids, np.int64)
                    slot = np.asarray([sig.index(int(gmap[b]))
                                       for b in wave])
                    # slot-major member order: each program's dispatch
                    # runs on a contiguous sub-batch (grid order kept
                    # within a slot)
                    order = np.argsort(slot, kind="stable")
                    blocks, slot = wave[order], slot[order]
                    counts = np.bincount(slot, minlength=len(sig))
                    n = blocks.size
                    pids = gmap[blocks]
                    # per-slot shared-memory init, padded to the device
                    # depth and concatenated along the slot-major member
                    # order
                    segs, off = [], 0
                    for j, k in enumerate(sig):
                        c = int(counts[j])
                        batch = sh_batches[k]
                        if batch is None:
                            segs.append(jnp.zeros((c, shmem_pad), _U32))
                        else:
                            img = batch[local_bid[blocks[off:off + c]]]
                            if img.shape[1] < shmem_pad:
                                img = jnp.pad(
                                    img,
                                    ((0, 0), (0, shmem_pad - img.shape[1])))
                            segs.append(img)
                        off += c
                    sh0 = jnp.concatenate(segs, axis=0)
                with tracing.span("egpu.launch.dispatch"):
                    regs_f, sh_f, gm, oob_f = run_merged(
                        backend, msched, counts, engine_bid[blocks], pids,
                        jnp.zeros((n, MAX_THREADS, N_REGS), _U32), sh0, gm,
                        jnp.zeros((n,), jnp.bool_))
                with tracing.span("egpu.launch.unpack"):
                    wave_outs.append((blocks, regs_f, sh_f, oob_f))
                    halted = halted and msched.halted
                    rec = {
                        "programs": [names[k] for k in sig],
                        "width": int(n),
                        "scan_steps": int(msched.n_steps),
                    }
                    if eng == "megakernel":
                        # fused segments execute no padded rows: short
                        # members simply stop fusing earlier, so the
                        # merge's only cross-slot cost is the
                        # globally-ordered gmem drains — surfaced as
                        # per-wave fusion stats instead
                        rec.update(padded_steps=0, pad_overhead=0.0,
                                   fusion=msched.stats())
                    else:
                        pad = int(msched.padded_steps(slot))
                        rows = int(msched.n_steps) * n
                        rec.update(padded_steps=pad,
                                   pad_overhead=(pad / rows) if rows
                                   else 0.0)
                    per_wave.append(rec)
        else:
            # homogeneous path: exact lockstep batches per program,
            # program-major
            for k, pos in pos_of.items():
                cfg, (lo, hi) = cfgs[k], imems[k]
                sh_batch = sh_batches[k]
                for w0 in range(0, pos.size, dcfg.n_sms):
                    w1 = min(w0 + dcfg.n_sms, pos.size)
                    n = w1 - w0
                    with tracing.span("egpu.launch.stage"):
                        st = init_device_state(
                            cfg, n, gmem_depth=dcfg.global_mem_depth,
                            shmem=None if sh_batch is None
                            else sh_batch[w0:w1],
                            gmem=gm)
                        bidx = jnp.arange(w0, w1, dtype=_I32) \
                            if bids is None \
                            else jnp.asarray(bids[pos[w0:w1]], _I32)
                        pidx = jnp.full((n,), k, dtype=_I32)
                    with tracing.span("egpu.launch.dispatch"):
                        if eng == "trace":
                            fin = trace_engine.run_wave_trace(
                                cfg, backend, scheds[k], bidx, pidx, st)
                        elif eng == "megakernel":
                            fin = trace_engine.run_wave_megakernel(
                                backend, plans[k], bidx, pidx, st)
                        else:
                            fin = run_wave(cfg, backend, lo, hi, bidx, pidx,
                                           st)
                    with tracing.span("egpu.launch.unpack"):
                        gm = fin.gmem           # batches run back to back
                        # a per-Kernel shmem_depth override is padded back
                        # to the device depth in _assemble_blocks
                        wave_outs.append((pos[w0:w1], fin.regs, fin.shmem,
                                          fin.oob))
                        wave_cycles.append(int(fin.cycles))
                        wave_steps.append(int(fin.steps))
                        machine_by += np.asarray(fin.cycles_by_class,
                                                 np.int64)
                        halted = halted and bool(fin.halted)

        # ---- unpack: aggregate counters and the result's arrays ----------
        with tracing.span("egpu.launch.unpack"):
            if use_merged:
                merge_stats = trace_engine.merge_profile(per_wave, wp.policy)
            if mode == "static" and len(kernels) == 1:
                # the lockstep fast path: one program, shared sequencer per
                # wave — report the batch machine's own counters
                # (bit-identical to the first device layer; the
                # host-dispatch charge precedes the first wave)
                cycles = int(sum(wave_cycles)) + int(host_latency)
                steps = int(sum(wave_steps))
                by_class = machine_by
                waves_out = np.asarray(wave_cycles, np.int64)
            else:
                # per-SM sequencers: every block issues its own trace
                cycles = timing.makespan
                steps = sum(t.steps for t in block_traces)
                by_class = np.zeros((NUM_CLASSES,), np.int64)
                for t in block_traces:
                    by_class += np.asarray(t.cycles_by_class(), np.int64)
                waves_out = timing.wave_cycles

            regs, shmem_out, oob = _assemble_blocks(wave_outs, shmem_pad)
            return LaunchResult(
                grid=(n_blocks,),
                block=cfgs[0].n_threads if len(kernels) == 1
                else tuple(c.n_threads for c in cfgs),
                n_waves=len(waves_out),
                regs=regs,
                shmem=shmem_out,
                gmem=gm,
                oob=oob,
                halted=halted,
                steps=steps,
                cycles=cycles,
                wave_cycles=np.asarray(waves_out, np.int64),
                cycles_by_class=by_class.astype(np.int64),
                buffer_offsets=offsets,
                schedule=mode,
                engine=eng,
                engine_fallback=eng_fallback,
                program_names=tuple(names),
                grid_map=gmap,
                timing=timing,
                static_cycles=static_span,
                trace_merge=merge_stats,
                packing=wp.policy,
                wave_packing=wp,
                host_dispatch=host_dispatch,
                priority_respected=priority_respected,
            )
