"""Trace-compiled execution engine: decode-once ``lax.scan`` pipelines.

The eGPU ISA has no data-dependent control flow — the sequence of
instructions a block issues is a *static* property of the program
(``cycles.program_trace``, exact). The stepping machine in ``device.py``
nevertheless re-fetches the 40-bit I-word, re-extracts every field, and
re-dispatches the handler switch on every ``lax.while_loop`` iteration,
and spends iterations on NOPs (hazard padding) and control flow that have
no architectural data effect. Following the soft-GPGPU compilation
argument (arXiv 2406.03227: close the gap to hand-built pipelines by
compiling the schedule ahead of time; arXiv 2401.04261: hoist dispatch
work off the per-cycle path), this module lowers a program ONCE into a
pre-decoded structure-of-arrays instruction schedule and executes it as a
single jitted ``lax.scan`` over the ``(n_sms, 512)`` lockstep batch:

  * decode happens at trace time, on the host: every issued instruction's
    fields (opcode, registers, immediates, snoop extensions, flexible-ISA
    active shape, handler id) become one row of the schedule;
  * control flow and NOPs vanish from the executed pipeline — their
    sequencer effects are pre-resolved by the trace walk, and their cycle
    costs are a static property already carried by ``ProgramTrace``;
  * the scan body dispatches straight into the shared execute stage
    (``executor.make_data_handlers``), the SAME handler graph the stepping
    machine uses, so the two engines are bit-identical by construction —
    on every backend ("inline" jnp and the "pallas" kernel path alike);
  * one compiled artifact exists per ``(program, SMConfig)``: schedules
    are held in a keyed cache (device-resident arrays, so repeated
    launches skip the host decode AND the host->device transfer), and
    XLA's jit cache keys the compiled scan on (config, backend, shapes).

``device.launch(..., engine="trace")`` routes every functional wave here
while the scheduler/timing layer is fed unchanged — cycle counters come
from the static trace (``trace.static_cycles`` / ``cycles_by_class``),
which the golden-cycle suite pins bit-equal to the stepping machine's.

Heterogeneous waves
-------------------
A mixed ``programs=[Kernel(...), ...]`` grid packs blocks of *different*
programs into one wave (the tight-packing deployment of arXiv
2401.04261). Per-program schedules are merged into ONE padded schedule
(``MergedTraceSchedule``): each program's structure-of-arrays columns are
padded to the longest participant with masked no-op rows and stacked into
``(n_steps, n_programs)`` matrices, so the whole ``(n_sms, 512)`` wave
still runs as a single jitted ``lax.scan``. Wave members are ordered
slot-major; each scan step dispatches every LIVE program's pre-decoded
instruction, in program-slot order, on that program's own contiguous SM
sub-batch — through the SAME ``executor.make_data_handlers`` execute
stage, so inline and Pallas backends work unchanged and step-vs-trace
bit-identity is preserved for every launch whose concurrently-resident
blocks do not race through global memory (the CUDA contract;
``Kernel(barrier=True)`` is the fence for cross-block dataflow, and
merged waves never span a barrier phase).
The merge cache is keyed on the multiset of ``(program, SMConfig)`` pairs
present in the wave; XLA's jit cache then keys the compiled scan on
(slot configs, backend, schedule length, wave width). Padding overhead is
surfaced per wave in ``LaunchResult.profile()["trace_merge"]``.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import tracing
from .cycles import ProgramTrace, program_trace
from .executor import (
    DATA_SEL_OF_OP,
    _decode,
    exec_segment,
    get_execute_backend,
    make_data_handlers,
)
from .machine import MAX_THREADS, N_SP, SMConfig

_I32 = jnp.int32

ENGINES = ("step", "trace", "megakernel")

# "auto" only picks the megakernel engine for programs whose schedules it
# can unroll body-to-body without exploding trace/compile time; longer
# schedules fall back to the scanned trace engine (engine_fallback =
# "megakernel-unroll-cap"). An explicit engine="megakernel" ignores the
# cap — the caller owns the compile-time trade.
MEGAKERNEL_UNROLL_CAP = 4096

# ...and only when there is enough fusible work to amortize the plan:
# the megakernel's win is keeping registers/shmem resident across fused
# gmem-free runs, but a short program (BENCH_engine.json's saxpy256_b64:
# 7 residual data rows after partial evaluation) spends its time in
# dispatch glue, measuring 0.81x vs the step machine. Below this many
# residual (non-gmem) data rows in the LONGEST program of the launch,
# "auto" falls back to "step" (engine_fallback = "megakernel-too-small").
# Step, not trace: the same artifact shows trace also losing to step on
# that shape (0.874x mega-vs-trace with mega at 0.811x of step), and the
# ISSUE's acceptance gate holds auto to >= 0.95x of the BEST fixed
# engine. An explicit engine= choice ignores the threshold.
MEGAKERNEL_MIN_FUSED_ROWS = 16

# decoded-field columns of the structure-of-arrays schedule, in the order
# they are packed into the (n_steps, len(_FIELDS)) i32 matrix
_FIELDS = ("sel", "opcode", "typ", "rd", "ra", "rb", "imm", "x",
           "ext_a", "ext_b", "pen", "preg", "pneg",
           "act_waves", "act_wthreads")


@dataclasses.dataclass(frozen=True)
class TraceSchedule:
    """One program lowered to a pre-decoded instruction schedule.

    ``xs[f]`` is the (n_steps,) i32 column for decoded field ``f`` — one
    row per *data* instruction of the issued trace (NOP/control rows are
    compiled out). ``trace`` keeps the full issued trace for timing;
    ``by_class_base``/``by_class_gmem`` pre-reduce its per-class cycle
    totals so per-wave counters are O(classes), not O(steps).
    """

    cfg: SMConfig
    trace: ProgramTrace
    xs: dict[str, jax.Array]
    by_class_base: np.ndarray       # (NUM_CLASSES,) trace.cycles_by_class(1)
    by_class_gmem: np.ndarray       # (NUM_CLASSES,) gmem-only cycle rows

    @property
    def n_steps(self) -> int:
        """Data instructions executed per block (decode-free scan length)."""
        return int(self.xs["sel"].shape[0])

    @property
    def halted(self) -> bool:
        return self.trace.halted

    def cycles_by_class(self, wave_n: int) -> np.ndarray:
        """== ``trace.cycles_by_class(wave_n)`` (GMEM scaled by the wave
        width), from the precomputed reductions."""
        return self.by_class_base + (wave_n - 1) * self.by_class_gmem


def _decode_words(words: np.ndarray) -> dict[str, np.ndarray]:
    """Decode an array of 40-bit I-words at lowering time, through the
    SAME ``executor._decode`` the stepping machine runs per step — one
    bit-layout definition, so the engines cannot drift (the trace engine
    must see exactly the stepping machine's fields, including the
    signed-immediate view of snoop extension bits)."""
    w = np.asarray(words, np.int64)
    lo = jnp.asarray(w & 0xFFFFFFFF, jnp.uint32)
    hi = jnp.asarray((w >> 32) & 0x3FFF, jnp.uint32)
    return {k: np.asarray(v) for k, v in _decode(lo, hi).items()}


@functools.lru_cache(maxsize=256)
def _compile_cached(words_key: tuple, cfg: SMConfig) -> TraceSchedule:
    with tracing.span("egpu.plan.lower"):
        return _lower(words_key, cfg)


def _lower(words_key: tuple, cfg: SMConfig) -> TraceSchedule:
    """One program's schedule: from the on-disk cache, else lowered and
    stored there."""
    from . import compile_cache

    ckey = compile_cache.key_for("lowering", words_key, cfg)
    payload = compile_cache.load(ckey)
    # a payload written before a _FIELDS extension (e.g. the predicate
    # columns) is stale — treat it as a miss and re-lower, or the scan
    # body KeyErrors on the missing column
    if payload is not None and set(_FIELDS) <= set(payload["cols"]):
        trace, cols = payload["trace"], payload["cols"]
    else:
        trace = program_trace(np.asarray(words_key, np.int64),
                              cfg.n_threads, imem_depth=cfg.imem_depth,
                              max_steps=cfg.max_steps)
        # data steps only: rows whose handler has an architectural data
        # effect
        sel_of = DATA_SEL_OF_OP
        pcs = np.asarray([t.pc for t in trace.instrs
                          if sel_of[int(t.op)] != 0], np.int64)
        # the wave packer bins on trace.data_steps; it must equal the rows
        # lowered here or "length" packing minimizes the wrong metric
        assert pcs.size == trace.data_steps, \
            "cycles.ProgramTrace.data_steps disagrees with DATA_SEL_OF_OP"
        # every data pc addresses a real program word (STOP padding is
        # control)
        assert pcs.size == 0 or pcs.max() < len(words_key), \
            "data instruction issued from STOP-padded I-MEM"
        words = np.asarray(words_key, np.int64)[pcs] if pcs.size \
            else np.zeros((0,), np.int64)
        d = _decode_words(words)
        n_waves = cfg.n_waves
        depth_table = np.array(
            [n_waves, max(1, n_waves // 2), max(1, n_waves // 4), 1],
            np.int64)
        width_table = np.array([16, 8, 4, 1], np.int64)
        cols = dict(
            sel=sel_of[d["opcode"]],
            opcode=d["opcode"], typ=d["typ"],
            rd=d["rd"], ra=d["ra"], rb=d["rb"],
            imm=d["imm"], x=d["x"], ext_a=d["ext_a"], ext_b=d["ext_b"],
            pen=d["pen"], preg=d["preg"], pneg=d["pneg"],
            act_waves=depth_table[d["depth"]],
            act_wthreads=width_table[d["width"]],
        )
        cols = {f: np.asarray(cols[f], np.int32) for f in _FIELDS}
        compile_cache.store(ckey, {"trace": trace, "cols": cols})
    xs = {f: jnp.asarray(cols[f]) for f in _FIELDS}
    from .isa import NUM_CLASSES

    by_base = np.asarray(trace.cycles_by_class(1), np.int64)
    by_gmem = np.zeros((NUM_CLASSES,), np.int64)
    for t in trace.instrs:
        if t.gmem:
            by_gmem[t.klass] += t.cycles
    return TraceSchedule(cfg=cfg, trace=trace, xs=xs,
                         by_class_base=by_base, by_class_gmem=by_gmem)


def compile_program(program, cfg: SMConfig) -> TraceSchedule:
    """Lower ``program`` (a Program or encoded word array) for ``cfg``.

    Idempotent and cached: the keyed compile cache holds one schedule per
    ``(program words, SMConfig)``; XLA's jit cache then holds one compiled
    scan per (SMConfig, backend, batch shape).
    """
    words = program.words if hasattr(program, "words") else program
    key = tuple(int(w) for w in words)
    return _compile_cached(key, cfg)


def compile_cache_info():
    return _compile_cached.cache_info()


def compile_cache_clear() -> None:
    _compile_cached.cache_clear()
    _merge_cached.cache_clear()
    _megakernel_cached.cache_clear()
    _megakernel_runner.cache_clear()
    _merged_megakernel_cached.cache_clear()
    _merged_megakernel_runner.cache_clear()


@functools.partial(jax.jit, static_argnums=(0, 1))
def _run_schedule(cfg: SMConfig, backend_name: str, xs, block_idx,
                  prog_idx, regs, shmem, gmem, oob):
    """Execute a pre-decoded schedule: ONE fixed-length scan, no decode,
    no dynamic pc, no halt test — dispatch is a 10-way switch on the
    precompiled handler id into the shared execute stage."""
    backend = get_execute_backend(backend_name)
    tid = jnp.arange(MAX_THREADS, dtype=_I32)
    lane = tid % N_SP
    wave = tid // N_SP

    def step(carry, x):
        active = (lane < x["act_wthreads"]) & (wave < x["act_waves"]) \
            & (tid < cfg.n_threads)
        handlers = make_data_handlers(cfg, backend, x, active, block_idx,
                                      prog_idx)
        return jax.lax.switch(x["sel"], handlers, carry), None

    # unroll=2 halves the scan's per-step loop overhead (measured ~8% on
    # the QRD schedule); deeper unrolls regress compile AND run time
    carry, _ = jax.lax.scan(step, (regs, shmem, gmem, oob), xs, unroll=2)
    return carry


# ---------------------------------------------------------------------------
# heterogeneous waves: merged multi-program schedules
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MergedTraceSchedule:
    """Several programs' schedules merged into one padded scan.

    ``xs[f]`` is the (n_steps, n_programs) i32 matrix for decoded field
    ``f``: column ``k`` is program ``k``'s schedule, padded to the longest
    participant with ``sel=0`` rows (the identity handler — a masked
    no-op, architecturally invisible). One scan over the rows executes a
    whole mixed wave; each step dispatches the participating programs in
    slot order, masked to the SMs running them.
    """

    cfgs: tuple[SMConfig, ...]          # per program slot
    parts: tuple[TraceSchedule, ...]    # the merged per-program schedules
    xs: dict[str, jax.Array]            # (n_steps, n_programs) i32
    # scan segments (start, end, live slots): the scan is split at every
    # program's schedule end, so a finished program drops out of the
    # dispatch loop instead of burning masked no-op dispatches — the
    # padded column rows past a program's end are never executed
    segments: tuple[tuple[int, int, tuple[int, ...]], ...]

    @property
    def n_steps(self) -> int:
        return int(self.xs["sel"].shape[0])

    @property
    def n_programs(self) -> int:
        return len(self.parts)

    @property
    def halted(self) -> bool:
        return all(p.halted for p in self.parts)

    def padded_steps(self, slot_idx) -> int:
        """Scan rows during which a wave member's program is already
        finished (the SM idles while the wave drains its longest
        participant), for a wave running the slots in ``slot_idx`` — the
        merge's padding overhead."""
        return sum(self.n_steps - self.parts[int(s)].n_steps
                   for s in slot_idx)


def merge_profile(per_wave: list, policy: str) -> dict:
    """Aggregate the per-wave merge records into the
    ``LaunchResult.profile()["trace_merge"]`` dict.

    ``per_wave`` entries carry each wave's ``scan_steps`` (merged
    schedule rows), ``width`` (members) and ``padded_steps`` (masked
    no-op rows of members shorter than the wave's longest participant).
    ``policy`` is the RESOLVED wave-packing policy that chose the
    membership (``core.packing``). ``pad_overhead_total`` is the
    launch-level aggregate the packer minimizes: the total padded scan
    steps summed over every merged wave (the per-wave ``padded_steps``
    aggregated); ``pad_overhead`` is that total as a fraction of all
    scheduled scan rows.
    """
    scanned = sum(w["scan_steps"] * w["width"] for w in per_wave)
    padded = sum(w["padded_steps"] for w in per_wave)
    out = {
        "policy": policy,
        "n_waves": len(per_wave),
        "scan_steps": scanned,          # scheduled scan rows x width
        "pad_overhead_total": padded,   # masked no-op rows of those —
                                        # the launch-level aggregate of
                                        # the per-wave padded_steps
        "pad_overhead": (padded / scanned) if scanned else 0.0,
        "per_wave": per_wave,
    }
    # megakernel waves additionally carry per-wave fusion stats —
    # aggregate them launch-wide so profiles expose how much of the
    # schedule ran fused vs through the serialized global port
    fus = [w["fusion"] for w in per_wave if "fusion" in w]
    if fus:
        out["fusion"] = {
            "segments": sum(f["segments"] for f in fus),
            "fused_rows": sum(f["fused_rows"] for f in fus),
            "folded_rows": sum(f["folded_rows"] for f in fus),
            "gmem_rows": sum(f["gmem_rows"] for f in fus),
            "max_fused_run": max(f["max_fused_run"] for f in fus),
        }
    return out


@functools.lru_cache(maxsize=256)
def _merge_cached(keys: tuple, cfgs: tuple) -> MergedTraceSchedule:
    with tracing.span("egpu.plan.merge"):
        parts = tuple(_compile_cached(k, c) for k, c in zip(keys, cfgs))
        n_steps = max(p.n_steps for p in parts)
        xs = {f: jnp.stack([jnp.pad(p.xs[f], (0, n_steps - p.n_steps))
                            for p in parts], axis=1)
              for f in _FIELDS}
        bounds = sorted({p.n_steps for p in parts} | {0})
        segments = tuple(
            (a, b, tuple(k for k, p in enumerate(parts) if p.n_steps >= b))
            for a, b in zip(bounds[:-1], bounds[1:]))
        return MergedTraceSchedule(cfgs=cfgs, parts=parts, xs=xs,
                                   segments=segments)


def compile_merged(programs, cfgs) -> MergedTraceSchedule:
    """Merge the schedules of ``programs`` (Programs or word arrays, one
    per ``SMConfig`` in ``cfgs``) into one padded heterogeneous-wave
    schedule. Cached on the multiset of ``(program words, SMConfig)``
    pairs (in slot order); the per-program lowerings are shared with
    ``compile_program``'s cache."""
    keys = []
    for p in programs:
        words = p.words if hasattr(p, "words") else p
        keys.append(tuple(int(w) for w in words))
    return _merge_cached(tuple(keys), tuple(cfgs))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _run_merged(cfgs: tuple, backend_name: str, segments: tuple,
                counts: tuple, xs, block_idx, prog_idx, regs, shmem,
                gmem, oob):
    """Execute one merged heterogeneous wave: one fixed-length scan per
    segment, each step dispatching the LIVE program slots' pre-decoded
    instructions, in slot order — the single global port drains one
    program's writers before the next program's, mirroring the per-cycle
    (sm, thread) drain discipline. Wave members arrive ordered slot-major
    (``counts[k]`` SMs per slot), so each dispatch runs the shared
    execute stage on its program's own contiguous sub-batch — no masked
    work on other programs' SMs. Segment boundaries sit at each program's
    schedule end, so the padded rows of finished programs cost nothing."""
    backend = get_execute_backend(backend_name)
    tid = jnp.arange(MAX_THREADS, dtype=_I32)
    lane = tid % N_SP
    wave = tid // N_SP
    offs = np.concatenate([[0], np.cumsum(counts)])
    carry = (regs, shmem, gmem, oob)

    for a, b, live in segments:
        def step(carry, x, live=live):
            regs, shmem, gmem, oob = carry
            for k in live:
                cfg = cfgs[k]
                lo, hi = int(offs[k]), int(offs[k + 1])
                d = {f: x[f][k] for f in _FIELDS}
                active = (lane < d["act_wthreads"]) \
                    & (wave < d["act_waves"]) & (tid < cfg.n_threads)
                handlers = make_data_handlers(
                    cfg, backend, d, active, block_idx[lo:hi],
                    prog_idx[lo:hi], shmem_depth=cfg.shmem_depth)
                sub = (regs[lo:hi], shmem[lo:hi], gmem, oob[lo:hi])
                r_k, s_k, gmem, o_k = jax.lax.switch(d["sel"], handlers,
                                                     sub)
                regs = jax.lax.dynamic_update_slice_in_dim(regs, r_k,
                                                           lo, 0)
                shmem = jax.lax.dynamic_update_slice_in_dim(shmem, s_k,
                                                            lo, 0)
                oob = jax.lax.dynamic_update_slice_in_dim(oob, o_k, lo, 0)
            return (regs, shmem, gmem, oob), None

        carry, _ = jax.lax.scan(step, carry,
                                {f: xs[f][a:b] for f in _FIELDS},
                                unroll=2)
    return carry


def run_wave_merged(backend: str, msched: MergedTraceSchedule,
                    counts: tuple, block_idx, prog_idx, regs, shmem,
                    gmem, oob):
    """Run one heterogeneous wave. Wave members MUST be ordered
    slot-major — ``counts[k]`` consecutive SMs run program slot ``k`` of
    the merged schedule (the device layer's merged dispatch orders them;
    cross-program global-store drains follow that device order).
    ``block_idx``/``prog_idx`` carry each SM's program-local ``BID`` and
    launch-wide ``PID``. ``shmem`` is the device-depth batch — programs
    with a shallower ``Kernel(shmem_depth=)`` override are bounds-checked
    at their own depth inside the execute stage. Returns
    (regs, shmem, gmem, oob)."""
    return _run_merged(msched.cfgs, backend, msched.segments,
                       tuple(int(c) for c in counts), msched.xs,
                       jnp.asarray(block_idx, _I32),
                       jnp.asarray(prog_idx, _I32), regs, shmem, gmem,
                       oob)


# ---------------------------------------------------------------------------
# segment megakernels: fused runs between global-port accesses
# ---------------------------------------------------------------------------
#
# The scanned trace engine still pays per-row dispatch: a 10-way
# ``lax.switch`` on the handler id plus traced decoded fields and a
# recomputed active mask, every scan step. But every field of every row
# is a HOST constant — so the megakernel engine unrolls each *segment*
# (the maximal run of SM-local rows between global-port accesses; GLD/GST
# rows serialize on the one device-wide port and so delimit segments)
# body-to-body with constant fields and constant masks, and hands the
# whole run to the ``ExecBackend.segment`` seam as ONE fused kernel. The
# switch, the mask arithmetic and the operand selects fold away at trace
# time; the Pallas implementation additionally keeps the SM batch's
# registers/shmem resident in VMEM across the fused steps
# (``kernels.simt_step.simt_segment``). Gmem rows between segments still
# dispatch through the same per-row handlers as the scan.
#
# Functionally the megakernel engine IS the trace engine — same rows,
# same handler graph (``executor.make_data_handlers``), same counters
# from the static trace — so it is bit-identical to both other engines
# by construction. Only compile strategy changes.

def _active_mask(cfg: SMConfig, act_waves: int, act_wthreads: int
                 ) -> np.ndarray:
    """The (512,) flexible-ISA thread mask of one row, as a host
    constant — exactly the scan body's per-step mask computation."""
    tid = np.arange(MAX_THREADS)
    lane = tid % N_SP
    wave = tid // N_SP
    return ((lane < act_wthreads) & (wave < act_waves)
            & (tid < cfg.n_threads))


def _fused_rows(sched: TraceSchedule) -> tuple:
    """Lower a schedule's rows to host-constant ``executor.FusedRow``s."""
    from .executor import FusedRow

    cols = {f: np.asarray(sched.xs[f]) for f in _FIELDS}
    rows = []
    for i in range(sched.n_steps):
        d = {f: np.int32(cols[f][i]) for f in
             ("opcode", "typ", "rd", "ra", "rb", "imm", "x", "ext_a",
              "ext_b", "pen", "preg", "pneg")}
        waves = int(cols["act_waves"][i])
        wthreads = int(cols["act_wthreads"][i])
        rows.append(FusedRow(
            sel=int(cols["sel"][i]), d=d,
            active=_active_mask(sched.cfg, waves, wthreads),
            act_waves=waves, act_wthreads=wthreads))
    return tuple(rows)


_GMEM_SELS = (8, 9)        # GLD/GST data-switch branches (the global port)


def _segment_items(rows, slot: int | None = None) -> tuple:
    """Split a row sequence at global-port rows: ``("fused", slot, rows)``
    runs as one fused kernel, ``("gmem", slot, row)`` dispatches the
    serialized port row by itself."""
    items, run = [], []
    for r in rows:
        if r.sel in _GMEM_SELS:
            if run:
                items.append(("fused", slot, tuple(run)))
                run = []
            items.append(("gmem", slot, r))
        else:
            run.append(r)
    if run:
        items.append(("fused", slot, tuple(run)))
    return tuple(items)


def _partial_eval_items(items, cfg_of, depth_of) -> tuple:
    """Run the plan-time partial evaluator over a segment item list.

    Threads per-slot register-column constant state (starting from the
    zero-init wave contract: ``device.init_device_state`` always zeroes
    registers) through the plan in execution order, wrapping every fused
    payload in an ``executor.FusedSegment``. A GLD row makes its
    destination runtime; GST reads only. ``cfg_of``/``depth_of`` map the
    slot tag of each item to its SMConfig / shared-memory depth."""
    from .executor import eval_segment_rows
    from .machine import N_REGS

    with tracing.span("egpu.plan.partial_eval"):
        state: dict = {}
        out = []
        for kind, slot, payload in items:
            cols = state.setdefault(
                slot, [np.zeros(MAX_THREADS, np.uint32)] * N_REGS)
            if kind == "fused":
                seg, cols = eval_segment_rows(cfg_of(slot), payload, cols,
                                              depth_of(slot))
                state[slot] = cols
                out.append((kind, slot, seg))
            else:
                if payload.sel == 8:                # GLD: rd now runtime
                    cols = list(cols)
                    cols[int(payload.d["rd"])] = None
                    state[slot] = cols
                out.append((kind, slot, payload))
        return tuple(out)


def _fusion_stats(items) -> dict:
    segs = [it[2] for it in items if it[0] == "fused"]
    return {
        "segments": len(segs),
        "fused_rows": sum(len(s.rows) for s in segs),
        "folded_rows": sum(s.n_folded for s in segs),
        "gmem_rows": sum(1 for it in items if it[0] == "gmem"),
        "max_fused_run": max((len(s.rows) for s in segs), default=0),
    }


@dataclasses.dataclass(frozen=True)
class MegakernelPlan:
    """One program lowered to fused segments (megakernel engine unit).

    ``items`` is the ordered execution plan; ``sched`` keeps the
    underlying trace schedule for the timing model (cycle counters are
    engine-independent — the megakernel is a functional-path
    optimization only).
    """

    key: tuple                 # program words (the compile-cache key)
    cfg: SMConfig
    sched: TraceSchedule
    items: tuple

    @property
    def halted(self) -> bool:
        return self.sched.halted

    def stats(self) -> dict:
        return _fusion_stats(self.items)


@functools.lru_cache(maxsize=256)
def _megakernel_cached(words_key: tuple, cfg: SMConfig) -> MegakernelPlan:
    sched = _compile_cached(words_key, cfg)
    items = _partial_eval_items(
        _segment_items(_fused_rows(sched)),
        lambda _s: cfg, lambda _s: cfg.shmem_depth)
    return MegakernelPlan(key=words_key, cfg=cfg, sched=sched, items=items)


def compile_megakernel(program, cfg: SMConfig) -> MegakernelPlan:
    """Lower ``program`` to a fused-segment megakernel plan for ``cfg``.

    Cached like ``compile_program`` (and sharing its schedule cache); the
    jitted runner is cached separately per (program, config, backend)."""
    words = program.words if hasattr(program, "words") else program
    return _megakernel_cached(tuple(int(w) for w in words), cfg)


@functools.lru_cache(maxsize=256)
def _megakernel_runner(words_key: tuple, cfg: SMConfig, backend_name: str):
    """The jitted homogeneous-wave megakernel for one (program, config,
    backend). The plan is closed over, not passed: its rows hold
    unhashable host constants, and closing over it keys XLA's jit cache
    on exactly (plan identity, batch shapes). The function's name names
    the XLA module, so a profile's device operations say which runner
    ran."""
    plan = _megakernel_cached(words_key, cfg)
    backend = get_execute_backend(backend_name)

    @jax.jit
    def egpu_wave_megakernel(block_idx, prog_idx, regs, shmem, gmem, oob):
        for kind, _, payload in plan.items:
            if kind == "fused":
                regs, shmem, oob = exec_segment(
                    backend, cfg, payload, block_idx, prog_idx, regs,
                    shmem, oob)
            else:
                handlers = make_data_handlers(cfg, backend, payload.d,
                                              jnp.asarray(payload.active),
                                              block_idx, prog_idx)
                regs, shmem, gmem, oob = handlers[payload.sel](
                    (regs, shmem, gmem, oob))
        return regs, shmem, gmem, oob

    return egpu_wave_megakernel


def run_wave_megakernel(backend: str, plan: MegakernelPlan, block_idx,
                        prog_idx, state):
    """Megakernel replacement for ``run_wave_trace``: same DeviceState
    in/out contract, same static-trace counters — only the functional
    path changes (fused segments instead of a scanned schedule)."""
    n = state.regs.shape[0]
    fn = _megakernel_runner(plan.key, plan.cfg, backend)
    regs, shmem, gmem, oob = fn(
        jnp.asarray(block_idx, _I32), jnp.asarray(prog_idx, _I32),
        state.regs, state.shmem, state.gmem, state.oob)
    tr = plan.sched.trace
    return state.replace(
        regs=regs, shmem=shmem, gmem=gmem, oob=oob,
        halted=state.halted | jnp.asarray(tr.halted),
        steps=state.steps + jnp.int32(tr.steps),
        cycles=state.cycles + jnp.int32(tr.static_cycles(n)),
        cycles_by_class=state.cycles_by_class
        + jnp.asarray(plan.sched.cycles_by_class(n), _I32),
    )


@dataclasses.dataclass(frozen=True)
class MergedMegakernelPlan:
    """A heterogeneous wave's fused-segment plan.

    Unlike ``MergedTraceSchedule`` there is NO padding: each slot's rows
    fuse independently, and only the global-port rows impose a global
    order — they drain in (scan step, program slot) lexicographic order,
    exactly the merged scan's dispatch order, so cross-program
    global-store drains stay bit-identical to the scan and the step
    machine.
    """

    keys: tuple                # per-slot program words
    cfgs: tuple[SMConfig, ...]
    parts: tuple[TraceSchedule, ...]
    items: tuple               # ("fused"|"gmem", slot, payload)

    @property
    def halted(self) -> bool:
        return all(p.halted for p in self.parts)

    @property
    def n_steps(self) -> int:
        """Longest participant's schedule (the merged scan's row count —
        kept for profile continuity; the megakernel executes no padded
        rows)."""
        return max((p.n_steps for p in self.parts), default=0)

    def stats(self) -> dict:
        return _fusion_stats(self.items)


@functools.lru_cache(maxsize=256)
def _merged_megakernel_cached(keys: tuple, cfgs: tuple
                              ) -> MergedMegakernelPlan:
    with tracing.span("egpu.plan.merge"):
        parts = tuple(_compile_cached(k, c) for k, c in zip(keys, cfgs))
        slot_rows = [_fused_rows(p) for p in parts]
        # global-port rows must drain in the merged scan's dispatch order:
        # (schedule step, slot order) — between them, different slots' rows
        # touch disjoint per-SM state and commute, so each slot's runs fuse
        # independently and flush only when one of its gmem rows comes due
        events = sorted((i, k) for k, rows in enumerate(slot_rows)
                        for i, r in enumerate(rows) if r.sel in _GMEM_SELS)
        cursor = [0] * len(parts)
        items = []
        for i, k in events:
            if cursor[k] < i:
                items.append(("fused", k, tuple(slot_rows[k][cursor[k]:i])))
            items.append(("gmem", k, slot_rows[k][i]))
            cursor[k] = i + 1
        for k, rows in enumerate(slot_rows):
            if cursor[k] < len(rows):
                items.append(("fused", k, tuple(rows[cursor[k]:])))
        items = _partial_eval_items(
            tuple(items), lambda s: cfgs[s], lambda s: cfgs[s].shmem_depth)
        return MergedMegakernelPlan(keys=keys, cfgs=cfgs, parts=parts,
                                    items=items)


def compile_merged_megakernel(programs, cfgs) -> MergedMegakernelPlan:
    """Megakernel counterpart of ``compile_merged``: fuse each slot's
    segments, ordering only the global-port rows across slots."""
    keys = []
    for p in programs:
        words = p.words if hasattr(p, "words") else p
        keys.append(tuple(int(w) for w in words))
    return _merged_megakernel_cached(tuple(keys), tuple(cfgs))


@functools.lru_cache(maxsize=256)
def _merged_megakernel_runner(keys: tuple, cfgs: tuple,
                              backend_name: str):
    mplan = _merged_megakernel_cached(keys, cfgs)
    backend = get_execute_backend(backend_name)

    @functools.partial(jax.jit, static_argnums=(0,))
    def egpu_wave_merged_megakernel(counts, block_idx, prog_idx, regs, shmem,
                                    gmem, oob):
        offs = np.concatenate([[0], np.cumsum(counts)])
        for kind, k, payload in mplan.items:
            cfg = cfgs[k]
            lo, hi = int(offs[k]), int(offs[k + 1])
            if kind == "fused":
                r_k, s_k, o_k = exec_segment(
                    backend, cfg, payload, block_idx[lo:hi],
                    prog_idx[lo:hi], regs[lo:hi], shmem[lo:hi],
                    oob[lo:hi], shmem_depth=cfg.shmem_depth)
            else:
                handlers = make_data_handlers(
                    cfg, backend, payload.d, jnp.asarray(payload.active),
                    block_idx[lo:hi], prog_idx[lo:hi],
                    shmem_depth=cfg.shmem_depth)
                sub = (regs[lo:hi], shmem[lo:hi], gmem, oob[lo:hi])
                r_k, s_k, gmem, o_k = handlers[payload.sel](sub)
            regs = regs.at[lo:hi].set(r_k)
            shmem = shmem.at[lo:hi].set(s_k)
            oob = oob.at[lo:hi].set(o_k)
        return regs, shmem, gmem, oob

    return egpu_wave_merged_megakernel


def run_wave_merged_megakernel(backend: str, mplan: MergedMegakernelPlan,
                               counts: tuple, block_idx, prog_idx, regs,
                               shmem, gmem, oob):
    """Run one heterogeneous wave on the megakernel engine. Same
    slot-major member-ordering contract as ``run_wave_merged``; returns
    (regs, shmem, gmem, oob)."""
    fn = _merged_megakernel_runner(mplan.keys, mplan.cfgs, backend)
    return fn(tuple(int(c) for c in counts),
              jnp.asarray(block_idx, _I32), jnp.asarray(prog_idx, _I32),
              regs, shmem, gmem, oob)


def run_wave_trace(cfg: SMConfig, backend: str, sched: TraceSchedule,
                   block_idx, prog_idx, state):
    """Trace-engine replacement for ``device.run_wave``: same DeviceState
    in/out contract, counters synthesized from the static trace (identical
    to the stepping machine's — the lockstep wave rule charges each member
    for the whole wave's port drain, ``trace.static_cycles``)."""
    n = state.regs.shape[0]
    regs, shmem, gmem, oob = _run_schedule(
        cfg, backend, sched.xs, jnp.asarray(block_idx, _I32),
        jnp.asarray(prog_idx, _I32), state.regs, state.shmem, state.gmem,
        state.oob)
    tr = sched.trace
    return state.replace(
        regs=regs, shmem=shmem, gmem=gmem, oob=oob,
        halted=state.halted | jnp.asarray(tr.halted),
        steps=state.steps + jnp.int32(tr.steps),
        cycles=state.cycles + jnp.int32(tr.static_cycles(n)),
        cycles_by_class=state.cycles_by_class
        + jnp.asarray(sched.cycles_by_class(n), _I32),
    )
