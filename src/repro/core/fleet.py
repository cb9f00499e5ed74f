"""Device fleet: N simulated eGPUs behind the one ``launch()`` front door.

The eGPU paper closes on the claim that "multiple eGPUs can also be
tightly packed together into a single Agilex FPGA logic region, with
minimal speed penalty", and the scalable follow-up (arXiv 2401.04261)
makes the device count a first-class scaling axis next to the SM count.
This module models that axis: :class:`FleetConfig` describes ``n_devices``
identical eGPUs (each a full ``DeviceConfig`` sector — its own SMs, its
own global-memory port), and :func:`launch_fleet` routes one grid across
them.

Contracts, in order of importance:

* **Bit-identical function.** A fleet launch computes exactly what the
  single-device ``device.launch`` computes on the same grid, for every
  ``n_devices`` — blocks keep their fleet-level ``BID`` no matter which
  device they land on (the ``launch(block_ids=)`` router seam), barrier
  phases stay device-wide fences (a phase retires on EVERY device before
  the next issues anywhere), and per-device global-memory images are
  diff-merged against the phase's base image in device order. Under the
  standard launch contract (same-phase blocks don't race through gmem)
  the merge is exact: each device's sub-launch changes disjoint words.
  ``fleet(n_devices=1)`` simply IS the plain launch (delegation, not
  re-implementation).

* **A NUMA tier in the cycle model.** Each simulated device owns a local
  slice of the shared global memory; blocks routed off
  ``FleetConfig(home_device=)`` pay ``remote_gmem_latency`` extra cycles
  per global access (their static traces are re-priced before
  scheduling, so the charge flows through the same static/dynamic
  machinery, the makespan, and ``cycles_by_class`` — golden-pinnable
  like every other cycle). The default latency of 0 models the paper's
  tightly-packed single-region fleet.

* **Real JAX devices underneath.** When the workload is uniform enough
  (one program, one phase, a halting trace, equal per-device block
  counts) and jax exposes enough devices, the functional execution runs
  as ONE ``shard_map`` over the ``"fleet"`` mesh axis
  (``launch.mesh.make_fleet_mesh`` + ``launch.shardings.fleet_spec``):
  every simulated eGPU executes its block slice on its own XLA device
  against its own gmem replica, and the replicas diff-merge exactly like
  the host path. The mapped program is jitted once per launch shape
  (``_fleet_program``), and each device's inputs go from the host
  straight onto that device. ``placement="auto"`` (default) uses it when it can and
  records why not when it can't (``profile()["fleet"]["placement"]`` /
  ``["placement_reason"]``); ``"host"`` forces the per-device host loop;
  ``"shard_map"`` raises when the preconditions fail instead of
  silently degrading.

Timing: the fleet schedule is the union of per-device schedules
(``scheduler.merge_schedules``) — device ``d`` owns SMs
``[d*n_sms, (d+1)*n_sms)`` of the fleet view, each phase starts
everywhere at the previous phase's fleet-wide retire (max over devices),
and the makespan is the last retire anywhere. Near-linear throughput
scaling on mixed grids is pinned by ``benchmarks/fleet_bench.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..launch.mesh import make_fleet_mesh
from ..launch.shardings import fleet_spec
from . import compile_cache, trace_engine, tracing
from .cycles import ProgramTrace
from .device import (
    _U32,
    DeviceConfig,
    LaunchResult,
    _kernel_shmem,
    _lower_kernels,
    _normalize_grid,
    _resolve_engine,
    _resolve_schedule,
    as_u32_image,
    launch,
    pack_buffers,
)
from .isa import NUM_CLASSES
from .machine import MAX_THREADS, N_REGS
from .packing import pack_waves
from .scheduler import merge_schedules, schedule_blocks

ROUTES = ("block", "kernel")
PLACEMENTS = ("auto", "host", "shard_map")

_I32 = jnp.int32


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """N identical simulated eGPUs sharing one launch front door.

    ``device`` is the per-device sector configuration (every device is
    identical — the paper packs copies of one layout). ``route`` picks
    the block router: ``"block"`` splits each barrier phase's blocks
    into ``n_devices`` contiguous grid-order ranges (balanced to within
    one block); ``"kernel"`` sends program ``k``'s blocks to device
    ``k % n_devices`` (whole kernels stay device-local — the natural
    router for mixed grids whose programs shouldn't share a port).
    ``remote_gmem_latency`` is the NUMA tier: extra cycles per global
    access for blocks running off ``home_device``. ``placement`` picks
    where the functional execution runs (see module docstring).
    """

    n_devices: int = 1
    device: DeviceConfig = dataclasses.field(default_factory=DeviceConfig)
    remote_gmem_latency: int = 0
    home_device: int = 0
    route: str = "block"
    placement: str = "auto"

    def __post_init__(self):
        if self.n_devices < 1:
            raise ValueError(f"n_devices={self.n_devices} must be >= 1")
        if self.remote_gmem_latency < 0:
            raise ValueError(f"remote_gmem_latency="
                             f"{self.remote_gmem_latency} must be >= 0")
        if not 0 <= self.home_device < self.n_devices:
            raise ValueError(f"home_device={self.home_device} outside "
                             f"[0, {self.n_devices})")
        if self.route not in ROUTES:
            raise ValueError(f"route={self.route!r} must be one of "
                             f"{ROUTES}")
        if self.placement not in PLACEMENTS:
            raise ValueError(f"placement={self.placement!r} must be one "
                             f"of {PLACEMENTS}")

    @property
    def n_sms(self) -> int:
        """Total SMs across the fleet."""
        return self.n_devices * self.device.n_sms


def _remote_trace(trace: ProgramTrace, lat: int) -> ProgramTrace:
    """Re-price a static trace for a non-home device: every global-port
    access costs ``lat`` extra cycles (the NUMA tier). The re-priced
    trace flows through the ordinary static/dynamic schedulers and
    ``cycles_by_class`` — the charge is just cycles, not a new
    mechanism."""
    if lat == 0:
        return trace
    instrs = tuple(dataclasses.replace(i, cycles=i.cycles + lat)
                   if i.gmem else i for i in trace.instrs)
    return dataclasses.replace(trace, instrs=instrs)


def _route_blocks(fcfg: FleetConfig, gmap: np.ndarray,
                  block_phase: np.ndarray) -> np.ndarray:
    """(n_blocks,) device index per block. Contiguous grid-order ranges
    per phase ("block"), or program-keyed ("kernel")."""
    n_blocks = gmap.shape[0]
    device_of = np.zeros(n_blocks, np.int64)
    if fcfg.route == "kernel":
        device_of[:] = gmap % fcfg.n_devices
        return device_of
    for p in np.unique(block_phase):
        idx = np.flatnonzero(block_phase == p)
        for d, chunk in enumerate(np.array_split(idx, fcfg.n_devices)):
            device_of[chunk] = d
    return device_of


def _resolve_placement(fcfg: FleetConfig, kernels, gmap, block_phase,
                       traces, eng: str) -> tuple[str, str]:
    """Decide host vs shard_map; returns ``(placement, reason)``."""
    if fcfg.placement == "host":
        return "host", "requested"
    n = fcfg.n_devices
    reasons = []
    if len({int(k) for k in gmap}) != 1:
        reasons.append("mixed-program grid")
    if np.unique(block_phase).size != 1:
        reasons.append("multi-phase (barrier) launch")
    if not all(t.halted for t in traces):
        reasons.append("fuel-limited trace")
    if gmap.shape[0] % n != 0:
        reasons.append(f"{gmap.shape[0]} blocks not divisible by "
                       f"{n} devices")
    if fcfg.route != "block":
        reasons.append(f"route={fcfg.route!r} is not block-contiguous")
    if n > len(jax.devices()):
        reasons.append(f"jax exposes {len(jax.devices())} device(s) < "
                       f"{n}")
    if not reasons:
        return "shard_map", "uniform single-program single-phase grid"
    reason = "; ".join(reasons)
    if fcfg.placement == "shard_map":
        raise ValueError(f"placement='shard_map' unavailable: {reason}")
    return "host", reason


# one jitted shard_map program per launch shape (see _fleet_program)
_FLEET_PROGRAMS: dict[tuple, tuple[Any, NamedSharding, NamedSharding]] = {}
# each schedule's columns, replicated once per mesh: (id, sharding) ->
# (schedule, placed columns)
_PLACED_XS: dict[tuple[int, NamedSharding], tuple[Any, Any]] = {}


def _fleet_program(n_dev: int, per: int, n_sms: int, cfg, backend: str,
                   n_steps: int):
    """``(program, sharded, replicated)``: the jitted ``shard_map`` that
    runs ``per`` blocks on each of ``n_dev`` devices, waves of ``n_sms``
    back to back against the device's own gmem replica, and the input
    shardings it takes. Built once per launch shape; the schedule
    (``xs``) is an argument, so programs of one length share it."""
    key = (n_dev, per, n_sms, cfg, backend, n_steps)
    hit = _FLEET_PROGRAMS.get(key)
    if hit is not None:
        return hit
    mesh = make_fleet_mesh(n_dev)
    spec = fleet_spec()

    def body(xs, bidx, pidx, sh, gmem):
        bidx, pidx, sh = bidx[0], pidx[0], sh[0]
        regs, shs, oobs = [], [], []
        # the same chunking as the single-device homogeneous path
        for w0 in range(0, per, n_sms):
            w1 = min(w0 + n_sms, per)
            r, s, gmem, o = trace_engine._run_schedule(
                cfg, backend, xs, bidx[w0:w1], pidx[w0:w1],
                jnp.zeros((w1 - w0, MAX_THREADS, N_REGS), _U32),
                sh[w0:w1], gmem, jnp.zeros((w1 - w0,), jnp.bool_))
            regs.append(r)
            shs.append(s)
            oobs.append(o)
        return (jnp.concatenate(regs)[None], jnp.concatenate(shs)[None],
                gmem[None], jnp.concatenate(oobs)[None])

    # check_vma=False: the Pallas backend's kernels declare plain output
    # shapes, with no varying-manual-axes annotation to check
    program = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), spec, spec, spec, P()),
        out_specs=(spec,) * 4, check_vma=False))
    hit = (program, NamedSharding(mesh, spec), NamedSharding(mesh, P()))
    _FLEET_PROGRAMS[key] = hit
    return hit


def _host_u32(arr, depth: int, what: str) -> np.ndarray:
    """``machine.as_u32_image`` on the host: the same words, in numpy."""
    a = np.asarray(arr)
    a = np.ascontiguousarray(a, jax.dtypes.canonicalize_dtype(a.dtype))
    a = a.view(np.uint32) if a.dtype == np.float32 else a.astype(np.uint32)
    pad = depth - a.shape[-1]
    if pad < 0:
        raise ValueError(f"{what} image of {a.shape[-1]} words exceeds "
                         f"depth {depth}")
    if pad:
        a = np.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
    return a


def _stage_shard_map(fcfg: FleetConfig, backend: str, cfg, words,
                     local_bid, device_of, shmem, gm):
    """Everything the mapped call takes, each device's slice placed from
    the host straight onto its own JAX device: ``(program, args, order,
    sched)``, ``order`` the grid blocks in device-major order."""
    n_dev = fcfg.n_devices
    n_blocks = local_bid.shape[0]
    per = n_blocks // n_dev
    sched = trace_engine.compile_program(words, cfg)
    program, sharded, replicated = _fleet_program(
        n_dev, per, fcfg.device.n_sms, cfg, backend, sched.n_steps)
    # route="block" on a single phase is contiguous by construction
    order = np.concatenate([np.flatnonzero(device_of == d)
                            for d in range(n_dev)])
    depth = cfg.shmem_depth
    if shmem is None:
        sh = np.zeros((n_blocks, depth), np.uint32)
    else:
        batch = _host_u32(shmem, depth, "shared-memory (program 0)")
        if batch.ndim == 1:
            batch = np.broadcast_to(batch, (n_blocks, depth))
        elif batch.shape[0] != n_blocks:
            raise ValueError(f"shared-memory batch of {batch.shape[0]} "
                             f"images != {n_blocks} blocks of program 0")
        sh = batch[local_bid[order]]
    bid, pid, sh = jax.device_put(
        (local_bid[order].astype(np.int32).reshape(n_dev, per),
         np.zeros((n_dev, per), np.int32), sh.reshape(n_dev, per, depth)),
        sharded)
    key = (id(sched), replicated)
    if key not in _PLACED_XS:   # the value keeps sched, so no id recurs
        _PLACED_XS[key] = (sched, jax.device_put(sched.xs, replicated))
    xs = _PLACED_XS[key][1]
    gm0 = jax.device_put(np.asarray(gm), replicated)
    return program, (xs, bid, pid, sh, gm0), order, sched


def launch_fleet(fcfg: FleetConfig, program=None, grid=None,
                 block: int | None = None, *,
                 programs: Sequence[Any] | None = None,
                 grid_map: Sequence[int] | None = None,
                 buffers: Mapping[str, Any] | None = None,
                 shmem: Any = None, gmem: Any = None,
                 backend: str | None = None, dim_x: int | None = None,
                 schedule: str | None = None,
                 engine: str | None = None,
                 packing: str | None = None,
                 queue_depth: int = 0) -> LaunchResult:
    """CUDA-style launch across a fleet of simulated eGPUs.

    Same two grid forms, same keyword surface, and bit-identical
    functional results as :func:`core.device.launch` on one device —
    the fleet only changes where blocks run and what the cycle model
    charges. The returned :class:`LaunchResult` carries the fleet view
    in ``result.fleet`` / ``profile()["fleet"]``: per-device occupancy,
    the routing, the resolved placement, and the NUMA charge.
    """
    dcfg = fcfg.device
    if fcfg.n_devices == 1:
        res = launch(dcfg, program, grid, block, programs=programs,
                     grid_map=grid_map, buffers=buffers, shmem=shmem,
                     gmem=gmem, backend=backend, dim_x=dim_x,
                     schedule=schedule, engine=engine, packing=packing,
                     queue_depth=queue_depth)
        t = res.timing
        res.fleet = {
            "n_devices": 1, "route": fcfg.route, "placement": "host",
            "placement_reason": "single-device fleet is the plain device",
            "remote_gmem_latency": int(fcfg.remote_gmem_latency),
            "remote_gmem_cycles": 0,
            "per_device": [{
                "device": 0, "home": fcfg.home_device == 0,
                "blocks": res.n_blocks,
                "busy": int(t.sm_busy.sum()) if t is not None else 0,
                "wait": int(t.sm_wait.sum()) if t is not None else 0,
                "idle": int(t.sm_idle.sum()) if t is not None else 0,
                "makespan": int(res.cycles),
            }],
        }
        return res

    with tracing.span("egpu.launch_fleet"):
        return _launch_fleet(fcfg, program, grid, block, programs,
                             grid_map, buffers, shmem, gmem, backend, dim_x,
                             schedule, engine, packing, queue_depth)


def _launch_fleet(fcfg: FleetConfig, program, grid, block, programs,
                  grid_map, buffers, shmem, gmem, backend, dim_x, schedule,
                  engine, packing, queue_depth) -> LaunchResult:
    """``launch_fleet`` on two or more devices, one span per phase."""
    dcfg = fcfg.device
    # ---- plan: normalize + lower exactly like the single device, route,
    # place --------------------------------------------------------------
    with tracing.span("egpu.fleet.plan"):
        kernels, gmap, shmems = _normalize_grid(dcfg, program, grid, block,
                                                dim_x, programs, grid_map,
                                                shmem)
        n_blocks = int(gmap.shape[0])
        backend = backend or dcfg.backend
        mode = _resolve_schedule(schedule, dcfg, len(kernels))
        compile_cache.configure_jax_cache()
        names, cfgs, imems, traces, word_arrays = _lower_kernels(dcfg,
                                                                 kernels)
        eng, eng_fallback = _resolve_engine(engine, dcfg, traces)

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

        phase_of_kernel = np.cumsum([int(k.barrier) for k in kernels])
        block_phase = phase_of_kernel[gmap]
        device_of = _route_blocks(fcfg, gmap, block_phase)
        local_bid = np.zeros(n_blocks, np.int64)
        for k in range(len(kernels)):
            pos = np.flatnonzero(gmap == k)
            local_bid[pos] = np.arange(pos.size)
        placement, placement_reason = _resolve_placement(
            fcfg, kernels, gmap, block_phase, traces, eng)

    # ---- stage: the global-memory image ---------------------------------
    with tracing.span("egpu.fleet.stage"):
        offsets = None
        if buffers is not None:
            if gmem is not None:
                raise ValueError("pass either buffers= or gmem=, not both")
            gm, offsets = pack_buffers(buffers, dcfg.global_mem_depth)
        elif gmem is not None:
            gm = as_u32_image(gmem, dcfg.global_mem_depth, "global-memory")
        else:
            gm = jnp.zeros((dcfg.global_mem_depth,), _U32)

    # ---- functional execution -------------------------------------------
    regs_slots: list[Any] = [None] * n_blocks
    shmem_slots: list[Any] = [None] * n_blocks
    oob_slots: list[Any] = [None] * n_blocks
    halted = True
    shmem_pad = dcfg.sm.shmem_depth
    sub_engine = eng
    shard_devices = None
    if placement == "shard_map":
        with tracing.span("egpu.fleet.stage"):
            mapped, args, order, sched = _stage_shard_map(
                fcfg, backend, cfgs[0], word_arrays[0], local_bid,
                device_of, shmems[0], gm)
        with tracing.span("egpu.fleet.dispatch"):
            regs_d, sh_d, gmems_d, oob_d = mapped(*args)
        # per-device replicas diff-merge against the launch image in
        # device order — exact under the no-race launch contract
        with tracing.span("egpu.fleet.merge"):
            merged = gm
            for d in range(fcfg.n_devices):
                changed = gmems_d[d] != gm
                merged = jnp.where(changed, gmems_d[d], merged)
            gm = merged
        with tracing.span("egpu.fleet.unpack"):
            flat_regs = regs_d.reshape(n_blocks, MAX_THREADS, N_REGS)
            flat_sh = sh_d.reshape(n_blocks, -1)
            flat_oob = oob_d.reshape(n_blocks)
            for i, b in enumerate(order):
                regs_slots[b] = flat_regs[i]
                shmem_slots[b] = flat_sh[i]
                oob_slots[b] = flat_oob[i]
            if flat_sh.shape[1] < shmem_pad:
                pad = shmem_pad - flat_sh.shape[1]
                for b in range(n_blocks):
                    shmem_slots[b] = jnp.pad(shmem_slots[b], (0, pad))
            # the JAX device holding each simulated device's slice, in
            # fleet order
            shards = sorted(regs_d.addressable_shards,
                            key=lambda s: s.index[0].start or 0)
            shard_devices = [int(s.device.id) for s in shards]
        halted = bool(sched.halted)
        sub_engine = "trace"        # the mapped body runs the scanned
        eng_fallback = None         # schedule; engines are bit-identical
    else:
        # host path: phase-by-phase, per-device sub-launches against the
        # phase's base gmem, diff-merged in device order
        with tracing.span("egpu.fleet.stage"):
            # fleet-level per-kernel shmem batches (program-local order)
            counts = [int((gmap == k).sum()) for k in range(len(kernels))]
            sh_batches = [_kernel_shmem(shmems[k], cfgs[k].shmem_depth,
                                        counts[k], k) if counts[k] else None
                          for k in range(len(kernels))]
        for p in np.unique(block_phase):
            pblocks = np.flatnonzero(block_phase == p)
            base = gm
            merged = gm
            for d in range(fcfg.n_devices):
                bd = pblocks[device_of[pblocks] == d]
                if bd.size == 0:
                    continue
                with tracing.span("egpu.fleet.stage"):
                    sub_shmems: list[Any] = []
                    for k in range(len(kernels)):
                        batch = sh_batches[k]
                        mine = bd[gmap[bd] == k]
                        if batch is None or mine.size == 0:
                            sub_shmems.append(None)
                        else:
                            sub_shmems.append(np.asarray(
                                batch[local_bid[mine]]))
                with tracing.span("egpu.fleet.dispatch"):
                    sub = launch(dcfg, programs=kernels,
                                 grid_map=gmap[bd], shmem=sub_shmems,
                                 gmem=base, backend=backend, schedule=mode,
                                 engine=sub_engine, packing=packing,
                                 block_ids=local_bid[bd])
                with tracing.span("egpu.fleet.merge"):
                    changed = sub.gmem != base
                    merged = jnp.where(changed, sub.gmem, merged)
                with tracing.span("egpu.fleet.unpack"):
                    for i, b in enumerate(bd):
                        regs_slots[b] = sub.regs[i]
                        shmem_slots[b] = sub.shmem[i]
                        oob_slots[b] = sub.oob[i]
                halted = halted and sub.halted
            gm = merged
    with tracing.span("egpu.fleet.unpack"):
        regs = jnp.stack(regs_slots, axis=0)
        shmem_out = jnp.stack(shmem_slots, axis=0)
        oob = jnp.stack(oob_slots, axis=0)

    with tracing.span("egpu.fleet.schedule"):
        (timing, static_span, by_class, steps, remote_gmem_cycles,
         per_device, resolved_packing) = _fleet_timing(
            fcfg, kernels, traces, gmap, block_phase, device_of, mode,
            packing, host_latency)
    fleet_info = {
        "n_devices": int(fcfg.n_devices),
        "route": fcfg.route,
        "placement": placement,
        "placement_reason": placement_reason,
        "remote_gmem_latency": int(fcfg.remote_gmem_latency),
        "remote_gmem_cycles": int(remote_gmem_cycles),
        "per_device": per_device,
    }
    if shard_devices is not None:
        fleet_info["shard_devices"] = shard_devices

    return LaunchResult(
        grid=(n_blocks,),
        block=cfgs[0].n_threads if len(kernels) == 1
        else tuple(c.n_threads for c in cfgs),
        n_waves=len(timing.wave_cycles),
        regs=regs,
        shmem=shmem_out,
        gmem=gm,
        oob=oob,
        halted=halted,
        steps=int(steps),
        cycles=int(timing.makespan),
        wave_cycles=np.asarray(timing.wave_cycles, np.int64),
        cycles_by_class=by_class.astype(np.int64),
        buffer_offsets=offsets,
        schedule=mode,
        engine=sub_engine,
        engine_fallback=eng_fallback,
        program_names=tuple(names),
        grid_map=gmap,
        timing=timing,
        static_cycles=int(static_span),
        trace_merge=None,
        packing=resolved_packing,
        wave_packing=None,
        host_dispatch=host_dispatch,
        priority_respected=(mode == "dynamic")
        or not any(k.priority for k in kernels),
        fleet=fleet_info,
    )


def _fleet_timing(fcfg: FleetConfig, kernels, traces, gmap, block_phase,
                  device_of, mode: str, packing, host_latency: int):
    """The fleet's cycle model: per-device schedules of each phase merged
    into the fleet view (and the static re-run), the aggregate counters,
    and ``profile()["fleet"]`` less the placement. Returns ``(timing,
    static makespan, cycles by class, steps, fleet info, packing)``."""
    dcfg = fcfg.device
    n_blocks = int(gmap.shape[0])
    lat = int(fcfg.remote_gmem_latency)
    remote_traces = [_remote_trace(t, lat) for t in traces]

    def _trace_of(b: int, d: int) -> ProgramTrace:
        return (traces if d == fcfg.home_device
                else remote_traces)[int(gmap[b])]

    block_priority = np.asarray([kernels[k].priority for k in gmap],
                                np.int64)
    policy = packing if packing is not None else dcfg.packing
    resolved_packing = "grid"

    def _fleet_schedule(sched_mode: str):
        nonlocal resolved_packing
        parts = []
        t0 = int(host_latency)
        for p in np.unique(block_phase):
            pblocks = np.flatnonzero(block_phase == p)
            span = t0
            for d in range(fcfg.n_devices):
                bd = pblocks[device_of[pblocks] == d]
                if bd.size == 0:
                    continue
                trs = [_trace_of(b, d) for b in bd]
                wp = pack_waves([t.data_steps for t in trs],
                                dcfg.n_sms, policy=policy)
                if wp.policy == "length":
                    resolved_packing = "length"
                s = schedule_blocks(trs, dcfg.n_sms, sched_mode,
                                    priority_of=block_priority[bd],
                                    packing=wp, start_cycle=t0)
                parts.append((s, bd, d * dcfg.n_sms))
                span = max(span, s.makespan)
            t0 = span
        return merge_schedules(parts, fcfg.n_sms, n_blocks)

    timing = _fleet_schedule(mode)
    static_span = timing.makespan if mode == "static" \
        else _fleet_schedule("static").makespan

    # ---- aggregate counters ---------------------------------------------
    steps = 0
    by_class = np.zeros((NUM_CLASSES,), np.int64)
    remote_gmem_cycles = 0
    for b in range(n_blocks):
        t = _trace_of(b, int(device_of[b]))
        steps += t.steps
        by_class += np.asarray(t.cycles_by_class(), np.int64)
        if int(device_of[b]) != fcfg.home_device:
            remote_gmem_cycles += t.gmem_cycles \
                - traces[int(gmap[b])].gmem_cycles

    per_device = []
    for d in range(fcfg.n_devices):
        lo, hi = d * dcfg.n_sms, (d + 1) * dcfg.n_sms
        mine = device_of == d
        dev_finish = int(timing.block_finish[mine].max()) \
            if mine.any() else 0
        per_device.append({
            "device": int(d), "home": d == fcfg.home_device,
            "blocks": int(mine.sum()),
            "busy": int(timing.sm_busy[lo:hi].sum()),
            "wait": int(timing.sm_wait[lo:hi].sum()),
            "idle": int(timing.sm_idle[lo:hi].sum()),
            "makespan": dev_finish,
        })

    return (timing, static_span, by_class, steps, remote_gmem_cycles,
            per_device, resolved_packing)
