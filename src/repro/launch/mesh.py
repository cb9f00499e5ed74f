"""Production meshes.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.

Single pod:  (16, 16)    axes ("data", "model")       = 256 chips
Multi pod:   (2, 16, 16) axes ("pod", "data", "model") = 512 chips

The sharding discipline (launch/shardings.py):
  * batch over ("pod", "data") — pure DP across pods (cheapest inter-pod
    traffic: one gradient all-reduce per step);
  * weights 2D-sharded: "model" = tensor parallel (heads / d_ff / experts /
    vocab), "data" = FSDP (ZeRO-3 style parameter+optimizer sharding,
    re-gathered per layer inside the scan);
  * elastic: any (data, model) shape works — checkpoints are mesh-agnostic
    and restore reshards (checkpoint/ckpt.py).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Elastic variant: any shape whose product <= len(jax.devices()).

    Every axis is Auto: the shardings come from ``launch/shardings.py``
    and the compiler, not from the array types (``jax.make_mesh``
    defaults to Explicit axes)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_fleet_mesh(n_devices: int):
    """1-D mesh for the simulated-eGPU fleet (``core.fleet``): axis
    ``"fleet"`` carries one simulated device per real JAX device. Run
    CPU-only hosts with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    to expose N devices."""
    if n_devices < 1:
        raise ValueError(f"n_devices={n_devices} must be >= 1")
    if n_devices > len(jax.devices()):
        raise ValueError(
            f"fleet mesh wants {n_devices} devices but jax exposes "
            f"{len(jax.devices())}; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_devices} "
            f"(CPU) or use placement='host'")
    # the fleet body is a manual shard_map over "fleet", and its state
    # carries no sharding in its types
    return make_mesh((n_devices,), ("fleet",))


def data_axes(mesh) -> tuple[str, ...]:
    """The axes a batch dimension shards over."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def batch_divisor(mesh) -> int:
    d = 1
    for a in data_axes(mesh):
        d *= mesh.shape[a]
    return d
