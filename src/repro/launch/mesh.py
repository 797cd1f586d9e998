"""Production mesh definitions (TPU v5e target).

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax init; smoke tests must
keep seeing 1 device).  Sharding hints (``nn.shard_hints``) resolve
against the mesh activated with ``jax.set_mesh``.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod; multi-pod stacks a leading 'pod' axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_debug_mesh(devices: int | None = None):
    """Tiny mesh over whatever devices exist (tests / examples)."""
    n = devices or len(jax.devices())
    return jax.make_mesh((1, n), ("data", "model"))


# Mesh axis name the edge engine shards its fleet over.
DEVICE_AXIS = "device"

# Second fleet mesh axis for the hierarchical fog tier (core.topology):
# a 2-D ("fog", "device") mesh shards the [D] slot axis fog-major, so a
# fog shard holds whole contiguous blocks of slots and the two-tier
# aggregation runs as a group-local psum over DEVICE_AXIS followed by a
# fog-axis psum over FOG_AXIS.
FOG_AXIS = "fog"


def _auto_mesh(shape, axis_names):
    """``jax.make_mesh`` with Auto axes.  The engines place the fleet through
    shard_map specs and NamedShardings; with Explicit axes (the default), a
    single-device jit that follows a sharded run fails to lower ("Length of
    device assignment 1 is not equal to the size of the mesh")."""
    return jax.make_mesh(shape, axis_names, axis_types=(
        jax.sharding.AxisType.Auto,) * len(axis_names))


def make_device_mesh(shards: int | None = None):
    """1-D mesh for the federated fleet's device axis (``EdgeEngine(mesh=...)``).

    The engine's ``[D, ...]`` stacked state is shard_map-ed over the single
    ``"device"`` axis: each accelerator simulates D/shards edge devices and
    the in-compile fog aggregation psum-reduces across the axis.  On CPU,
    force multiple host devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` *before* any jax
    import (see tests/test_shard_engine.py and the CI sharded job).
    """
    n = shards or len(jax.devices())
    return _auto_mesh((n,), (DEVICE_AXIS,))


def make_fog_mesh(fog_shards: int | None = None,
                  device_shards: int | None = None):
    """2-D ``("fog", "device")`` mesh for hierarchical fleets.

    The engine's ``[D, ...]`` stacked state shards its leading axis over
    BOTH axes (``P((FOG_AXIS, DEVICE_AXIS))``, fog-major): global slot
    ``(f·device_shards + d)·D_local + k`` lives on mesh coordinate
    ``(f, d)``.  Fog groups (``core.topology.FogTopology``) are decoupled
    from the mesh factorization — segment reductions psum over both axes —
    but aligning groups with fog shards keeps intra-fog traffic on the
    faster axis.  Defaults: ``fog_shards × device_shards`` covering every
    visible device, fog-major (validated on CI-sized fake multi-host
    meshes via ``--xla_force_host_platform_device_count``).
    """
    n = len(jax.devices())
    if fog_shards is None:
        fog_shards = n // (device_shards or 1) if device_shards else n
        device_shards = device_shards or 1
    elif device_shards is None:
        device_shards = n // fog_shards
    if fog_shards < 1 or device_shards < 1:
        raise ValueError(f"mesh shape ({fog_shards}, {device_shards}) "
                         f"must be positive")
    return _auto_mesh((fog_shards, device_shards), (FOG_AXIS, DEVICE_AXIS))


def batch_axes(mesh) -> tuple:
    """Mesh axes a batch dimension shards over."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


# v5e hardware constants used by the roofline analysis (benchmarks/roofline.py)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
