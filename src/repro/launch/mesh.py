"""Production mesh construction.

A function (NOT a module-level constant) so importing this module never
touches jax device state — the dry-run driver must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
initialization, and tests/benches must keep seeing the single real CPU
device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh",
           "make_exec_mesh", "default_exec_partitions"]


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding code here
    relies on the compiler propagating shardings, not on explicit axes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod; multi-pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests, CPU training)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(n // data, 1))
    return _auto_mesh((data, model), ("data", "model"))


def make_exec_mesh(partitions: int = 0):
    """1-D ``"part"`` mesh for partitioned query execution.

    The axis spans ``min(partitions, len(jax.devices()))`` devices — on a
    one-device CPU host a P>1 query is *emulated*: the merge combine still
    runs under ``shard_map`` over this axis (size 1), so the partition
    code path and its launch/parity contracts never depend on the real
    device count.
    """
    n = len(jax.devices())
    size = min(max(1, int(partitions)) or n, n) if partitions else n
    return _auto_mesh((max(size, 1),), ("part",))


def default_exec_partitions() -> int:
    """Mesh-derived default for ``core.planner.num_partitions``: one
    partition per available device."""
    return max(1, len(jax.devices()))
