"""Mesh construction helpers.

The reference's analogue of "pick a scheduler" (dask cluster / cubed spec) is
picking a device mesh. One logical axis is enough for groupby map-reduce —
the reduced axis is sharded over it; ICI carries the combine collectives.
Multi-host meshes work unchanged: jax.devices() spans hosts under
jax.distributed, and the same psum rides ICI within a host and DCN across.
"""

from __future__ import annotations

from typing import Any, Callable

import numpy as np


def shard_map(
    f: Callable, *, mesh: Any, in_specs: Any, out_specs: Any, check_vma: bool = True
) -> Callable:
    """``jax.shard_map``. Every shard_map construction in the package goes
    through here (floxlint FLX004 flags bare ``jax.shard_map`` attribute
    access), so the one spelling lives in one place."""
    import jax

    return jax.shard_map(  # floxlint: disable=FLX004
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma
    )


def axis_size(axis_name: str) -> int:
    """``jax.lax.axis_size`` (FLX004 flags bare access, as for shard_map)."""
    import jax

    return jax.lax.axis_size(axis_name)  # floxlint: disable=FLX004


def make_mesh(
    n_devices: int | None = None,
    axis_name: str = "data",
    *,
    shape: tuple[int, ...] | None = None,
    axis_names: tuple[str, ...] | None = None,
) -> Any:
    """A 1-D mesh over the first ``n_devices`` devices (default: all), or a
    multi-axis mesh via ``shape``/``axis_names`` — e.g.
    ``make_mesh(shape=(n_hosts, 8), axis_names=("dcn", "ici"))`` for
    multi-host: the reduction axis is then sharded over BOTH axes
    (pass ``axis_name=("dcn", "ici")`` to groupby_reduce) and psum rides ICI
    within a host and DCN across.
    """
    import jax
    from jax.sharding import Mesh

    devices = jax.devices()
    if shape is not None:
        if axis_names is None or len(axis_names) != len(shape):
            raise ValueError("axis_names must match shape")
        need = int(np.prod(shape))
        if need > len(devices):
            raise ValueError(f"Requested {need} devices; only {len(devices)} available.")
        return Mesh(np.asarray(devices[:need]).reshape(shape), tuple(axis_names))
    if n_devices is None:
        n_devices = len(devices)
    if n_devices > len(devices):
        raise ValueError(f"Requested {n_devices} devices; only {len(devices)} available.")
    return Mesh(np.asarray(devices[:n_devices]), (axis_name,))
