"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then builds meshes.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh for tests/examples (e.g. (1, 1) on one CPU device)."""
    return jax.make_mesh(shape, axes)


def make_problem_mesh():
    """A 1-D mesh over every visible device, axis name ``problem``.

    The sweep-sharding mesh: batched-tuner grids (``core.batch.solve_grid``)
    flatten the (workload x rho) cross product onto one problem axis, and a
    ``NamedSharding(mesh, P("problem"))`` on the inputs lets XLA partition
    the independent vmap lanes device-parallel (see
    ``repro.api.backends.ShardedBackend``).  The axis is ``Auto``: the
    compiler propagates the problem sharding to the values the grid derives
    inside (the start inits), which an ``Explicit`` axis, ``make_mesh``'s
    default, refuses at the vmap."""
    return jax.make_mesh((len(jax.devices()),), ("problem",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def make_host_mesh(model: int = 1):
    """A mesh over however many devices this host actually has."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return jax.make_mesh((n // model, model), ("data", "model"))


def data_axes(mesh) -> Tuple[str, ...]:
    """Axes that carry the batch dimension (pod + data when present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def axis_size(mesh, name: str) -> int:
    names = mesh.axis_names
    if name not in names:
        return 1
    return mesh.devices.shape[names.index(name)]
