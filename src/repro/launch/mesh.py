"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state. Single pod: 16x16 = 256 chips
("data", "model"). Multi-pod: 2x16x16 = 512 chips ("pod", "data", "model").
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes, **kw):
    # make_mesh defaults to Explicit axes; the sharding rules constrain
    # with_sharding_constraint-style, which needs Auto
    return jax.make_mesh(shape, axes,
                         (jax.sharding.AxisType.Auto,) * len(axes), **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over the host devices (tests/examples)."""
    n = data * model
    if len(jax.devices()) < n:
        raise RuntimeError(f"need {n} devices, have {len(jax.devices())}")
    return _make_mesh((data, model), ("data", "model"))


def make_elastic_mesh(data: int = 1, model: int = 1, *, devices=None):
    """Mesh over an explicit device PREFIX — the elastic-restore shapes.

    ``make_host_mesh`` spans every host device, so halved/doubled
    topologies of the same job can't coexist in one process; this builds
    ("data", "model") over ``devices`` (default: the first data*model
    host devices), which is how the reshard benchmark/tests stand up
    source and target meshes side by side."""
    n = data * model
    if devices is None:
        devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    return _make_mesh((data, model), ("data", "model"),
                      devices=list(devices)[:n])


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
