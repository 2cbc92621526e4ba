"""Production mesh builders (functions — importing never touches jax
device state).

Every mesh is built with ``Auto`` axes: the engine places its state with
``NamedSharding`` and lets GSPMD propagate through the round step, which
is what ``jax.make_mesh``'s ``Explicit`` default would turn into
type-level sharding checks.
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod (data, model); 2 pods -> (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host actually has — used by examples/smoke tests."""
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))


def client_axes(mesh) -> tuple:
    """Mesh axes that carry the client-parallel dimension."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def num_clients_for(mesh) -> int:
    sizes = dict(mesh.shape)
    n = sizes.get("data", 1)
    if "pod" in sizes:
        n *= sizes["pod"]
    return n
