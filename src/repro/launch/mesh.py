"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — smoke tests must keep seeing 1 CPU device while
dryrun.py boots with 512 forced host devices. Every mesh is built with Auto
axis types: sharding is propagated by the compiler (GSPMD), with
``shard_hint`` constraints where the model asks for them.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def make_serving_mesh(tp: int):
    """The (data=1, model=tp) mesh tensor-parallel serving runs on — the
    one ``serve.py --tp`` and ``chip_smoke.py --chips 4`` build: the first
    ``tp`` local devices, Auto axes."""
    devices = jax.devices()
    if len(devices) < tp:
        raise ValueError(f"a model axis of {tp} needs {tp} devices, found "
                         f"{len(devices)}")
    return make_mesh((1, tp), ("data", "model"), devices=devices[:tp])


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod; 2 pods when multi_pod (pod axis = pure DP/DCN)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh on the local device (CPU tests/examples)."""
    return make_mesh((1, 1), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch is sharded over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def model_size(mesh) -> int:
    return mesh.shape["model"]


def data_size(mesh) -> int:
    n = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return n
