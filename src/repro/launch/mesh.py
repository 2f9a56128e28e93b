"""Mesh construction for the training and dry-run launchers.

Functions, not module constants — importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis in ``Auto`` mode.

    The sharding rules (`dist.sharding`) and the train steps place inputs
    and leave propagation to the compiler; the ``Explicit`` axes that
    ``jax.make_mesh`` defaults to would instead demand an output sharding
    for every reshape of a sharded array (the microbatch split, for one).
    """
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16)=('data','model') single pod / (2,16,16)=('pod','data','model')
    two pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2):
    """Small mesh over whatever devices exist (tests on CPU)."""
    n = len(jax.devices())
    if n < data * model:
        data, model = 1, min(n, model)
    return make_mesh((data, model), ("data", "model"))
