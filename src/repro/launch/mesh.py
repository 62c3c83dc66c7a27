"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing never touches jax
device state.  Single-pod: 16x16 = 256 chips (data x model).  Multi-pod:
2x16x16 = 512 chips (pod x data x model); the 'pod' axis carries the
second-level data parallelism across the inter-pod (DCN/ICI) boundary.

``make_elastic_mesh`` builds the largest (data, model) mesh available from
whatever devices are present — the elastic-scaling path used by
``launch/train.py`` after a failure shrinks the fleet.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Mesh over whatever devices exist on this host (tests / smoke)."""
    n = len(jax.devices())
    assert n % model == 0
    return jax.make_mesh(
        (n // model, model), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)


def make_elastic_mesh(target_model: int = 16, devices=None):
    """Largest (data, model) mesh from the available device pool: keeps the
    'model' extent fixed (TP degree is baked into layouts) and absorbs node
    loss by shrinking 'data'.  ``devices`` defaults to every visible
    device."""
    devs = jax.devices() if devices is None else list(devices)
    n = len(devs)
    model = min(target_model, n)
    while n % model:
        model -= 1
    data = n // model
    return jax.make_mesh(
        (data, model), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2, devices=devs)
