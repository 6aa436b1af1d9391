"""Production mesh construction.

A FUNCTION (not a module constant) so importing never touches jax device
state.  Single pod: 16x16 = 256 chips (data x model).  Multi-pod: 2 pods x
256 = 512 chips with a leading "pod" axis (pure DP across the slower
inter-pod links).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: shardings propagate
    through GSPMD and ``with_sharding_constraint`` hints, which is what
    the sharding rules and the ``shard_map`` dispatch are written for.
    (JAX's own default is ``Explicit`` axes, under which reshapes of
    sharded activations are refused at trace time.)"""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Degenerate mesh over whatever devices exist (tests / smoke runs)."""
    n = len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))


def make_serve_mesh(data: int = 1, model: int = 1):
    """An explicit ``data x model`` serving mesh (DESIGN.md §13).

    ``model`` chips per replica cut each compiled CIMA image (TP);
    ``data`` replicas each hold a full image copy and serve their slice
    of the batch (DP for activations, KV pools and slot state).  Uses
    the first ``data * model`` available devices, so a 2x2 mesh works on
    an 8-device host.  ``data=1`` is the 1D model-parallel layout every
    pre-mesh caller used — same numerics, same per-device tiles.
    """
    n = len(jax.devices())
    need = data * model
    if need > n:
        raise ValueError(
            f"make_serve_mesh({data}x{model}) needs {need} devices, "
            f"have {n} (set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count=N for simulated chips)")
    import numpy as np
    from jax.sharding import Mesh

    devs = np.asarray(jax.devices()[:need]).reshape(data, model)
    return Mesh(devs, ("data", "model"))
