"""Production meshes.

``make_production_mesh`` is a function (not a module-level constant) so
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any
jax import, and everything else must see the real single device.

Every axis is ``Auto``: the step functions place activations with
``with_sharding_constraint`` hints (``sharding.api.shard``) and leave the
rest to GSPMD, which ``jax.make_mesh``'s default ``Explicit`` axes would
turn into hard sharding-type assertions.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(n_pods: int = 1, data: int = 2, model: int = 2):
    """Mesh over this host's devices: forced host devices in tests and
    examples, the local chips on an accelerator host."""
    if n_pods > 1:
        return _auto_mesh((n_pods, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))
