"""The jax names the rest of the repo uses, for the installed jax (0.9).

Every site that needs a sharding type, ``shard_map`` or a mesh imports it
from here, so the next jax rename is a one-file fix (repro.analysis TAO001
flags any direct jax.sharding/jax.experimental use outside this module).
"""
from __future__ import annotations

import jax

__all__ = [
    "Mesh",
    "NamedSharding",
    "PartitionSpec",
    "SingleDeviceSharding",
    "shard_map",
    "backend_initialized",
    "make_mesh",
    "on_tpu",
]

Mesh = jax.sharding.Mesh
NamedSharding = jax.sharding.NamedSharding
PartitionSpec = jax.sharding.PartitionSpec
SingleDeviceSharding = jax.sharding.SingleDeviceSharding
shard_map = jax.shard_map


def on_tpu() -> bool:
    """True when the default jax backend is a real TPU.

    The kernel wrappers in ``repro.kernels`` use this to pick native Mosaic
    lowering on TPU and ``interpret=True`` everywhere else, so CPU CI runs
    the same Pallas programs.
    """
    return jax.default_backend() == "tpu"


def backend_initialized() -> bool:
    """Whether this process has initialised a JAX backend — on a TPU host,
    whether it now holds the chip, which a child process then cannot
    open."""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def make_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with Auto axes: the compiler places whatever the
    step does not pin with ``shard_map`` specs."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )
