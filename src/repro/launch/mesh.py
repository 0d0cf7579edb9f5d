"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run sets
``xla_force_host_platform_device_count`` before first jax init and only
then calls it.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.configs.base import ParallelConfig

__all__ = ["make_production_mesh", "parallel_config_for", "make_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh for tests/examples (e.g. (4, 2) on 8 CPU devices).

    Axes are ``Auto``: the training and sharding code places arrays with
    ``NamedSharding``/``with_sharding_constraint`` and lets the compiler
    propagate, which ``jax.make_mesh``'s default ``Explicit`` axes reject."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )


def parallel_config_for(mesh, *, fsdp: bool = False, sequence_parallel: bool = False) -> ParallelConfig:
    axis_names = mesh.axis_names
    return ParallelConfig(
        data_axis="data" if "data" in axis_names else axis_names[0],
        model_axis="model" if "model" in axis_names else axis_names[-1],
        pod_axis="pod" if "pod" in axis_names else None,
        fsdp=fsdp,
        sequence_parallel=sequence_parallel,
    )
