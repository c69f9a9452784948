"""Production meshes. A FUNCTION (not module-level constant) so importing
this module never touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """The one place this repo builds a mesh. Every axis is Auto: the code
    places arrays with NamedSharding/shard_map and lets GSPMD propagate,
    whereas ``jax.make_mesh`` defaults to Explicit axes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_serve_mesh(dp: int = 1, mp: int = 1):
    """Serving mesh ("data", "model"): ``mp``-way model sharding partitions
    every paged arena's kv-head (or latent feature) axis — per-device HBM
    holds 1/mp of the cache and each device sweeps only its head shard
    (serving/sharded.py); ``dp`` replicates the engine (arenas + params) for
    throughput. CPU runs emulate devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    n = len(jax.devices())
    if dp * mp > n:
        raise ValueError(f"mesh ({dp},{mp}) needs {dp * mp} devices, have {n} "
                         "(on CPU run with JAX_PLATFORMS=cpu, or set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    return make_mesh((dp, mp), ("data", "model"))


def parse_mesh_arg(arg: str):
    """CLI ``--mesh dp,mp`` -> Mesh (e.g. "1,2")."""
    try:
        dp, mp = (int(x) for x in arg.split(","))
    except ValueError as e:
        raise ValueError(f"--mesh wants 'dp,mp' (e.g. 1,2); got {arg!r}") from e
    return make_serve_mesh(dp, mp)


def mesh_shape_dict(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
