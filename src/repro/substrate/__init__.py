"""Device-mesh and sharding substrate.

Single choke point for the JAX mesh-context API (``jax.set_mesh``,
``jax.sharding.AxisType``, ``jax.sharding.get_abstract_mesh``,
``jax.shard_map``).  No module outside this package may touch those names
directly -- scripts/ci.sh greps for violations.
"""
from .compat import (
    compiled_cost_analysis,
    constrain,
    constrain_spec,
    current_abstract_mesh,
    current_axis_sizes,
    degrade_spec,
    enable_compile_cache,
    host_id,
    make_mesh,
    mesh_axis_sizes,
    mesh_context,
    process_topology,
    shard_map,
)

__all__ = [
    "compiled_cost_analysis",
    "constrain",
    "constrain_spec",
    "current_abstract_mesh",
    "current_axis_sizes",
    "degrade_spec",
    "enable_compile_cache",
    "host_id",
    "make_mesh",
    "mesh_axis_sizes",
    "mesh_context",
    "process_topology",
    "shard_map",
]
