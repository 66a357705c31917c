"""Mesh/sharding substrate on the installed JAX (0.9): the single call sites
for the mesh-context API (``jax.set_mesh``, ``jax.sharding.AxisType``,
``jax.sharding.get_abstract_mesh``, ``jax.shard_map``).

    operation             implementation
    -------------------   ---------------------------------------------
    make_mesh             jax.make_mesh(axis_types=Auto...)
    mesh_context          jax.set_mesh
    current_abstract_mesh jax.sharding.get_abstract_mesh (None when empty)
    constrain             with_sharding_constraint, a no-op with no mesh
"""
from __future__ import annotations

import contextlib
import math
import os
import socket
from pathlib import Path
from typing import Any, Iterator, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, PartitionSpec


# ------------------------------------------------------------------ make_mesh
def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Sequence[Any] | None = None) -> Mesh:
    """Build a Mesh of `shape` over `axes`, optionally from explicit devices.

    The axes are AxisType.Auto (the compiler keeps full sharding freedom).
    Raises RuntimeError when fewer devices exist than the shape needs.
    """
    shape = tuple(int(s) for s in shape)
    axes = tuple(axes)
    n = math.prod(shape)
    devs = np.asarray(devices if devices is not None else jax.devices()).ravel()
    if devs.size < n:
        raise RuntimeError(f"need {n} devices, have {devs.size}")
    return jax.make_mesh(shape, axes, devices=list(devs[:n]),
                         axis_types=(AxisType.Auto,) * len(axes))


def mesh_axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


# --------------------------------------------------------------- mesh context
@contextlib.contextmanager
def mesh_context(mesh: Mesh) -> Iterator[Mesh]:
    """Activate `mesh` for jit tracing / sharding constraints in this block."""
    with jax.set_mesh(mesh):
        yield mesh


def current_abstract_mesh():
    """The abstract mesh active for the current trace, or None when there is
    none.  Callers may rely on .shape / .axis_names only."""
    am = jax.sharding.get_abstract_mesh()
    return None if am is None or am.empty else am


def current_axis_sizes() -> dict[str, int] | None:
    """axis-name -> size of the active mesh, or None outside any mesh."""
    am = current_abstract_mesh()
    return None if am is None else dict(am.shape)


# ------------------------------------------------------------------ topology
def host_id() -> str:
    """A stable identifier for this host (the pool's placement unit)."""
    return socket.gethostname()


def process_topology() -> dict:
    """Host/process placement of the CURRENT process — the seam the engine
    pool probes through: same pid => in-process transfer, same host / other
    pid => pipe transport, other host => network (future).

    Reading the platform initializes the jax backend; a backend that fails
    to initialize raises here rather than reporting a device-less process.
    """
    return {"host": host_id(), "pid": os.getpid(),
            "n_cpus": os.cpu_count() or 1,
            "platform": jax.default_backend(),
            "n_devices": jax.device_count()}


# ------------------------------------------------------- compilation cache
REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Keep JAX's persistent compilation cache at a fixed place and return
    the directory in use.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    reads it itself and nothing is set here; otherwise the cache goes to
    ``<repo>/.jax_cache`` -- a fixed path, since a directory that moves
    never hits.  Entry points call this from ``main()``, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# ------------------------------------------------------------- cost analysis
def compiled_cost_analysis(compiled) -> dict:
    """Compiled.cost_analysis() as a dict ({} when XLA offers no analysis)."""
    return dict(compiled.cost_analysis() or {})


# ----------------------------------------------------------------- shard_map
def shard_map(f, *, mesh: Mesh, in_specs, out_specs):
    """jax.shard_map with replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ----------------------------------------------------------------- constrain
def constrain_spec(x, spec: PartitionSpec):
    """with_sharding_constraint that no-ops when no mesh is active."""
    if current_abstract_mesh() is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def degrade_spec(shape: Sequence[int],
                 candidates: Sequence[Sequence[str]],
                 sizes: dict[str, int]) -> PartitionSpec:
    """Greedy divisibility degradation: per dimension, keep the candidate
    mesh axes (outermost first) that exist in `sizes`, are not yet used, and
    whose cumulative product divides the dimension.  The single source of
    this algorithm -- models.common.resolve_spec layers logical-name lookup
    on top of it.
    """
    out: list[Any] = []
    used: set[str] = set()
    for dim, names in zip(shape, candidates):
        keep: list[str] = []
        shard = 1
        for ax in names:
            if ax is None:
                continue
            if ax in sizes and ax not in used and dim % (shard * sizes[ax]) == 0:
                keep.append(ax)
                shard *= sizes[ax]
        used.update(keep)
        if not keep:
            out.append(None)
        elif len(keep) == 1:
            out.append(keep[0])
        else:
            out.append(tuple(keep))
    return PartitionSpec(*out)


def constrain(x, *axes):
    """Constrain `x` by mesh-axis names, degrading gracefully.

    Each entry is a mesh axis name, a tuple of names, or None.  Axes absent
    from the active mesh or not dividing the dimension are dropped; with no
    active mesh the call is the identity.
    """
    sizes = current_axis_sizes()
    if not sizes:
        return x
    cands = [entry if isinstance(entry, tuple) else (entry,) for entry in axes]
    spec = degrade_spec(x.shape, cands, sizes)
    return jax.lax.with_sharding_constraint(x, spec)
