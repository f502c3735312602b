"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; the dry-run sets
``--xla_force_host_platform_device_count=512`` before first jax use.

Single pod: (16, 16) = 256 chips, axes (data, model) — TPU v5e pod slice.
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model); the pod axis
composes with data for batch sharding, so only the gradient all-reduce
(training) crosses the inter-pod links — the deployment-standard layout.
"""
from __future__ import annotations

import jax


# §Perf knob: alternate factorization of the same chips, e.g. (64, 4)
# for small-model training where 16-way TP over-pays in activation
# all-reduces.  None = the assignment's production shapes.
MESH_OVERRIDE = None


def _mesh(shape, axes):
    """A mesh whose axes the partitioner places (``Auto``), so that a
    sharding constraint such as ``moe.DISPATCH_SPEC`` may name them:
    ``jax.make_mesh``'s default ``Explicit`` axes refuse one."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    if MESH_OVERRIDE is not None and not multi_pod:
        return _mesh(MESH_OVERRIDE, ("data", "model"))
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def data_axes(mesh) -> tuple[str, ...]:
    """The batch-sharding axes of a mesh (pod composes with data)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_host_mesh(model: int = 1):
    """Tiny mesh over the locally visible devices (tests/examples)."""
    n = len(jax.devices())
    return _mesh((n // model, model), ("data", "model"))


def make_lane_mesh(n: int | None = None):
    """1-D ``lanes`` mesh for the engine's shard_map lane execution.

    This is the mesh the simulation-fleet axis shards over (see
    ``engine.configure_lane_mesh``) — orthogonal to the model meshes
    above, which shard *workload* tensors.  ``n=None`` takes every
    visible device; on a CPU host force the device count first
    (``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
    """
    from repro.core.engine import build_lane_mesh

    return build_lane_mesh(len(jax.devices()) if n is None else n)
