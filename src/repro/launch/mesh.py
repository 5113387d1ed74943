"""Production mesh definitions.

v5e pod = 16×16 = 256 chips; the multi-pod mesh adds a leading "pod" axis
(2 pods = 512 chips). Function, not module-level constant, so importing this
module never touches jax device state (the dry-run must set XLA_FLAGS before
any device query).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Activations carry sharding hints (models/layers.py shard_act) and the
    # partitioner resolves the rest: the axes are Auto, not Explicit.
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def mesh_axis_sizes(mesh) -> dict:
    # works for both concrete Mesh and AbstractMesh (shape is an OrderedDict)
    return dict(mesh.shape)
