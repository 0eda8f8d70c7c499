"""Device mesh construction and canonical shardings.

The reference's cluster topology is env-var driven process roles
(``DMLC_NUM_WORKER`` / ``DMLC_NUM_SERVER`` / ``DMLC_ROLE``,
``examples/local.sh:22-33``) rendezvoused by a scheduler over TCP.  On TPU
the topology is a :class:`jax.sharding.Mesh` over the chip grid:

* ``data`` axis — data parallelism; replaces the W worker processes.
  Per-shard gradients meet in a ``psum`` over ICI instead of W push RPCs.
* ``model`` axis — feature-dimension sharding; replaces ps-lite's
  range-partitioned key space across S servers (reference
  ``src/main.cc:98-101``, ``GetServerKeyRanges``).

Multi-host: the same mesh spans processes after
``jax.distributed.initialize()`` — DCN between hosts, ICI within — with no
code change here (`make_mesh` uses the global device list).
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"


def make_mesh(shape: dict | None = None, *, devices=None) -> Mesh:
    """Build a mesh. ``shape`` maps axis name -> size, e.g. ``{"data": 8}``
    or ``{"data": 4, "model": 2}``.  Default: all devices on ``data``."""
    devices = jax.devices() if devices is None else devices
    if shape is None:
        shape = {DATA_AXIS: len(devices)}
    sizes = list(shape.values())
    total = int(np.prod(sizes))
    if total > len(devices):
        raise ValueError(f"mesh shape {shape} needs {total} devices, have {len(devices)}")
    grid = np.asarray(devices[:total]).reshape(sizes)
    return Mesh(grid, tuple(shape.keys()))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Batch rows sharded over ``data`` (feature cols over ``model`` if present)."""
    if MODEL_AXIS in mesh.axis_names:
        return NamedSharding(mesh, P(DATA_AXIS, MODEL_AXIS))
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def feature_sharding(mesh: Mesh) -> NamedSharding:
    """Weight vector sharded over the ``model`` axis (ps-lite key-range
    analogue); replicated if the mesh has no model axis."""
    if MODEL_AXIS in mesh.axis_names:
        return NamedSharding(mesh, P(MODEL_AXIS))
    return NamedSharding(mesh, P())


def num_data_shards(mesh: Mesh) -> int:
    return mesh.shape[DATA_AXIS] if DATA_AXIS in mesh.axis_names else 1
