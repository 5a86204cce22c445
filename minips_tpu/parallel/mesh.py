"""Device-mesh bootstrap — the rebuild of Engine::StartEverything topology.

The reference boots one process per node, each hosting server threads +
worker threads, glued by a global id-mapper and a ZeroMQ mailbox (SURVEY.md
§3.1). On TPU the topology is a ``jax.sharding.Mesh``: every device is both
a "worker" (computes grads on its data shard) and a "server" (owns a
contiguous shard of every table — FlexPS-style colocation becomes literal
SPMD). SimpleIdMapper is replaced by mesh coordinates (SURVEY.md §2
"SimpleIdMapper").

Axes:
- ``data`` — the worker/data-parallel axis; also the server-shard axis
  (parameters are range-partitioned along it, the PS analog of
  weight-update sharding, PAPERS.md arXiv 2004.13336).
- ``model`` — reserved, size 1 by default. The reference has no TP/PP/SP/EP
  (SURVEY.md §2.2) but the mesh must not structurally preclude them
  (SURVEY.md §5.7).
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

DATA_AXIS = "data"
MODEL_AXIS = "model"


def make_mesh(
    num_workers: Optional[int] = None,
    *,
    model_size: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a ``Mesh`` with axes ``(data, model)``.

    ``num_workers`` defaults to all available devices / ``model_size``. This
    is the moral equivalent of the reference's hostfile + worker allocation
    (SURVEY.md §1 L7): the mesh defines who computes and who owns which
    parameter range, with no process bootstrapping needed on a single host
    (multi-host adds ``jax.distributed.initialize`` upstream, see
    minips_tpu/parallel/cluster.py).
    """
    devs = list(devices) if devices is not None else list(jax.devices())
    if num_workers is None:
        num_workers = len(devs) // model_size
    need = num_workers * model_size
    if need > len(devs):
        raise ValueError(
            f"mesh ({num_workers}x{model_size}) needs {need} devices, "
            f"have {len(devs)}"
        )
    grid = np.array(devs[:need]).reshape(num_workers, model_size)
    return Mesh(grid, (DATA_AXIS, MODEL_AXIS))


def local_mesh_size(mesh: Mesh, axis: str = DATA_AXIS) -> int:
    return mesh.shape[axis]


# The tile a one-dimensional array has on the TPU: the compiled text lays a
# vector out as ``{0:T(1024)}`` (float32; bfloat16 packs ``(128)(2,1)``
# inside the same 1,024 elements). A shard that does not end on a tile
# cannot be placed by an all-gather: the compiler then builds the gather
# as an all-reduce of zero-padded shards (PERF.md section 6, PR 36).
SHARD_TILE = 1024


def padded_size(n: int, shards: int) -> int:
    """Smallest multiple of ``shards`` >= n (range-partition padding)."""
    return shards * math.ceil(max(n, 1) / shards)


def pcast_varying(x, axes):
    """pcast x to varying over exactly the axes it isn't already varying
    over (pcast rejects varying→varying)."""
    have = jax.typeof(x).vma
    need = tuple(a for a in axes if a not in have)
    return jax.lax.pcast(x, need, to="varying") if need else x
