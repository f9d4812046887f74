"""Device mesh and sharding helpers.

The mesh has two named axes:

- ``"data"``  — data parallelism. One shard of the batch per mesh slot; the
  successor of a Spark RDD partition (reference ``Transformer.scala:22``:
  every node application is an SPMD map over partitions).
- ``"model"`` — model/feature-block parallelism. Columns of wide feature /
  weight matrices are sharded here; partial products are combined by XLA
  ``psum`` over ICI — the successor of the reference's ``VectorSplitter`` +
  block solvers (``nodes/util/VectorSplitter.scala:15-24``,
  ``nodes/learning/BlockLinearMapper.scala:47-74``).

Replication (Spark ``broadcast``, e.g. ``BlockWeightedLeastSquares.scala:223-226``)
is just a sharding spec with no named axes — XLA materializes one copy per
device.

Everything works mesh-less too (single chip): helpers accept ``mesh=None``
and degrade to plain arrays so the same pipeline code runs from a laptop CPU
test to a pod.
"""

from __future__ import annotations

import contextlib
import math
import threading
from typing import Iterator, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"

_state = threading.local()


def create_mesh(
    data: int | None = None,
    model: int = 1,
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Create a 2-axis ("data", "model") mesh.

    ``data=None`` uses all remaining devices on the data axis. A v5p pod
    slice's ICI torus is contiguous in ``jax.devices()`` order, so adjacent
    mesh slots get adjacent chips and collectives ride ICI.

    Multi-slice environments (devices reporting distinct ``slice_index``)
    get a HYBRID mesh: the slice dimension lands on the OUTER part of the
    "data" axis so data-parallel Gram/gradient reductions cross DCN only
    at the top of the reduction tree, while "model"-axis collectives stay
    entirely within one slice's ICI — the moral successor of the
    reference's ``spark.mlmatrix.treeBranchingFactor`` hierarchy control
    (``BlockWeightedLeastSquares.scala:186-188``).
    """
    devs = list(devices if devices is not None else jax.devices())
    if model < 1:
        raise ValueError(f"model axis size must be >= 1, got {model}")
    if data is None:
        if len(devs) % model:
            raise ValueError(f"{len(devs)} devices not divisible by model={model}")
        data = len(devs) // model
    n = data * model
    if n > len(devs):
        raise ValueError(f"mesh {data}x{model} needs {n} devices, have {len(devs)}")
    n_slices = len(_slice_groups(devs[:n]))
    if n_slices > 1 and data % n_slices == 0 and n == len(devs):
        from jax.experimental import mesh_utils

        grid = mesh_utils.create_hybrid_device_mesh(
            (data // n_slices, model),
            (n_slices, 1),  # DCN spans the data axis only
            devices=devs,
        )
        return Mesh(grid, (DATA_AXIS, MODEL_AXIS))
    grid = np.asarray(devs[:n]).reshape(data, model)
    return Mesh(grid, (DATA_AXIS, MODEL_AXIS))


def _slice_groups(devs: Sequence) -> dict:
    """Group devices by their DCN slice (``slice_index``); single-slice and
    CPU devices (no attribute) collapse to one group."""
    groups: dict = {}
    for d in devs:
        groups.setdefault(getattr(d, "slice_index", 0), []).append(d)
    return groups


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None) -> Iterator[Mesh | None]:
    """Context manager installing ``mesh`` as the ambient default mesh."""
    stack = getattr(_state, "stack", None)
    if stack is None:
        stack = _state.stack = []
    stack.append(mesh)
    try:
        yield mesh
    finally:
        stack.pop()


def current_mesh() -> Mesh | None:
    """The innermost mesh installed by :func:`use_mesh`, else None."""
    stack = getattr(_state, "stack", None)
    return stack[-1] if stack else None


def data_sharding(mesh: Mesh | None = None, ndim: int = 2) -> NamedSharding | None:
    """Sharding for a batch: leading axis split over "data", rest replicated."""
    mesh = mesh or current_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, P(DATA_AXIS, *([None] * (ndim - 1))))


def model_sharding(mesh: Mesh | None = None, ndim: int = 2) -> NamedSharding | None:
    """Sharding for a weight/feature-block matrix: last axis over "model"."""
    mesh = mesh or current_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, P(*([None] * (ndim - 1)), MODEL_AXIS))


def replicated_sharding(mesh: Mesh | None = None) -> NamedSharding | None:
    """Full replication — the successor of Spark ``sc.broadcast``."""
    mesh = mesh or current_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, P())


def data_sharding_fn(mesh: Mesh | None):
    """Per-chunk placement callable for the staging engine: maps a chunk
    — a bare array OR a pytree of arrays (the fused fit stages
    (data, labels) pairs) — to rank-matched data-axis sharding specs
    per leaf (None mesh → None, plain placement). The ONE home of the
    chunk→spec rule."""
    if mesh is None:
        return None
    import jax

    return lambda chunk: jax.tree_util.tree_map(
        lambda leaf: data_sharding(mesh, getattr(leaf, "ndim", 1)), chunk
    )


def data_axis_size(mesh: Mesh | None) -> int:
    """Size of the "data" axis; 1 for no mesh or a mesh without one —
    the ONE home of the shard-count read (planner, staging)."""
    if mesh is None:
        return 1
    try:
        return int(dict(mesh.shape).get(DATA_AXIS, 1))
    except Exception:  # noqa: BLE001 — foreign mesh-like object
        return 1


def shard_chunk_size(chunk_size: int, mesh: Mesh | None) -> int:
    """``chunk_size`` rounded UP to a data-axis multiple, so a staged
    chunk splits into even, static shard shapes."""
    n = data_axis_size(mesh)
    return -(-int(chunk_size) // n) * n


def pad_batch(
    x: np.ndarray | jax.Array, multiple: int
) -> tuple[np.ndarray | jax.Array, int]:
    """Zero-pad the leading axis to a multiple; returns (padded, n_valid).

    XLA needs static, evenly-divisible shard shapes where Spark tolerated
    ragged partitions. Downstream reductions must mask rows >= n_valid
    (evaluators and solvers in this framework all accept ``n_valid``).
    """
    n = x.shape[0]
    target = math.ceil(n / multiple) * multiple if n else multiple
    if target == n:
        return x, n
    pad_widths = [(0, target - n)] + [(0, 0)] * (x.ndim - 1)
    if isinstance(x, np.ndarray):
        return np.pad(x, pad_widths), n
    import jax.numpy as jnp

    return jnp.pad(x, pad_widths), n


def shard_batch(
    x,
    mesh: Mesh | None = None,
    *,
    pad: bool = True,
):
    """Place a host batch onto the mesh, sharded over the "data" axis.

    Pads the leading axis to the data-axis size when ``pad`` (returns the
    original row count via the companion :func:`pad_batch` if you need it —
    here the padded array only). Without a mesh: plain ``device_put``.
    """
    mesh = mesh or current_mesh()
    import jax.numpy as jnp

    x = jnp.asarray(x) if not isinstance(x, (np.ndarray, jax.Array)) else x
    if mesh is None:
        return jax.device_put(x)
    n_data = mesh.shape[DATA_AXIS]
    if pad and x.shape[0] % n_data:
        x, _ = pad_batch(x, n_data)
    return jax.device_put(x, data_sharding(mesh, x.ndim))
