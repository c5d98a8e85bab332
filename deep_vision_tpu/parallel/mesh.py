"""Device-mesh helpers: the framework's single source of parallelism.

The reference reached multi-device scale three different ways
(``nn.DataParallel`` — ResNet/pytorch/train.py:352-355, ``multi_gpu_model`` —
ResNet/tensorflow/train.py:247-251, ``tf.distribute.MirroredStrategy`` —
YOLO/tensorflow/train.py:281-296).  Here there is exactly one mechanism: a
``jax.sharding.Mesh`` with a ``data`` axis (and an optional ``model`` axis for
tensor parallelism).  Batches are sharded over ``data``; parameters are
replicated (or sharded over ``model``); XLA inserts the gradient all-reduce
(the psum the reference got implicitly from NCCL) over ICI.

Everything works identically on 1 device, 8 CPU "virtual" devices (tests), or
a multi-host pod: ``jit`` + GSPMD scales without code changes.
"""

from __future__ import annotations

import math
from typing import Any, Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deep_vision_tpu.obs import launch

DATA_AXIS = "data"
MODEL_AXIS = "model"
SPATIAL_AXIS = "spatial"  # image-row (context) axis — see parallel/spatial.py


def make_mesh(
    axis_sizes: Mapping[str, int] | None = None,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a Mesh.  Default: all devices on a single ``data`` axis.

    ``axis_sizes`` maps axis name -> size, e.g. ``{"data": 4, "model": 2}``.
    A size of -1 means "all remaining devices".
    """
    if devices is None:
        # the package's first look at the devices starts the runtime where
        # nobody has: the launch record's ``backend`` stage
        with launch.start().once("backend"):
            devices = jax.devices()
    if axis_sizes is None:
        axis_sizes = {DATA_AXIS: len(devices)}
    names = tuple(axis_sizes.keys())
    sizes = list(axis_sizes.values())
    if -1 in sizes:
        known = math.prod(s for s in sizes if s != -1)
        sizes[sizes.index(-1)] = len(devices) // known
    total = math.prod(sizes)
    if total > len(devices):
        raise ValueError(
            f"mesh {dict(zip(names, sizes))} needs {total} devices, "
            f"have {len(devices)}"
        )
    grid = np.asarray(devices[:total]).reshape(tuple(sizes))
    return Mesh(grid, names)


def batch_sharding(mesh: Mesh, ndim: int = 0) -> NamedSharding:
    """Sharding that splits dim 0 over the ``data`` axis (rest replicated)."""
    if ndim == 0:
        return NamedSharding(mesh, P(DATA_AXIS))
    return NamedSharding(mesh, P(DATA_AXIS, *([None] * (ndim - 1))))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def replicate(tree: Any, mesh: Mesh) -> Any:
    """Fully replicate a pytree over the mesh (params, opt state, ...).

    Works on multi-process meshes too: every process holds the same host
    value (same seed / same restore), so each contributes its addressable
    replicas via ``make_array_from_process_local_data``."""
    sharding = replicated_sharding(mesh)
    if jax.process_count() > 1:
        return jax.tree_util.tree_map(
            lambda x: jax.make_array_from_process_local_data(
                sharding, np.asarray(x)), tree)
    return jax.device_put(tree, sharding)


def shard_batch(tree: Any, mesh: Mesh) -> Any:
    """Device-put a host batch with dim 0 split over the ``data`` axis.

    The global batch size must be divisible by the ``data`` axis size —
    the same contract MirroredStrategy enforced with
    ``global_batch = replicas * per_replica`` (YOLO/tensorflow/train.py:282).

    On a mesh with a ``spatial`` axis, image-like leaves (ndim ≥ 4, H
    divisible) additionally shard dim 1 (rows) over it — GSPMD then
    spatially partitions the convolutions downstream, inserting the halo
    collective-permutes itself, so activations larger than one chip's HBM
    train with NO model changes (the Trainer-reachable counterpart of the
    explicit shard_map kernel in parallel/spatial.py).
    """
    n_data = mesh.shape[DATA_AXIS]
    n_spatial = mesh.shape.get(SPATIAL_AXIS, 1)
    # multi-process: the host batch is this process's LOCAL shard (loaders
    # shard files per host); each process contributes its portion of the
    # global array (the tf.data per-worker dataset semantics)
    multiproc = jax.process_count() > 1

    def _put(x):
        if isinstance(x, jax.Array):  # already placed (e.g. prefetch thread)
            return x
        x = np.asarray(x)
        if x.ndim == 0:
            if multiproc:
                return jax.make_array_from_process_local_data(
                    replicated_sharding(mesh), x)
            return jax.device_put(x, replicated_sharding(mesh))
        global_batch = x.shape[0] * (jax.process_count() if multiproc else 1)
        if global_batch % n_data != 0:
            raise ValueError(
                f"global batch {global_batch} (local {x.shape[0]}) not "
                f"divisible by data axis {n_data}")
        spec = [DATA_AXIS] + [None] * (x.ndim - 1)
        if n_spatial > 1 and x.ndim >= 4 and x.shape[1] % n_spatial == 0:
            spec[1] = SPATIAL_AXIS  # rows over the spatial axis
        sharding = NamedSharding(mesh, P(*spec))
        if multiproc:
            return jax.make_array_from_process_local_data(sharding, x)
        return jax.device_put(x, sharding)

    return jax.tree_util.tree_map(_put, tree)
