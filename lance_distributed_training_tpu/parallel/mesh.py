"""Device mesh + sharding utilities.

Replaces the reference's process-group lifecycle
(``dist.init_process_group`` … ``destroy_process_group``,
``/root/reference/lance_iterable.py:79-80,131-132``) with JAX's model:
``jax.distributed.initialize()`` once per host, a ``Mesh`` over all devices,
and ``NamedSharding`` annotations that make XLA insert the collectives
(gradient ``psum`` rides ICI, not host code).

The mesh has a leading ``data`` axis (the reference's only parallelism is
DDP — SURVEY.md §2.3) plus an optional trailing ``model`` axis so model
sharding can be added without redesign.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "get_mesh",
    "batch_sharding",
    "replicated_sharding",
    "make_global_batch",
    "process_topology",
    "sync_global_devices",
    "maybe_initialize_distributed",
]


_distributed_initialized = False


def maybe_initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host rendezvous — the ``init_process_group`` equivalent.

    Explicit args mirror torchrun's ``MASTER_ADDR``/``WORLD_SIZE``/``RANK``
    injection (``/root/reference/lance_iterable.py:154-156``); with no args,
    rendezvous happens only when the environment provides it
    (``JAX_COORDINATOR_ADDRESS``, or a TPU pod runtime where
    ``jax.distributed.initialize()`` self-discovers). Safe no-op when
    single-process — the reference's ``--no_ddp`` escape hatch
    (``lance_iterable.py:75,145,149-151``) is the default here: topology is
    discovered, never required.

    MUST run before anything initializes the XLA backend (jax raises
    otherwise) — so no ``jax.process_count()``/``jax.devices()`` guards here;
    idempotence comes from a module flag.
    """
    global _distributed_initialized
    if _distributed_initialized:
        return
    if coordinator_address is not None:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        _distributed_initialized = True
    elif os.environ.get("JAX_COORDINATOR_ADDRESS"):
        jax.distributed.initialize()
        _distributed_initialized = True


def get_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    data_axis: str = "data",
    model_axis: Optional[str] = "model",
    model_parallelism: int = 1,
    seq_axis: Optional[str] = "seq",
    seq_parallelism: int = 1,
    pipe_axis: Optional[str] = "pipe",
    pipe_parallelism: int = 1,
) -> Mesh:
    """Build the device mesh.

    Default is the reference-parity topology: 1-D ``('data',)`` over all
    devices (DDP, SURVEY.md §2.3). ``model_parallelism`` adds a trailing
    tensor-parallel axis, ``seq_parallelism`` a sequence/context-parallel axis
    (ring attention rides it, :mod:`.ring_attention`); the data axis absorbs
    the remaining devices. Axis order is ``(data, model, seq)`` — data
    outermost so its collectives (gradient psum) span the slower links when a
    multi-host mesh maps ICI-first.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    n = len(devices)
    mp = model_parallelism if model_axis is not None else 1
    sp = seq_parallelism if seq_axis is not None else 1
    pp = pipe_parallelism if pipe_axis is not None else 1
    if mp < 1 or sp < 1 or pp < 1:
        raise ValueError(
            f"parallelism degrees must be >=1, got {mp=} {sp=} {pp=}"
        )
    if n % (mp * sp * pp):
        raise ValueError(
            f"{n} devices not divisible by model*seq*pipe parallelism="
            f"{mp * sp * pp}"
        )
    shape, axes = [n // (mp * sp * pp)], [data_axis]
    if mp > 1:
        shape.append(mp)
        axes.append(model_axis)
    if sp > 1:
        shape.append(sp)
        axes.append(seq_axis)
    if pp > 1:
        shape.append(pp)
        axes.append(pipe_axis)
    return Mesh(np.array(devices).reshape(shape), tuple(axes))


def batch_sharding(mesh: Mesh, data_axis: str = "data") -> NamedSharding:
    """Sharding for a global batch: leading dim split over the data axis."""
    return NamedSharding(mesh, P(data_axis))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully-replicated sharding (params/opt-state in pure DP)."""
    return NamedSharding(mesh, P())


def make_global_batch(
    pytree,
    mesh: Mesh,
    data_axis: str = "data",
    seq_axis: Optional[str] = None,
):
    """Host numpy arrays → one *global* ``jax.Array`` batch-sharded over the mesh.

    The TPU-native answer to the reference's per-rank ``.to(device)`` copies
    (``/root/reference/lance_iterable.py:108-109``): each process contributes
    its local shard; JAX assembles the logical global array. Works both
    single-process (local data = global data, split across local devices) and
    multi-process (``jax.make_array_from_process_local_data``).

    With ``seq_axis`` set, rank-2 leaves (token arrays ``[B, S]``) are
    additionally split along the sequence axis — context parallelism's input
    layout (each device holds a [batch-shard × sequence-block] tile).

    This is the *synchronous* placement primitive (and the bit-parity
    reference the placement plane's tests pin against); the trainer's
    path is :class:`~..data.placement.PlacementPlane`, which
    dispatches the same transfers from a background thread so they overlap
    the step. ``device_put`` routes through ``_compat`` — the one H2D door
    LDT801 allows outside ``data/placement.py``.
    """
    from ._compat import device_put, make_array_from_process_local_data
    from .sharding import batch_partition_spec

    def _put(x, replicate: bool = False):
        x = np.asarray(x)
        if replicate:
            spec = P()
        else:
            spec = batch_partition_spec(x.ndim, data_axis=data_axis,
                                        seq_axis=seq_axis)
        sharding = NamedSharding(mesh, spec)
        if jax.process_count() == 1:
            return device_put(x, sharding)
        return make_array_from_process_local_data(sharding, x)

    if isinstance(pytree, dict):
        # Ragged token leaves (data/token_pack.py convention) have no
        # per-row leading dim to split — a flat values page replicates;
        # _host_* metadata stays numpy (the pack transform reads its grid
        # shape host-side, zero device syncs).
        from ..data.token_pack import is_host_meta_key, is_ragged_key

        return {
            k: (
                np.asarray(v) if is_host_meta_key(k)
                else _put(v, replicate=is_ragged_key(k))
            )
            for k, v in pytree.items()
        }
    return jax.tree_util.tree_map(_put, pytree)


def process_topology() -> tuple[int, int]:
    """(process_index, process_count) — torchrun's RANK/WORLD_SIZE equivalent
    (``/root/reference/lance_iterable.py:154-156``), discovered not injected."""
    return jax.process_index(), jax.process_count()


def sync_global_devices(name: str = "barrier") -> None:
    """Cross-host barrier — the ``dist.barrier()`` equivalent
    (``/root/reference/torch_version/map_style.py:50,55``)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)
