"""Device-mesh management: the TPU-native replacement for the reference's
process-level cluster topology (PS shards + Horovod ring — SURVEY.md C15/C16).

All parallelism in elasticdl-tpu is expressed as a `jax.sharding.Mesh` with
up to five logical axes:

  data     — data parallelism (the reference's only strategy)
  model    — sharded embedding tables / tensor parallelism
  seq      — sequence/context parallelism (ring attention)
  expert   — expert parallelism (MoE)
  pipe     — pipeline parallelism (GPipe microbatch schedule, ops/pipeline)

Elasticity = rebuilding the mesh when membership changes: the rendezvous
server bumps an epoch, every process re-initialises jax.distributed with the
new topology, `create_mesh` lays the surviving devices out again, and the
train step recompiles for the new shapes (state restored from Orbax).  The
task queue makes this cheap — no step-exact replay, just re-leased shards.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
EXPERT_AXIS = "expert"
PIPE_AXIS = "pipe"

# Trace-time mesh context: model code (e.g. ring attention inside a Flax
# module) needs the mesh for shard_map, but zoo `custom_model()` factories
# are mesh-agnostic.  The Trainer sets this before tracing/executing steps.
# THREAD-local: a background prewarm compile (Trainer.prewarm_*) traces
# under a different mesh concurrently with the training thread.
import contextlib as _contextlib
import threading as _threading

_MESH_TLS = _threading.local()
_DEFAULT_MESH: "Optional[Mesh]" = None


def set_current_mesh(mesh: "Mesh") -> None:
    global _DEFAULT_MESH
    _MESH_TLS.mesh = mesh
    # also serves as the cross-thread default: helper threads that never
    # set a mesh (data loaders calling feed etc.) see the training mesh
    _DEFAULT_MESH = mesh


# Export mode: serving export (jax2tf -> TF SavedModel) cannot stage
# shard_map or Pallas custom calls.  Inside this context, mesh-manual ops
# (ring attention, GPipe schedule, flash kernel) switch to their
# numerically-identical single-device lax formulations — the param tree is
# unchanged by design, so a checkpoint trained on any mesh exports.
_EXPORT_MODE = _threading.local()


@_contextlib.contextmanager
def export_mode():
    prev = getattr(_EXPORT_MODE, "on", False)
    _EXPORT_MODE.on = True
    try:
        yield
    finally:
        _EXPORT_MODE.on = prev


def in_export_mode() -> bool:
    return getattr(_EXPORT_MODE, "on", False)


def set_thread_mesh(mesh: "Mesh") -> None:
    """Thread-local ONLY (no cross-thread default update): for background
    work — prewarm compiles — that must not leak its mesh to others."""
    _MESH_TLS.mesh = mesh


def get_current_mesh() -> "Mesh":
    mesh = getattr(_MESH_TLS, "mesh", None)
    if mesh is not None:
        return mesh
    if _DEFAULT_MESH is not None:
        return _DEFAULT_MESH
    return create_mesh()


def create_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    data: int = -1,
    model: int = 1,
    seq: int = 1,
    expert: int = 1,
    pipe: int = 1,
) -> Mesh:
    """Build a mesh over `devices` (default: all).  `data=-1` absorbs the
    remaining devices after the explicit axes are carved out."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    fixed = model * seq * expert * pipe
    if data == -1:
        if n % fixed:
            raise ValueError(
                f"{n} devices not divisible by model*seq*expert*pipe={fixed}"
            )
        data = n // fixed
    if data * fixed != n:
        raise ValueError(
            f"mesh {data}x{model}x{seq}x{expert}x{pipe} != {n} devices"
        )
    # pipe is the OUTERMOST axis: neighbor stages land on ICI-adjacent
    # device groups, and the data/model/seq axes stay contiguous within a
    # stage (the same layout logic that keeps gradient reductions on ICI)
    arr = np.array(devices).reshape(pipe, data, model, seq, expert)
    return Mesh(
        arr, (PIPE_AXIS, DATA_AXIS, MODEL_AXIS, SEQ_AXIS, EXPERT_AXIS)
    )


def data_sharding(mesh: Mesh) -> NamedSharding:
    """Batch sharding: leading axis split over `data` (replicated over the
    other mesh axes)."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch(batch: Dict[str, np.ndarray], mesh: Mesh):
    """Place a host batch onto the mesh split along the data axis.

    The dedup'd id plane (data/wire.py) is the one structured leaf: only
    its `inverse8` plane is batch-major; the unique/starts/exc_val side
    planes are whole-batch tables every shard reads, so they replicate
    (splitting them over `data` would be wrong — and (F,) `starts` does
    not even divide the axis)."""
    from elasticdl_tpu.data.wire import is_packed_dedup

    sharding = data_sharding(mesh)
    repl = replicated(mesh)

    def put(x):
        if is_packed_dedup(x):
            return {
                k: jax.device_put(v, sharding if k == "inverse8" else repl)
                for k, v in x.items()
            }
        return jax.device_put(x, sharding)

    return jax.tree.map(put, batch, is_leaf=is_packed_dedup)


def make_global_batch(batch: Dict[str, np.ndarray], mesh: Mesh):
    """Assemble a host batch into global `jax.Array`s split along `data`.

    Multi-process SPMD path: every process passes the SAME full global
    batch (each rank reads the whole shard); `make_array_from_callback`
    transfers only the locally-addressable shards, so no host holds or
    ships more than its slice to devices.  Works identically in
    single-process mode, where it degenerates to a plain sharded put.
    """
    sharding = data_sharding(mesh)

    def to_global(x):
        x = np.asarray(x)
        return jax.make_array_from_callback(
            x.shape, sharding, lambda idx: x[idx]
        )

    return jax.tree.map(to_global, batch)


def local_batch_range(mesh: Mesh, global_batch_size: int):
    """Rows [start, stop) of a data-sharded global batch that THIS
    process's addressable devices hold, or None when they are not one
    contiguous row range (exotic device layouts — callers then fall back
    to full-batch reads).  This is what lets each rank read only its
    1/world_size slice of a task's records (SURVEY §3.3: per-worker
    disjoint reads) instead of every rank reading the whole shard."""
    sharding = data_sharding(mesh)
    index_map = sharding.addressable_devices_indices_map(
        (global_batch_size,)
    )
    spans = set()
    for idx in index_map.values():
        sl = idx[0]
        start = 0 if sl.start is None else sl.start
        stop = global_batch_size if sl.stop is None else sl.stop
        spans.add((start, stop))
    starts = sorted(spans)
    lo, hi = starts[0][0], starts[0][1]
    for start, stop in starts[1:]:
        if start > hi:
            return None  # hole between this process's row spans
        hi = max(hi, stop)
    return lo, hi


def make_global_batch_from_local(
    batch: Dict[str, np.ndarray], mesh: Mesh, global_batch_size: int,
    local_start: int,
):
    """Assemble global `jax.Array`s from ONLY this process's local rows
    (`local_batch_range` slice starting at `local_start` in global
    coordinates).  The callback is invoked for addressable shards only,
    so no host materializes — or reads — rows outside its slice."""
    sharding = data_sharding(mesh)

    def to_global(x):
        x = np.asarray(x)
        shape = (global_batch_size,) + x.shape[1:]

        def fetch(idx):
            sl = idx[0]
            start = (0 if sl.start is None else sl.start) - local_start
            stop = (
                global_batch_size if sl.stop is None else sl.stop
            ) - local_start
            if start < 0 or stop > len(x):
                raise IndexError(
                    "requested global rows outside this rank's local "
                    "slice (local_batch_range mismatch)"
                )
            return x[start:stop]

        return jax.make_array_from_callback(shape, sharding, fetch)

    return jax.tree.map(to_global, batch)


# What a tiered store's feed hangs on a batch (store/tiered.py: attach):
# an admission plan, or in deferred mode the raw sparse batch with its
# ranking.  Host bookkeeping, not rows of the batch: `pad_to_multiple`
# carries them around the pad as `Trainer.stage_batch` does around the
# shard.
STORE_KEYS = ("__store_plan__", "__store_sparse__")


def pad_to_multiple(batch: Dict[str, np.ndarray], multiple: int):
    """Pad batch leading dim up to a multiple (wrapping existing rows) so
    shapes stay static under jit; returns (padded_batch, real_count)."""
    carried = {k: batch[k] for k in STORE_KEYS if k in batch}
    if carried:
        batch = {k: v for k, v in batch.items() if k not in carried}
    sizes = {x.shape[0] for x in jax.tree.leaves(batch)}
    assert len(sizes) == 1, "ragged batch"
    n = sizes.pop()
    if n % multiple == 0:
        return {**batch, **carried} if carried else batch, n
    target = ((n + multiple - 1) // multiple) * multiple
    reps = (target + n - 1) // n

    def pad(x):
        return np.concatenate([x] * reps, axis=0)[:target]

    if "__store_sparse__" in carried:
        # the wrapped rows repeat ids, so the feed's ranking no longer
        # counts the batch: the trainer's `prepare` ranks it again
        sparse, _ = carried["__store_sparse__"]
        carried["__store_sparse__"] = (pad(np.asarray(sparse)), None)
    return {**jax.tree.map(pad, batch), **carried}, n
