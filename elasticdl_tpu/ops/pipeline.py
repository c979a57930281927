"""GPipe-style pipeline parallelism over the mesh `pipe` axis.

Net-new TPU capability relative to the reference (SURVEY.md §2: upstream
ships data parallelism plus sharded embeddings ONLY — no pipeline
parallelism).  Design is TPU-first rather than a port of any GPU pipeline
runtime:

- The layer stack is ONE stacked pytree (leading `num_layers` axis) whose
  leaves are sharded over `pipe`, so stage s holds layers
  [s*L/P, (s+1)*L/P) in HBM — no per-stage processes, no RPC.
- Scheduling is a single `lax.scan` over M + P - 1 ticks inside
  `shard_map`: every tick each stage applies its local layers to its
  current microbatch and hands the activation to the next stage with
  `jax.lax.ppermute` (a neighbor hop over ICI).  XLA compiles the whole
  schedule into one fused loop; there is no host-side orchestration per
  microbatch.
- Backward is just `jax.grad` through the scan: `ppermute` transposes to
  the reverse rotation, so the backward pipeline runs in the opposite
  direction automatically — no hand-written 1F1B state machine.

The classic GPipe bubble (P - 1 idle ticks out of M + P - 1) is the cost;
choose num_microbatches >= 4 * stages to keep it under ~20%.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.parallel.mesh import DATA_AXIS, PIPE_AXIS


def _sequential(apply_fn: Callable, stacked_params: Any, x):
    """Reference semantics: layers applied in order (pipe axis of size 1)."""

    def body(h, p):
        return apply_fn(p, h), None

    return lax.scan(body, x, stacked_params)[0]


def _pipeline_local(
    stacked_local_params: Any,
    x: jnp.ndarray,
    *,
    apply_fn: Callable,
    stages: int,
    num_microbatches: int,
    data_axis: str,
    pipe_axis: str,
    remat: bool,
):
    """Runs INSIDE shard_map.  x: (B_local, ...) activations for this data
    shard (replicated over `pipe`); stacked_local_params: this stage's
    (L/P, ...) slice of the layer stack."""
    mstages, batch = stages, x.shape[0]
    mcount = num_microbatches
    stage = lax.axis_index(pipe_axis)
    micro = x.reshape((mcount, batch // mcount) + x.shape[1:])

    def apply_stage(h):
        def body(h2, p):
            return apply_fn(p, h2), None

        return lax.scan(body, h, stacked_local_params)[0]

    if remat:
        apply_stage = jax.checkpoint(apply_stage)

    def varying(v):
        return lax.pcast(v, (data_axis, pipe_axis), to="varying")

    mb_shape = micro.shape[1:]
    state0 = varying(jnp.zeros(mb_shape, x.dtype))
    out0 = varying(jnp.zeros(micro.shape, x.dtype))
    # forward rotation only: stage 0 never receives, it feeds fresh
    # microbatches, so the hop P-1 -> 0 is omitted (no wrap traffic)
    perm = [(i, i + 1) for i in range(mstages - 1)]

    def tick(carry, t):
        state, out_buf = carry
        recv = lax.ppermute(state, pipe_axis, perm) if perm else state
        feed = lax.dynamic_index_in_dim(
            micro, jnp.minimum(t, mcount - 1), axis=0, keepdims=False
        )
        h_in = jnp.where(stage == 0, feed, recv)
        h_out = apply_stage(h_in)
        # the last stage's output at tick t is microbatch t-(P-1); ticks
        # before the pipeline fills write garbage to slot 0, which tick
        # t = P-1 then overwrites with the real microbatch 0
        slot = jnp.clip(t - (mstages - 1), 0, mcount - 1)
        out_buf = lax.dynamic_update_index_in_dim(out_buf, h_out, slot, 0)
        return (h_out, out_buf), None

    (_, out_buf), _ = lax.scan(
        tick, (state0, out0), jnp.arange(mcount + mstages - 1)
    )
    out = out_buf.reshape(x.shape)
    # only the last stage holds real outputs; psum both broadcasts them to
    # every pipe shard (making the result pipe-invariant, as the unmapped
    # out_spec requires) and zeroes nothing real (other stages contribute 0)
    out = jnp.where(stage == mstages - 1, out, jnp.zeros_like(out))
    return lax.psum(out, pipe_axis)


def gpipe_spmd(
    apply_fn: Callable,
    stacked_params: Any,
    x: jnp.ndarray,
    mesh,
    num_microbatches: int = 8,
    data_axis: str = DATA_AXIS,
    pipe_axis: str = PIPE_AXIS,
    remat: bool = False,
):
    """Apply a stacked layer pytree to x as a pipeline over mesh[`pipe`].

    apply_fn: (one_layer_params, h) -> h, shape-preserving (transformer
              block contract).
    stacked_params: pytree whose leaves have leading dim num_layers,
              sharded P(pipe) on that dim (pipeline_param_sharding).
    x:        (B, ...) activations, batch sharded P(data).

    Degenerates to a plain sequential scan when the pipe axis is 1 — so a
    model configured for pipelining trains identically (same param tree,
    same numerics) on a mesh without a pipe dimension; checkpoints move
    between the two meshes unchanged (the cross-mesh restore story,
    tests/test_remesh.py).
    """
    stages = mesh.shape[pipe_axis]
    num_layers = jax.tree.leaves(stacked_params)[0].shape[0]
    from elasticdl_tpu.parallel.mesh import in_export_mode

    if stages == 1 or in_export_mode():
        # pipe=1 — or serving export, where shard_map cannot stage
        # through jax2tf: the sequential scan is the same computation on
        # the same stacked param tree.
        return _sequential(apply_fn, stacked_params, x)
    if num_layers % stages:
        raise ValueError(
            f"num_layers={num_layers} not divisible by pipe={stages}"
        )
    local_batch = x.shape[0] // mesh.shape[data_axis]
    if local_batch % num_microbatches:
        raise ValueError(
            f"per-data-shard batch {local_batch} not divisible by "
            f"num_microbatches={num_microbatches}"
        )
    fn = functools.partial(
        _pipeline_local,
        apply_fn=apply_fn,
        stages=stages,
        num_microbatches=num_microbatches,
        data_axis=data_axis,
        pipe_axis=pipe_axis,
        remat=remat,
    )
    param_spec = jax.tree.map(lambda _: P(pipe_axis), stacked_params)
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(param_spec, P(data_axis)),
        out_specs=P(data_axis),
    )(stacked_params, x)
