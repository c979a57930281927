"""Ring attention: sequence/context parallelism over the mesh `seq` axis.

Net-new TPU capability relative to the reference (SURVEY.md §5 records the
reference has NO sequence parallelism; long-context is first-class here).
Design follows the blockwise ring-attention recipe (Liu et al.; see
PAPERS.md): Q stays resident per shard, K/V blocks rotate around the ring
via `jax.lax.ppermute` over ICI, and attention accumulates with the online
(flash) softmax — running max `m`, normaliser `l`, unnormalised output `o`
rescaled as blocks arrive.  Peak memory per chip is O(L_local^2) instead of
O(L^2), and the N-step rotation overlaps compute with neighbor transfers.

Everything is expressed with static-shape `lax.scan` + collectives so XLA
compiles one fused loop; no data-dependent Python control flow.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS

_NEG_INF = -1e30


def _ring_attention_local(
    q, k, v, *, ring_size: int, axis_name: str, causal: bool, scale: float,
    varying_axes: tuple,
):
    """Runs INSIDE shard_map.  q/k/v: (B, L_local, H, D) local blocks."""
    batch, q_len, heads, dim = q.shape
    k_len = k.shape[1]
    my_block = jax.lax.axis_index(axis_name)
    q_pos = my_block * q_len + jnp.arange(q_len)          # global positions

    perm = [(j, (j + 1) % ring_size) for j in range(ring_size)]

    # accumulators: (B, H, Lq) softmax stats, (B, H, Lq, D) output.
    # pcast-to-varying marks them as shard-varying so the scan carry
    # types match the per-shard loop outputs.
    def _varying(x):
        return jax.lax.pcast(x, varying_axes, to="varying")

    m0 = _varying(jnp.full((batch, heads, q_len), _NEG_INF, jnp.float32))
    l0 = _varying(jnp.zeros((batch, heads, q_len), jnp.float32))
    o0 = _varying(jnp.zeros((batch, heads, q_len, dim), jnp.float32))

    def step(carry, step_idx):
        o, m, l, k_cur, v_cur = carry
        # the block currently held arrived from shard (my - step) mod n
        src_block = (my_block - step_idx) % ring_size
        k_pos = src_block * k_len + jnp.arange(k_len)
        logits = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k_cur,
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]        # (Lq, Lk)
            logits = jnp.where(mask[None, None], logits, _NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        # guard fully-masked rows (m_new == -inf): keep weights at zero
        p = jnp.exp(logits - m_new[..., None])
        p = jnp.where(jnp.isfinite(logits), p, 0.0)
        correction = jnp.exp(m - m_new)
        l_new = l * correction + p.sum(axis=-1)
        o_new = o * correction[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_cur,
            preferred_element_type=jnp.float32,
        )
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return (o_new, m_new, l_new, k_next, v_next), None

    (o, m, l, _, _), _ = jax.lax.scan(
        step, (o0, m0, l0, k, v), jnp.arange(ring_size)
    )
    out = o / jnp.maximum(l, 1e-30)[..., None]             # (B, H, Lq, D)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)       # (B, Lq, H, D)


def ring_self_attention(
    q, k, v, mesh, causal: bool = False, scale: Optional[float] = None,
    data_axis: str = DATA_AXIS, seq_axis: str = SEQ_AXIS,
):
    """Sequence-parallel attention over `mesh`'s seq axis.

    q/k/v: (B, L, H, D) GLOBAL arrays (sharded or shardable as
    P(data, seq, None, None)); returns same shape/sharding.
    Degenerates to one local flash-style pass when the seq axis is 1.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    from elasticdl_tpu.parallel.mesh import in_export_mode

    if in_export_mode():
        # Serving export: jax2tf cannot stage shard_map/Pallas; the plain
        # lax formulation is numerically the same computation.
        return full_attention_reference(q, k, v, causal=causal, scale=scale)
    ring_size = mesh.shape[seq_axis]
    spec = P(data_axis, seq_axis, None, None)
    if ring_size == 1:
        # Sequence axis unsharded: every K/V block is local, so skip the
        # ring machinery and run the Pallas flash kernel (same online
        # softmax, tiled in VMEM — ops/flash_attention.py).  Still under
        # shard_map over the SAME specs: each data shard runs the kernel
        # on its local batch, so inputs stay batch-sharded and the output
        # keeps the documented sharding (a bare call would force full
        # replication under jit).  Tile-shape constraints (L % 128,
        # D <= 128) take the fused-lax ring body with ring size 1 instead
        # — dispatched on an EXPLICIT shape check: a blanket
        # `except ValueError` here once swallowed a shard_map vma error
        # and silently downgraded every single-chip run (bench included)
        # to the O(L^2) path (round-5 on-chip profile finding).
        # The vma audit is on when Mosaic compiles the kernel (it types
        # its outputs' vma from its inputs) and off in interpret mode
        # (CPU tests), which re-evaluates the kernel body where the
        # block-slicing internals mix varying and invariant operands and
        # fail the audit; the wrapper's in/out specs pin the sharding
        # contract either way.
        from elasticdl_tpu.ops.flash_attention import (
            use_interpret,
            flash_attention,
            flash_shapes_ok,
        )

        if flash_shapes_ok(q.shape, k.shape):
            return jax.shard_map(
                functools.partial(
                    flash_attention, causal=causal, scale=scale
                ),
                mesh=mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
                check_vma=not use_interpret(),
            )(q, k, v)
    fn = functools.partial(
        _ring_attention_local,
        ring_size=ring_size,
        axis_name=seq_axis,
        causal=causal,
        scale=scale,
        varying_axes=(data_axis, seq_axis),
    )
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)


def full_attention_reference(q, k, v, causal: bool = False,
                             scale: Optional[float] = None):
    """O(L^2) single-device attention — the numerical reference ring
    attention is validated against in tests."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        q_len, k_len = q.shape[1], k.shape[1]
        mask = jnp.arange(q_len)[:, None] >= jnp.arange(k_len)[None, :]
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bhqd", weights, v, preferred_element_type=jnp.float32
    )
    return out.transpose(0, 2, 1, 3).astype(q.dtype)
