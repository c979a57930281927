"""Pallas TPU flash-attention kernel (single-shard fast path).

The framework's attention stack has two tiers (SURVEY.md §5 long-context —
net-new capability vs the reference, which has no attention ops at all):

- cross-chip: `ops/ring_attention.py` rotates K/V blocks over ICI with
  online-softmax accumulation (sequence scales with chips);
- on-chip (this module): a hand-written Pallas kernel computes the local
  attention with the same online softmax, tiled for the MXU/VMEM instead
  of materialising the (L, L) score matrix in HBM.  Used by
  `ring_self_attention` when the mesh's `seq` axis is 1 (every block is
  local) and directly by models.

Kernel shape (round 5 — second generation): inputs stay in the model's
native (B, L, H, D) layout viewed as (B, L, H*D) — a FREE reshape — and
the grid runs over (B, Lq/BLOCK_Q) with a static per-head loop inside
each program slicing D-wide column chunks.  The first-generation kernel
merged to (B*H, L, D) via transposes that were pure layout copies in
the BERT step and ran more, smaller grid programs; this layout deletes
the transposes (`PERF.md` §7 has the history; the kernel's time on the
v5e is `flash_fwd_ms_per_step`, `PERF.md` §5).  Each program holds one Q tile resident in VMEM and
streams K/V tiles, carrying the running max `m`, normaliser `l` and
unnormalised accumulator in f32.  Causal masking prunes whole K tiles
above the diagonal.  The FORWARD is O(L) in HBM (nothing (L, L)-shaped
is ever materialised; only the log-sum-exp is saved).  Backward is a
`jax.custom_vjp` that recomputes probabilities from the saved
log-sum-exp in plain jnp on the (B, L, H, D) layout — XLA fuses it, but
its einsum operands are O(L^2), so truly long-context TRAINING belongs
to the ring tier (sequence sharded over chips), where per-chip lengths
stay modest.

Off-TPU the kernel runs in Pallas interpret mode (tests exercise the SAME
kernel code path on CPU).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int, causal: bool,
    scale: float, q_len: int, k_len: int, block_q: int, heads: int,
    dim: int,
):
    qi = pl.program_id(1)
    # operands stay in the INPUT dtype (bf16 in mixed-precision training)
    # so the MXU runs at full rate — f32 upcasts before the dots would
    # quarter the matmul rate on v5e; accumulation is f32 via
    # preferred_element_type, softmax math is f32.
    q_all = q_ref[0]                                    # (BLOCK_Q, H*D)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0
    )
    num_kb = k_len // block_k
    if causal:
        # K tiles strictly above this Q tile's diagonal are all-masked:
        # stop the stream early instead of computing and zeroing them.
        last_kb = jnp.minimum(
            (qi + 1) * block_q + block_k - 1, k_len
        ) // block_k
        num_iters = jnp.minimum(num_kb, last_kb)
    else:
        num_iters = num_kb

    # STATIC head loop (Mosaic has no dynamic_slice on values): each head
    # is a D-wide column chunk of the (BLOCK_Q, H*D) tile; the compiler
    # reuses one set of scratch buffers across the unrolled iterations.
    for h in range(heads):
        lo = h * dim
        q = q_all[:, lo:lo + dim]                       # (BLOCK_Q, D)

        def body(kb, carry, lo=lo, q=q):
            o, m, l = carry
            k = k_ref[0, pl.ds(kb * block_k, block_k), lo:lo + dim]
            v = v_ref[0, pl.ds(kb * block_k, block_k), lo:lo + dim]
            logits = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                   # (BLOCK_Q, BLOCK_K)
            if causal:
                k_pos = kb * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (1, block_k), 1
                )
                logits = jnp.where(q_pos >= k_pos, logits, _NEG_INF)
            m_new = jnp.maximum(m, logits.max(axis=-1, keepdims=True))
            p = jnp.exp(logits - m_new)
            if causal:
                # rows fully masked in this tile contribute nothing
                p = jnp.where(logits > _NEG_INF / 2, p, 0.0)
            correction = jnp.exp(m - m_new)
            l_new = l * correction + p.sum(axis=-1, keepdims=True)
            o_new = o * correction + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return o_new, m_new, l_new

        o0 = jnp.zeros((block_q, dim), jnp.float32)
        m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((block_q, 1), jnp.float32)
        o, m, l = jax.lax.fori_loop(0, num_iters, body, (o0, m0, l0))
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[0, :, lo:lo + dim] = (o / l_safe).astype(o_ref.dtype)
        # lse block is (BLOCK_Q, H): per-head column write; H as the
        # block's last dim equals the array's, satisfying the TPU
        # lowering's last-two-dims rule for any head count
        lse_ref[0, :, h:h + 1] = m + jnp.log(l_safe)


def _pallas_forward(q3, k3, v3, causal: bool, scale: float, block_q: int,
                    block_k: int, heads: int, dim: int, interpret: bool):
    """q3/k3/v3: (B, L, H*D) -> (out (B, L, H*D), lse (B, L, H))."""
    batch, q_len, hd = q3.shape
    k_len = k3.shape[1]
    grid = (batch, q_len // block_q)
    kernel = functools.partial(
        _fwd_kernel,
        block_k=block_k,
        causal=causal,
        scale=scale,
        q_len=q_len,
        k_len=k_len,
        block_q=block_q,
        heads=heads,
        dim=dim,
    )

    # Outputs inherit the inputs' varying-axes type (vma): inside a
    # shard_map with the varying-axis audit on, an untyped out_shape is a
    # ValueError.
    vma = frozenset().union(*(jax.typeof(x).vma for x in (q3, k3, v3)))

    def out_struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, k_len, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, k_len, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, heads), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            out_struct((batch, q_len, hd), q3.dtype),
            out_struct((batch, q_len, heads), jnp.float32),
        ],
        interpret=interpret,
    )(q3, k3, v3)


def use_interpret() -> bool:
    """Whether the kernel runs in the Pallas interpreter (any backend
    but TPU) instead of being compiled by Mosaic.  The one predicate
    every caller that treats the two differently dispatches on."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, causal, scale):
    return _flash_fwd(q, k, v, causal, scale)[0]


def _pick_block(length: int) -> int:
    # Per-grid-program overhead dominates these small-matmul kernels,
    # so fewer/larger programs win (256-512-sized tiles beat 128-tiles
    # on an older machine; `PERF.md` §7).  Blocks must divide the
    # length (the grid streams whole tiles).
    for cand in (512, 256, 128):
        if length >= cand and length % cand == 0:
            return cand
    return length


def _flash_fwd(q, k, v, causal, scale):
    batch, q_len, heads, dim = q.shape
    k_len = k.shape[1]
    hd = heads * dim
    # the optimum at BERT-base shapes on an older machine: Q tiles of
    # 256 with K streamed in 512s (not measured again on the v5e)
    block_q = 256 if q_len % 256 == 0 else _pick_block(q_len)
    block_k = _pick_block(k_len)
    out3, lse = _pallas_forward(
        q.reshape(batch, q_len, hd),
        k.reshape(batch, k_len, hd),
        v.reshape(batch, k_len, hd),
        causal, scale, block_q, block_k, heads, dim, use_interpret(),
    )
    out = out3.reshape(batch, q_len, heads, dim)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, residuals, g):
    """Flash backward by recompute: probabilities are rebuilt from the
    saved log-sum-exp, so nothing O(L^2) was ever saved.  Expressed in
    jnp on the (B, L, H, D) layout — XLA fuses the whole thing (the
    O(L^2) intermediate lives only inside the fused computation) and
    folds the bhqk<->blhd layout changes into the matmuls instead of
    materialising transposes."""
    q, k, v, out, lse = residuals            # lse: (B, Lq, H)
    # matmul operands in the input dtype (MXU full rate), f32 accumulate;
    # softmax/correction math in f32
    g = g.astype(q.dtype)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        q_len, k_len = q.shape[1], k.shape[1]
        mask = jnp.arange(q_len)[:, None] >= jnp.arange(k_len)[None, :]
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    p = jnp.exp(logits - lse.transpose(0, 2, 1)[..., None])
    pc = p.astype(q.dtype)
    dv = jnp.einsum(
        "bhqk,bqhd->bkhd", pc, g, preferred_element_type=jnp.float32
    )
    dp = jnp.einsum(
        "bqhd,bkhd->bhqk", g, v, preferred_element_type=jnp.float32
    )
    delta = (
        (g.astype(jnp.float32) * out.astype(jnp.float32))
        .sum(-1)                              # (B, Lq, H)
        .transpose(0, 2, 1)[..., None]        # (B, H, Lq, 1)
    )
    ds = (p * (dp - delta) * scale).astype(q.dtype)
    dq = jnp.einsum(
        "bhqk,bkhd->bqhd", ds, k, preferred_element_type=jnp.float32
    )
    dk = jnp.einsum(
        "bhqk,bqhd->bkhd", ds, q, preferred_element_type=jnp.float32
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


# Per-program K/V VMEM residency ceiling: the (B, L, H*D)-layout kernel
# holds a WHOLE (k_len, H*D) K and V block per program, and the BINDING
# limit is the 16 MB *scoped* VMEM window.  The boundary is EMPIRICAL,
# not a clean K/V-bytes formula — the scope also charges the Q/out
# block pipeline and f32 scratch: measured on v5e, BERT-base at L=2048
# (k_len*H*D = 1.57M elements) overflows the scope by 8 KB while
# L=1024 (0.79M) compiles with room.  1.25M keeps L=1024-class shapes
# on the kernel with margin below the measured failure; beyond it
# callers fall back to the fused-lax ring body, and truly long context
# belongs to the ring tier (sequence sharded over chips) regardless.
# Re-derive by measurement, not arithmetic, if the scope or kernel
# layout changes.
_MAX_KV_BLOCK_ELEMENTS = 5 * 256 * 1024  # 1.25M


def flash_shapes_ok(q_shape, k_shape, causal: bool = False) -> bool:
    """Whether (B, L, H, D) q/k shapes satisfy a kernel's constraints.
    The resident kernel: tile shapes (L multiple of 128 or a sub-128
    multiple of 8, D <= 128) AND per-program K/V VMEM residency (k_len *
    H * D within _MAX_KV_BLOCK_ELEMENTS), q and k of one head count.  Past
    those, CAUSAL self-attention at a head width of whole lane tiles goes
    to the streaming kernel (`stream_shapes_ok`), which holds one K/V tile
    at a time and takes grouped keys and values (q's heads a multiple of
    k's).  Callers dispatch on THIS instead of catching ValueError from
    `flash_attention` — a blanket except around a traced call swallowed
    an unrelated shard_map vma error for a full round and silently
    downgraded the bench to the O(L^2) reference path (round-5 profile
    finding)."""
    return _resident_shapes_ok(q_shape, k_shape) or (
        causal and stream_shapes_ok(q_shape, k_shape, k_shape)
    )


def _resident_shapes_ok(q_shape, k_shape) -> bool:
    def bad(length):
        return (length >= 128 and length % 128 != 0) or (
            length < 128 and length % 8 != 0
        )

    heads, dim = q_shape[2], q_shape[3]
    return not (
        heads != k_shape[2]
        or bad(q_shape[1])
        or bad(k_shape[1])
        or dim > 128
        or k_shape[1] * heads * dim > _MAX_KV_BLOCK_ELEMENTS
    )


def flash_attention(
    q, k, v, causal: bool = False, scale: Optional[float] = None
):
    """Single-device flash attention; q/k/v: (B, L, H, D) -> (B, L, H, D).

    Differentiable (custom VJP with flash recompute).  Sequence lengths
    must be multiples of the 128 tile (or shorter than it) — pad upstream
    if not; head dim <= 128, or causal self-attention at a head width of
    whole lane tiles (the streaming kernel).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    from elasticdl_tpu.parallel.mesh import in_export_mode

    if in_export_mode():
        # Serving export: Pallas custom calls don't stage through jax2tf;
        # the O(L^2) lax reference computes the same function.  Lazy
        # import — ring_attention imports this module.
        from elasticdl_tpu.ops.ring_attention import (
            full_attention_reference,
        )

        return full_attention_reference(q, k, v, causal=causal, scale=scale)
    # The SAME predicate callers dispatch on (an un-tileable k_len would
    # silently DROP tail keys — the kernel streams whole tiles); a
    # separate inline copy here could drift from flash_shapes_ok and
    # reintroduce the uncaught-ValueError-in-shard_map failure mode.
    if not flash_shapes_ok(q.shape, k.shape, causal) or k.shape != v.shape:
        raise ValueError(
            f"flash_attention needs L a multiple of 128 (or a sub-128 "
            f"multiple of 8) for BOTH q and k/v, k.shape == v.shape, "
            f"D <= 128, and Lk*H*D <= {_MAX_KV_BLOCK_ELEMENTS} (the "
            f"per-program K/V VMEM residency ceiling); got "
            f"Lq={q.shape[1]}, Lk={k.shape[1]}, H={q.shape[2]}, "
            f"D={q.shape[3]}"
        )
    if not _resident_shapes_ok(q.shape, k.shape):
        return _stream(q, k, v, float(scale), None)
    return _flash(q, k, v, causal, scale)


# ---- causal self-attention past the resident kernel's shapes -------------
#
# The kernel above keeps a program's whole K and V in VMEM and its
# backward builds (B, H, L, L) operands: a decoder at 20 heads of width
# 256 over 4,096 positions passes both limits (5,120 columns a row; 671
# MB of probabilities a sequence).  `blocked_causal_attention` walks the
# causal triangle one row of query tiles at a time, forward and backward,
# in plain lax: nothing (B, H, L, L)-shaped exists for more than one tile
# row, the tiles above the diagonal are never computed, and the backward
# rebuilds each row's probabilities from the saved log-sum-exp.
#
# Both forms here take GROUPED keys and values (q of H heads over k, v of
# Hkv, H a multiple of Hkv: query head h reads K/V head h // (H / Hkv))
# and a WINDOW (query t sees keys s with t - window < s <= t): K/V are
# never repeated to H heads, and keys outside the band are neither
# computed nor read.
#
# Both forms NAME the forward's two results (`checkpoint_name`) where they
# are made: the output and the log-sum-exp are all the backward needs of
# the forward besides its arguments, and the forward is the costliest
# thing in a decoder block to run twice (31.8 and 40.8 ms a call in the
# long-context cells, `PERF.md` section 6, PR 36).  A caller that
# rematerialises a block saves the two by name (`SAVED_NAMES`;
# `model_zoo/common/decoder.py: remat_block`) and its remat rebuilds the
# rest of the block, q, k and v among it, but not this call: the forward
# runs once a step.  Outside a `jax.checkpoint` a name is the identity.

#
# WHERE THE LOG-SUM-EXP LIES.  The streaming forward saves it LANE-MAJOR,
# float32 (B, H, 1, L), as the blocked form always has ((B, Hkv, G, L)).
# The kernels' tile bodies want it a (tile, 1) COLUMN beside the (tile,
# tile) scores, and until PR 60 the forward wrote that column out as (B, H,
# L, 1): the chip tiles an array's two minor axes (8, 128), so the array
# took 128 times its values in HBM, and every rematerialised block of a
# straight-line step held it from its forward to its backward (537 MB in a
# Laguna window layer, 235 MB a SmallThinker layer; what a `scan` stacks
# XLA laid out lane-major by itself, at a relayout copy an application: the
# Ouro cell's 24).  The row takes 8 times its values by that tiling and the
# values alone as XLA lays it out (`T(1,128)`: a compile for a described
# v5e shows it).  The column is turned into the row once a QUERY tile
# in the forward and back once a query tile in the backward
# (`_stream_fwd_rows`, `_stream_bwd_rows`: wrappers, the kernels and their
# tile bodies are as they were and their results the same bits).  Placed by
# PR 60's chip probe (`scripts/probe_attention_lse.py`, v5e, the two
# programs alone, bfloat16, five traced calls; ms a call: forward kernel |
# backward kernel | what the layout costs beside them || MB the saved
# array takes in HBM as (8, 128) tiles it), at the Ouro cell's call ((1,
# 8192), 16 | 16 heads), the Laguna cell's window call ((2, 8192), 64 | 8,
# a band of 512) and the SmallThinker cell's two ((1, 16384), 28 | 4, no
# band / a band of 4,096):
#   the column, (B, H, L, 1), the parent's
#     Ouro 3.746 | 5.382 | 0 || 67      Laguna 7.308 | 8.583 | 0 || 537
#     SmallThinker 26.647 | 35.999 / 11.680 | 15.465 | 0 || 235
#   the row, turned in the kernels (this)
#     Ouro 3.780 | 5.406 | 0 || 4.2     Laguna 7.589 | 9.125 | 0 || 33.6
#     SmallThinker 26.321 | 35.818 / 11.788 | 15.641 | 0 || 14.7
#   the column squeezed to (B, H, L) beside the kernels
#     Ouro 3.746 | 5.382 | 0.16 || 0.5  Laguna 7.308 | 8.583 | 1.53 || 4.2
#     SmallThinker 26.647 | 35.999 / 11.680 | 15.465 | 0.68 || 2.1
# (the squeeze and the expand are XLA copies that read or write the PADDED
# array, 0.06-0.11 ms at 67 MB and 0.71-0.82 at 537: two to four times the
# turn's cost at every call; and where forward and backward are one
# straight-line program XLA CANCELS the pair and holds the padded column
# from kernel to kernel after all, as a compile for a described v5e shows).
# The turn is one (tile, 128) transpose: 0.14 us a query tile forward, 0.26
# backward, which a window of two key tiles a query tile feels (+3.8% and
# +6.3% of Laguna's window kernels) and sixteen do not (+0.9% and +0.4% at
# Ouro's call).  A Mosaic reshape of the column costs more (3.853 | 5.420
# and 8.559 | 9.259).
_BLOCKED_TILE = 512
SAVED_NAMES = ("attention_core_out", "attention_core_lse")


def _named(out, lse):
    """The forward's two results under the names a remat saves them by."""
    return tuple(
        checkpoint_name(t, name) for t, name in zip((out, lse), SAVED_NAMES)
    )


def _tile_rows(length: int, tile: int, window: Optional[int] = None):
    """[(first key, start, end)] of the query tiles [start, end); the
    keys of a row are [first key, end)."""
    tile = min(tile, length)
    return [
        (0 if window is None else max(0, s - window + 1), s,
         min(s + tile, length))
        for s in range(0, length, tile)
    ]


def _row_logits(q_row, k_pre, start, first, scale, window):
    """(B, Hkv, G, rows, keys) f32 logits of one tile row of grouped
    queries (B, rows, Hkv, G, D) against keys (B, keys, Hkv, D) that
    begin at position `first`, masked to the causal band."""
    logits = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q_row, k_pre,
        preferred_element_type=jnp.float32,
    ) * scale
    q_pos = start + jnp.arange(q_row.shape[1])[:, None]
    k_pos = first + jnp.arange(k_pre.shape[1])[None, :]
    seen = q_pos >= k_pos
    if window is not None:
        seen &= q_pos - k_pos < window
    return jnp.where(seen, logits, _NEG_INF)


def _grouped(q, kv_heads: int):
    """(B, L, H, D) -> (B, L, Hkv, H / Hkv, D): a free view."""
    batch, length, heads, dim = q.shape
    return q.reshape(batch, length, kv_heads, heads // kv_heads, dim)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _blocked(q, k, v, scale, tile, window):
    return _blocked_fwd(q, k, v, scale, tile, window)[0]


def _blocked_fwd(q, k, v, scale, tile, window):
    outs, lses = [], []
    q5 = _grouped(q, k.shape[2])
    for first, start, end in _tile_rows(q.shape[1], tile, window):
        logits = _row_logits(
            q5[:, start:end], k[:, first:end], start, first, scale, window
        )
        m = logits.max(axis=-1, keepdims=True)
        p = jnp.exp(logits - m)
        l = p.sum(axis=-1, keepdims=True)
        out = jnp.einsum(
            "bhgqk,bkhd->bqhgd", p.astype(v.dtype), v[:, first:end],
            preferred_element_type=jnp.float32,
        ) / l[..., 0].transpose(0, 3, 1, 2)[..., None]
        outs.append(out.astype(q.dtype))
        lses.append((m + jnp.log(l))[..., 0])           # (B, Hkv, G, rows)
    out, lse = _named(
        jnp.concatenate(outs, axis=1).reshape(*q.shape[:3], v.shape[-1]),
        jnp.concatenate(lses, axis=3),
    )
    return out, (q, k, v, out, lse)


def _blocked_bwd(scale, tile, window, residuals, g):
    q, k, v, out, lse = residuals
    g = g.astype(q.dtype)
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    delta = _grouped(delta[..., None], k.shape[2])[..., 0].transpose(
        0, 2, 3, 1
    )                                                   # (B, Hkv, G, L)
    q5, g5 = _grouped(q, k.shape[2]), _grouped(g, k.shape[2])
    dqs = []
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    for first, start, end in _tile_rows(q.shape[1], tile, window):
        q_row, g_row = q5[:, start:end], g5[:, start:end]
        logits = _row_logits(
            q_row, k[:, first:end], start, first, scale, window
        )
        p = jnp.exp(logits - lse[..., start:end, None])
        dp = jnp.einsum(
            "bqhgd,bkhd->bhgqk", g_row, v[:, first:end],
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta[..., start:end, None]) * scale).astype(q.dtype)
        dqs.append(jnp.einsum(
            "bhgqk,bkhd->bqhgd", ds, k[:, first:end],
            preferred_element_type=jnp.float32,
        ).astype(q.dtype))
        dk = dk.at[:, first:end].add(jnp.einsum(
            "bhgqk,bqhgd->bkhd", ds, q_row,
            preferred_element_type=jnp.float32,
        ))
        dv = dv.at[:, first:end].add(jnp.einsum(
            "bhgqk,bqhgd->bkhd", p.astype(q.dtype), g_row,
            preferred_element_type=jnp.float32,
        ))
    return (
        jnp.concatenate(dqs, axis=1).reshape(q.shape), dk.astype(k.dtype),
        dv.astype(v.dtype),
    )


_blocked.defvjp(_blocked_fwd, _blocked_bwd)


def _grouped_shapes_ok(q_shape, k_shape, v_shape) -> bool:
    """Self-attention over grouped keys and values: one batch and length,
    q's heads a multiple of k's, k and v alike (v's width apart)."""
    return (
        tuple(q_shape[:2]) == tuple(k_shape[:2])
        and tuple(k_shape[:3]) == tuple(v_shape[:3])
        and q_shape[3] == k_shape[3]
        and k_shape[2] > 0 and q_shape[2] % k_shape[2] == 0
    )


def blocked_causal_attention(q, k, v, scale: Optional[float] = None,
                             tile: int = _BLOCKED_TILE,
                             window: Optional[int] = None):
    """Causal self-attention, q (B, L, H, D) over k (B, L, Hkv, D) and
    v (B, L, Hkv, Dv) -> (B, L, H, Dv), any head width, one row of
    `tile` queries at a time (module comment above)."""
    if not _grouped_shapes_ok(q.shape, k.shape, v.shape):
        raise ValueError(
            f"blocked_causal_attention is self-attention over grouped "
            f"keys and values: q {q.shape}, k {k.shape}, v {v.shape} must "
            f"agree (v's width and a whole group of q's heads a K/V head "
            f"apart)"
        )
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _blocked(q, k, v, float(scale), int(tile), _band(window, q))


def _band(window, q) -> Optional[int]:
    """`window` as the kernels take it: None where it hides no key."""
    if window is None or window >= q.shape[1]:
        return None
    if window < 1:
        raise ValueError(f"a window of {window} keys sees nothing")
    return int(window)


# ---- the streaming kernel: causal, head width a multiple of 128, or 64 ----
#
# Inputs stay (B, L, H*D) (the free view of the model's layout) and the
# forward's grid walks (batch, head, query tile, key tile): a program holds
# ONE (tile, D) block of Q, K and V (D = 256 is two lane tiles, so a head is
# a column block and no head loop is needed), K/V tiles stream through
# VMEM with the running max, normaliser ((tile, 1) each) and accumulator
# in scratch, and tiles above the diagonal neither compute nor move (their
# block index is clamped to the last tile needed, which Pallas does not
# fetch again).  The log-sum-exp leaves a query tile as a (1, tile) row of
# a (B, H, 1, L) array (the comment above `SAVED_NAMES`).
#
# The backward is ONE kernel from the saved log-sum-exp, as the blocked
# form above but tile by tile.  Its grid is (batch, K/V head, the group's
# query heads x query tiles, key tiles the query tile meets), the two inner
# axes sequential: a program holds one (query tile, key tile) pair with
# the QUERY tile resident and the key tiles streaming, rebuilds the tile's
# logits, probabilities, dP and dS once, and feeds all three gradients
# from them (five products and one elementwise pass a tile).  dQ gathers
# in a (tile, D) float32 scratch and leaves at the query tile's last key
# step.
# dK and dV gather in float32 scratch over the WHOLE length of the K/V
# head, a key tile's rows at `pl.ds(j * tile, tile)`, and leave once a
# (batch, K/V head), after the group's last query tile: 2 x L x D x 4
# bytes of scratch and two (L, D) output blocks, double-buffered (16 MiB
# at (8192, 128) and (4096, 256) in bfloat16, 24 in float32 as
# `stream_backward_vmem_bytes` reckons), which is why the call names
# `_STREAM_VMEM_LIMIT` and why
# `stream_shapes_ok` refuses a length whose scratch would not fit under
# it.  A key tile's dK and dV receive their terms by (query head of the
# group, then query tile) and a query tile's dQ by key tile.
# THE NAME: the one backward kernel is `causal_attention_dkv` /
# `window_attention_dkv` although it carries dQ as well, because the
# benchmark's rules (`benchmarks/layer_metrics/*.json`) find the kernels
# as `(causal|window)_attention_(fwd|dkv|dq)` and a PR that changes the
# kernel may not edit them; the rename to `_bwd` waits for a `benchmark`
# PR (`ROADMAP.md`, Reach, metrics).
#
# Grouped K/V: query head h takes the K/V column block h // group through
# the index map; the backward's grid runs over the K/V heads and its third
# axis over (query head of the group, query tile), so a K/V head's gradient
# sums over its group in scratch.  A window: the key axis is as long as
# the band (`_band_steps` tiles), the tiles outside it are never visited,
# and both of its edges are masked in the tile (`_mask_tile`).
#
# WHAT PACES A TILE (measured on the v5e, `PERF.md` section 6, PR 43) is
# the cross-lane unit: the two row reductions of a (tile, tile) score
# tile, the broadcasts of a (tile, 1) column along the lanes and the
# transposed operands of the backward's products.  The vector unit has
# slack under them.  So EVERY visited tile is masked with both compares
# (`_mask_tile`) and EVERY score tile is multiplied by `scale`, although
# only the diagonal's tile and the band's far tile hold an element to
# hide and a power-of-two scale could ride on the (tile, D) query block:
# taking both out of every tile moved no kernel by 1%, and a tile body
# branched by the edges that cross it cost 0.4-0.6%.  And so the
# forward's running max and normaliser are (tile, 1) scratch, read and
# written as they are: broadcast to a lane tile at every key step, as
# they were, they cost a sixth of the forward.  And so the ORDER of the
# independent statements of a tile body is part of the kernel: the
# forward's row sum stands after the P V product and the backward's three
# gathering products stand last, the plain one first, so that the
# cross-lane work runs under a product (the same arithmetic in another
# order costs 7-11% more).  What is left of the forward is the row max and
# the row sum, a fifth of it each (`ROADMAP.md`, Speed 2).
#
# A head of HALF a lane tile (D = 64) is no column block of (B, L, H*D):
# a block's last dimension is whole lane tiles or the whole array's.  Such
# a call goes head-major, (B, H, L, D), whose last dimension IS the head:
# the same kernels over the same grids hold (tile, 64) blocks, the index
# maps name the head on its own axis, and the transposes in and out are
# layout copies in XLA (eight streams a layer, forward and backward).

_STREAM_TILE = 512
_LANES = 128
_HALF_HEAD = _LANES // 2
# What the backward kernel may hold in VMEM (the v5e has 128 MiB; the
# compiler's own default is 16), and the part of it left to the tiles'
# blocks and a tile's temporaries: at the 8,192-position cells' shapes the
# kernel compiles under 24 MiB with 16 MiB held for the whole length, and
# is refused 16; at 16,384 positions of 128 (32 MiB held in bfloat16, 48 in
# float32) it compiles under this limit and runs on the chip.
_STREAM_VMEM_LIMIT = 64 * 1024 * 1024
_STREAM_TILE_ROOM = 16 * 1024 * 1024


def _lane_tiles(dim: int) -> int:
    """`dim` rounded up to whole lane tiles."""
    return -(-dim // _LANES) * _LANES


def stream_backward_vmem_bytes(length: int, dim: int,
                               v_dim: Optional[int] = None) -> int:
    """Bytes of VMEM the backward kernel holds for the WHOLE length of one
    K/V head at the widest operands it takes (float32): dK (`dim` wide)
    and dV (`v_dim` wide, `dim` unless said) in float32 scratch and their
    two output blocks, double-buffered, every row padded to whole lane
    tiles."""
    columns = _lane_tiles(dim) + _lane_tiles(dim if v_dim is None else v_dim)
    return length * columns * (4 + 2 * 4)


def _stream_tiles(length: int):
    for cand in (_STREAM_TILE, 256, 128):
        if length % cand == 0:
            return cand
    return None


def stream_shapes_ok(q_shape, k_shape, v_shape) -> bool:
    """Whether the streaming kernel takes causal self-attention at these
    (B, L, H, D) shapes: k at q's batch, length and width, v at k's head
    count, q's heads a multiple of theirs (grouped keys and values; the
    same count is a group of one), L whole 128-tiles, the VALUE width
    whole lane tiles (the key width is then its own: one that is no whole
    number of lane tiles is padded with zero columns, `_padded_keys`), or
    both widths half a lane tile (64, which goes head-major), and L x
    (D + Dv) no more than the backward's whole-length scratch may hold
    under `_STREAM_VMEM_LIMIT` (16,384 positions at heads of 128, 8,192 at
    256, or at 192 padded to 256 over 128).  A window asks nothing more.
    16,384 at heads of 128 is the limit less the tiles' room EXACTLY (48
    MiB as `stream_backward_vmem_bytes` reckons it, at float32); Mosaic
    compiles it and the v5e runs it, at 28 query heads over 4 K/V heads
    in bfloat16 (16 MiB of scratch and 16 of output blocks), with a band
    of 4,096 and without (PR 57's cell; `PERF.md` section 6)."""
    dim, v_dim = q_shape[3], v_shape[3]
    return (
        _grouped_shapes_ok(q_shape, k_shape, v_shape)
        and (v_dim % _LANES == 0 or dim == v_dim == _HALF_HEAD)
        and _stream_tiles(q_shape[1]) is not None
        and stream_backward_vmem_bytes(q_shape[1], dim, v_dim)
        <= _STREAM_VMEM_LIMIT - _STREAM_TILE_ROOM
    )


def _padded_keys(q, k):
    """q and k with zero columns up to whole lane tiles where their width
    is none and is not the head-major 64: q k^T is the same number, the
    padded columns' gradient is dropped by the pad's own transpose, and v
    keeps its width (padding it too would double the PV and dV
    products)."""
    dim = q.shape[3]
    if dim % _LANES == 0 or dim == _HALF_HEAD:
        return q, k
    pad = ((0, 0), (0, 0), (0, 0), (0, _lane_tiles(dim) - dim))
    return jnp.pad(q, pad), jnp.pad(k, pad)


def _band_steps(num: int, tile: int, window: Optional[int]) -> int:
    """Tiles of the other kind one tile meets: all `num` under the causal
    mask alone (those past the diagonal are skipped in place), under a
    window the diagonal's and the ceil((window - 1) / tile) before it."""
    if window is None:
        return num
    return min(num, -(-(window - 1) // tile) + 1)


def _mask_tile(s, i, j, tile, window):
    """Mask logits of query tile i against key tile j."""
    q_pos = i * tile + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = j * tile + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if window is None:
        return jnp.where(q_pos >= k_pos, s, _NEG_INF)
    return jnp.where(
        (q_pos >= k_pos) & (q_pos - k_pos < window), s, _NEG_INF
    )


def _key_tile(i, y, steps, window):
    """The key tile inner step y of query tile i holds: step y is key tile
    y under the causal mask alone; the band's `steps` tiles end on the
    diagonal."""
    return y if window is None else i - (steps - 1) + y


def _key_live(i, j, window):
    """Whether key tile j is one query tile i meets: under the causal
    mask alone the steps past the diagonal are skipped, under a window
    those before the sequence's first tile."""
    return j <= i if window is None else j >= 0


def _dot(a, b, contract):
    return jax.lax.dot_general(
        a, b, (contract, ((), ())), preferred_element_type=jnp.float32
    )


def _stream_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc,
                       acc_sc, *, scale: float, tile: int, steps: int,
                       window):
    i, y = pl.program_id(2), pl.program_id(3)
    j = _key_tile(i, y, steps, window)

    @pl.when(y == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when(_key_live(i, j, window))
    def _():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = _mask_tile(
            _dot(q, k, ((1,), (1,))) * scale, i, j, tile, window
        )                                               # (tile, tile)
        m_prev = m_sc[...]                              # (tile, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        # a row the band hides from this whole tile reads exp(0) here;
        # the diagonal's tile comes last, holds a key of every row and
        # scales what such a row gathered by exp(-1e30 - m) = 0
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        acc_sc[...] = acc_sc[...] * correction + _dot(
            p.astype(v.dtype), v, ((1,), (0,))
        )
        m_sc[...] = m_new
        # the row sum AFTER the product it does not feed: it crosses the
        # lanes while the MXU works (7% of this kernel, as above)
        l_sc[...] = l_sc[...] * correction + p.sum(axis=-1, keepdims=True)

    @pl.when(y == steps - 1)
    def _():
        l = l_sc[...]
        o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_sc[...] + jnp.log(l)


def _stream_bwd_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                       dq_ref, dk_ref, dv_ref, dq_sc, dk_sc, dv_sc, *,
                       scale: float, tile: int, num: int, steps: int,
                       group: int, window):
    """The whole backward of one (query tile, key tile) pair: the logits,
    probabilities, dP and dS are rebuilt once and feed dV, dK and dQ.  It
    runs under the name `*_attention_dkv` (module comment above)."""
    x, y = pl.program_id(2), pl.program_id(3)
    # the outer axis: the group's query heads in turn, `num` query tiles
    # each
    i = x % num
    j = _key_tile(i, y, steps, window)

    @pl.when((x == 0) & (y == 0))
    def _():
        dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)

    @pl.when(y == 0)
    def _():
        dq_sc[...] = jnp.zeros(dq_sc.shape, jnp.float32)

    @pl.when(_key_live(i, j, window))
    def _():
        q, k, v, g = q_ref[0], k_ref[0], v_ref[0], g_ref[0]
        s = _mask_tile(
            _dot(q, k, ((1,), (1,))) * scale, i, j, tile, window
        )
        p = jnp.exp(s - lse_ref[0, 0])                  # (tile q, tile k)
        dp = _dot(g, v, ((1,), (1,)))
        ds = (p * (dp - delta_ref[0, 0]) * scale).astype(q.dtype)
        # the three gathering products LAST, the plain one first: the
        # compiler schedules near the order it is given, and the two
        # transposed operands then cross the lanes under a product (8-11%
        # of this kernel at heads of 64 and 128, `PERF.md` section 6, PR 43)
        dq_sc[...] += _dot(ds, k, ((1,), (0,)))
        keys = pl.ds(pl.multiple_of(j * tile, tile), tile)
        dk_sc[keys, :] += _dot(ds, q, ((0,), (0,)))
        dv_sc[keys, :] += _dot(p.astype(g.dtype), g, ((0,), (0,)))

    @pl.when(y == steps - 1)
    def _():
        dq_ref[0] = dq_sc[...].astype(dq_ref.dtype)

    @pl.when((x == group * num - 1) & (y == steps - 1))
    def _():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _lane_major(column):
    """A (tile, 1) column as the (1, tile) row of the same values: one
    (tile, 128) transpose (a Mosaic reshape of the column costs twice as
    much; the comment above `SAVED_NAMES` has the probe)."""
    return jnp.broadcast_to(column, (column.shape[0], _LANES)).T[:1]


def _sublane_major(row):
    """A (1, tile) row as the (tile, 1) column of the same values."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T[:, :1]


def _stream_fwd_rows(q_ref, k_ref, v_ref, o_ref, lse_ref, lse_sc, *scratch,
                     steps: int, **static):
    """`_stream_fwd_kernel` with its log-sum-exp column caught in scratch
    (`lse_sc` has the shape of the column block the kernel wrote to, so
    the kernel indexes it as it did its output) and written LANE-MAJOR, a
    (1, tile) row once a query tile."""
    _stream_fwd_kernel(
        q_ref, k_ref, v_ref, o_ref, lse_sc, *scratch, steps=steps, **static
    )

    @pl.when(pl.program_id(3) == steps - 1)
    def _():
        lse_ref[0, 0] = _lane_major(lse_sc[0, 0])


def _stream_bwd_rows(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref,
                     dk_ref, dv_ref, lse_sc, *scratch, **static):
    """`_stream_bwd_kernel` over a lane-major log-sum-exp: the row turns
    into the (tile, 1) column the tile body reads once a query tile, not
    once a (query tile, key tile) pair."""
    @pl.when(pl.program_id(3) == 0)
    def _():
        lse_sc[0, 0] = _sublane_major(lse_ref[0, 0])

    _stream_bwd_kernel(
        q_ref, k_ref, v_ref, g_ref, lse_sc, delta_ref, dq_ref, dk_ref,
        dv_ref, *scratch, **static
    )


def _stream_call(kernel, grid, in_specs, out_specs, out_shape, scratch,
                 operands, name, inner_axes: int = 1,
                 vmem_limit: Optional[int] = None):
    """One streaming kernel over a four-axis grid whose last `inner_axes`
    carry scratch from step to step."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch,
        ),
        out_shape=[
            jax.ShapeDtypeStruct(shape, dtype, vma=vma)
            for shape, dtype in out_shape
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                ("parallel",) * (4 - inner_axes)
                + ("arbitrary",) * inner_axes
            ),
            vmem_limit_bytes=vmem_limit,
        ),
        interpret=use_interpret(),
        name=name,
    )(*operands)


def _same_head(h, x, y):
    return h


def _stream_specs(tile: int):
    """Block specs by role, for a grid (batch, head, outer step, inner
    step): `which` gives the block of `rows` rows (a tile unless said) of
    `dim` columns a spec follows, clamped to the tiles its kernel visits
    so that a skipped step moves nothing, and `head` the column block (the
    grid's own head unless said).  A head that is no whole lane tile is a
    block of the head-major (B, H, L, D) view, its head axis squeezed: the
    kernels see (1, rows, D) either way.  `per_row` is a (tile, 1) column
    of a float32 (B, H, L, 1) array (the backward's `delta`, which lives
    one call), `per_lane` a (1, tile) row of a (B, H, 1, L) one (the saved
    log-sum-exp)."""
    def tiles(dim, which, head=_same_head, rows=tile):
        if dim % _LANES:
            return pl.BlockSpec(
                (1, None, rows, dim),
                lambda b, h, x, y: (b, head(h, x, y), which(x, y), 0),
            )
        return pl.BlockSpec(
            (1, rows, dim),
            lambda b, h, x, y: (b, which(x, y), head(h, x, y)),
        )

    def per_row(which, head=_same_head):
        return pl.BlockSpec(
            (1, 1, tile, 1),
            lambda b, h, x, y: (b, head(h, x, y), which(x, y), 0),
        )

    def per_lane(which, head=_same_head):
        return pl.BlockSpec(
            (1, 1, 1, tile),
            lambda b, h, x, y: (b, head(h, x, y), 0, which(x, y)),
        )

    return tiles, per_row, per_lane


def _stream_names(window) -> str:
    """The kernels' names in the trace: a windowed call is timed apart."""
    return "causal_attention" if window is None else "window_attention"


def _resident_row(i, y):
    return i


def _streamed_keys(steps: int, window):
    """Index of the key tile a (query tile i, inner step y) block holds:
    keys past the diagonal stay on the diagonal's tile, keys before the
    band's first tile on tile 0."""
    def keys(i, y):
        j = _key_tile(i, y, steps, window)
        return jnp.minimum(i, j) if window is None else jnp.maximum(j, 0)

    return keys


def _kv_head(group: int):
    """The K/V column block of the grid's query head."""
    return _same_head if group == 1 else (lambda h, x, y: h // group)


def _stream_view(t):
    """(B, L, H, D) as the kernels' operand: the free (B, L, H*D) view at
    a head of whole lane tiles, head-major (B, H, L, D) below that."""
    if t.shape[3] % _LANES:
        return t.transpose(0, 2, 1, 3)
    return t.reshape(*t.shape[:2], -1)


def _stream_shape(shape):
    """The shape `_stream_view` gives a (B, L, H, D) array."""
    batch, length, heads, dim = shape
    if dim % _LANES:
        return (batch, heads, length, dim)
    return (batch, length, heads * dim)


def _stream_unview(t, shape):
    """A kernel's output back as (B, L, H, D) = `shape`."""
    if shape[3] % _LANES:
        return t.transpose(0, 2, 1, 3)
    return t.reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _stream(q, k, v, scale, window):
    return _stream_fwd(q, k, v, scale, window)[0]


def _stream_fwd(q, k, v, scale, window):
    batch, length, heads, dim = q.shape
    v_dim = v.shape[3]
    group = heads // k.shape[2]
    tile = _stream_tiles(length)
    num = length // tile
    steps = _band_steps(num, tile, window)
    tiles, _, per_lane = _stream_specs(tile)
    keys, kv_head = _streamed_keys(steps, window), _kv_head(group)
    flat = _stream_shape((batch, length, heads, v_dim))
    # grid (b, h, i over queries, y over the keys i meets)
    out, lse = _stream_call(
        functools.partial(
            _stream_fwd_rows, scale=scale, tile=tile, steps=steps,
            window=window,
        ),
        (batch, heads, num, steps),
        [tiles(dim, _resident_row), tiles(dim, keys, kv_head),
         tiles(v_dim, keys, kv_head)],
        [tiles(v_dim, _resident_row), per_lane(_resident_row)],
        [(flat, q.dtype), ((batch, heads, 1, length), jnp.float32)],
        [pltpu.VMEM((1, 1, tile, 1), jnp.float32),
         pltpu.VMEM((tile, 1), jnp.float32),
         pltpu.VMEM((tile, 1), jnp.float32),
         pltpu.VMEM((tile, v_dim), jnp.float32)],
        [_stream_view(t) for t in (q, k, v)],
        _stream_names(window) + "_fwd",
    )
    # named as they leave the kernel (module comment of the blocked form):
    # with the two saved, a block's remat has no use for this call; the
    # log-sum-exp a (B, H, 1, L) row, 8 times its values in HBM
    out, lse = _named(out, lse)
    out = _stream_unview(out, (batch, length, heads, v_dim))
    return out, (q, k, v, out, lse)


def _stream_bwd(scale, window, residuals, g):
    q, k, v, out, lse = residuals
    batch, length, heads, dim = q.shape
    kv_heads, v_dim = k.shape[2], v.shape[3]
    group = heads // kv_heads
    tile = _stream_tiles(length)
    num = length // tile
    steps = _band_steps(num, tile, window)
    tiles, per_row, per_lane = _stream_specs(tile)
    flat = _stream_shape(q.shape)
    g = g.astype(q.dtype)
    delta = (
        (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
        .transpose(0, 2, 1)[..., None]
    )                                                   # (B, H, L, 1)
    # grid (b, K/V head, x over the group's heads times the query tiles,
    # y over the keys the query tile meets)
    def row(x, y):
        return x % num

    def q_head(h, x, y):
        return h * group + x // num

    streamed = _streamed_keys(steps, window)

    def keys(x, y):
        return streamed(row(x, y), y)

    def whole(x, y):
        return 0

    dq, dk, dv = _stream_call(
        functools.partial(
            _stream_bwd_rows, scale=scale, tile=tile, num=num,
            steps=steps, group=group, window=window,
        ),
        (batch, kv_heads, group * num, steps),
        [tiles(dim, row, q_head), tiles(dim, keys), tiles(v_dim, keys),
         tiles(v_dim, row, q_head), per_lane(row, q_head),
         per_row(row, q_head)],
        [tiles(dim, row, q_head), tiles(dim, whole, rows=length),
         tiles(v_dim, whole, rows=length)],
        [(flat, q.dtype), (_stream_shape(k.shape), k.dtype),
         (_stream_shape(v.shape), v.dtype)],
        [pltpu.VMEM((1, 1, tile, 1), jnp.float32),
         pltpu.VMEM((tile, dim), jnp.float32),
         pltpu.VMEM((length, dim), jnp.float32),
         pltpu.VMEM((length, v_dim), jnp.float32)],
        [_stream_view(t) for t in (q, k, v, g)] + [lse, delta],
        _stream_names(window) + "_dkv", inner_axes=2,
        vmem_limit=_STREAM_VMEM_LIMIT,
    )
    return (
        _stream_unview(dq, q.shape), _stream_unview(dk, k.shape),
        _stream_unview(dv, v.shape),
    )


_stream.defvjp(_stream_fwd, _stream_bwd)


def causal_attention(q, k, v, scale: Optional[float] = None,
                     window: Optional[int] = None):
    """Causal self-attention for a decoder's train step, q (B, L, H, D)
    over k (B, L, Hkv, D) and v (B, L, Hkv, Dv), H a multiple of Hkv
    (grouped keys and values: query head h reads K/V head h // (H / Hkv))
    -> (B, L, H, Dv), at any length and head widths: the one entry a
    model calls.  With `window`, query t sees the keys s with t - window
    < s <= t.  The streaming Pallas kernels where the shapes tile
    (`stream_shapes_ok`: values of whole lane tiles under keys of any
    width, or both of 64), the blocked lax form elsewhere (the same
    mathematics, one row of query tiles at a time)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    from elasticdl_tpu.parallel.mesh import in_export_mode

    if stream_shapes_ok(q.shape, k.shape, v.shape) and not in_export_mode():
        return _stream(
            *_padded_keys(q, k), v, float(scale), _band(window, q)
        )
    return blocked_causal_attention(q, k, v, scale, window=window)
