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
merged to (B*H, L, D) via transposes that cost ~23 ms/step of pure
layout copies in the BERT bench (docs/BERT_PROFILE.md) and ran more,
smaller grid programs; this layout measures ~19% faster solo AND deletes
the transposes.  Each program holds one Q tile resident in VMEM and
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
from jax.experimental import pallas as pl

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
    # 256-512-sized tiles measured 1.6-2x the 128-tile rate on v5e
    # (docs/BERT_PROFILE.md): per-grid-program overhead dominates these
    # small-matmul kernels, so fewer/larger programs win.  Blocks must
    # divide the length (the grid streams whole tiles).
    for cand in (512, 256, 128):
        if length >= cand and length % cand == 0:
            return cand
    return length


def _flash_fwd(q, k, v, causal, scale):
    batch, q_len, heads, dim = q.shape
    k_len = k.shape[1]
    hd = heads * dim
    # measured optimum at BERT-base shapes: Q tiles of 256 with K
    # streamed in 512s (10.3 TFLOPs solo vs 9.9 at 512/512)
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


def flash_shapes_ok(q_shape, k_shape) -> bool:
    """Whether (B, L, H, D) q/k shapes satisfy the kernel's constraints:
    tile shapes (L multiple of 128 or a sub-128 multiple of 8, D <= 128)
    AND per-program K/V VMEM residency (k_len * H * D within
    _MAX_KV_BLOCK_ELEMENTS).  Callers dispatch on THIS instead of
    catching ValueError from `flash_attention` — a blanket except around
    a traced call swallowed an unrelated shard_map vma error for a full
    round and silently downgraded the bench to the O(L^2) reference path
    (round-5 profile finding)."""
    def bad(length):
        return (length >= 128 and length % 128 != 0) or (
            length < 128 and length % 8 != 0
        )

    heads, dim = q_shape[2], q_shape[3]
    return not (
        bad(q_shape[1])
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
    if not; head dim <= 128.
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
    if not flash_shapes_ok(q.shape, k.shape) or k.shape != v.shape:
        raise ValueError(
            f"flash_attention needs L a multiple of 128 (or a sub-128 "
            f"multiple of 8) for BOTH q and k/v, k.shape == v.shape, "
            f"D <= 128, and Lk*H*D <= {_MAX_KV_BLOCK_ELEMENTS} (the "
            f"per-program K/V VMEM residency ceiling); got "
            f"Lq={q.shape[1]}, Lk={k.shape[1]}, H={q.shape[2]}, "
            f"D={q.shape[3]}"
        )
    return _flash(q, k, v, causal, scale)
