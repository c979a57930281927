"""The delta rule with a decay for every CHANNEL of the key (a linear
attention layer's token mixer: its state is carried along the whole
sequence), chunked, forward and backward:

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t            S in R^{dk x dv} a head, S_0 = 0, float32
    a_t = exp(g_t) in (0, 1]   g (dk,) a token and head, b a scalar

`kda` is the one entry a model calls: two Pallas kernels where the
shapes tile (`kda_shapes_ok`), named `kda_chunk_fwd` and `kda_chunk_bwd`
so that a device trace tells them from every other fusion, and
`chunked_kda`, the same chunked mathematics in plain `jnp` under
autodiff, elsewhere (the arrangement of `ops/flash_attention.py:
causal_attention`).

The chunked form (the WY / UT transform).  Inside a chunk of C tokens,
with G_i the running sum of g from the chunk's first token to token i,
D_ij = exp(G_i - G_j) (per channel, j <= i) and S the state the chunk
starts from:

    M_ij = sum_c k_ic k_jc D_ijc  (j < i)     P_ij = sum_c q_ic k_jc D_ijc
    T    = (I + Diag(b) M)^-1                 (j <= i)
    U    = T (b * (V - (K e^G) S))            the rows the chunk writes
    O    = (Q e^G) S + P U
    S'   = Diag(e^{G_C}) S + (K e^{G_C - G})^T U

EVERY EXPONENT IS <= 0: `1 / exp(G)` is never formed (it overflows where
the decay is strong).  The matrix products over D split the chunk into
sub-blocks of `_SUB` rows: a sub-block of rows against the rows BEFORE it
goes through the MXU with both sides scaled against the sub-block's first
row (exp(G_i - G_ref) and exp(G_ref - G_j), both <= 0 there), and the
diagonal sub-blocks are taken a column at a time, elementwise, with the
exponent clamped at 0 where the mask hides it.

WHAT IS FORMED OF (I + A)^-1: nothing.  U is found by substitution
(`_solve`), over the sub-blocks through the MXU (the rows found so far,
rounded to the stated type, times A) and inside a sub-block a row at a
time in float32 on the vector unit.  Two other ways were measured and
left out (`PERF.md` section 6, PR 54; `scripts/probe_scan_kernels.py`).
A Neumann product (I - A)(I + A^2)(I + A^4)... over the whole chunk is
exact on paper and cancels to nothing in float32 where keys align: it
turned the cell's loss to NaN once the job had learnt its pool.  T formed
block by block (the diagonal sub-blocks inverted by the same row steps,
the rest by merges through the MXU) and applied as products, U = T R and
dR = T^T dU, holds in float32 but reads two to five times the
substitution's error at operands of bfloat16 where keys align: in the
substitution a row rounded for the MXU is what every later sub-block is
corrected by, so a row's rounding is fed back to the rows after it, and a
product with T rounds every row unseen.  The backward's transposed system
(I + A)^T dR = dU is handed to the SAME substitution as the strictly upper
A^T, one transpose of a (C, C) tile, and runs from the last row up.

A system is a chain of C dependent row steps of a few cycles' work each,
and what it costs is that chain's latency, not a unit's throughput: the
systems of a grid step (a head's, and in the backward its transposed one,
which waits for nothing of U) are solved TOGETHER, a row step of each
after a row step of the others, in program order (the compiler does not
interleave heads whose bodies are written one after the other).

Kernel shape: the grid walks (batch, `_HEADS` heads a step, chunk), the
chunk axis sequential; operands stay (B, L, H*D) (the free view of the
model's layout, a head a column block), the state lives TRANSPOSED (dv,
dk) in float32 scratch so that every decay is a broadcast along lanes,
and the forward writes the state each chunk starts from.  The backward
walks the chunks from the last to the first with the state's gradient in
scratch and rebuilds a chunk's intermediates (G, M, P, U) from its inputs
and its boundary state: a chunked-scan backward.  The state, the running
sums, the substitution and the optional L2 norms of q and k (`qk_norm`)
are float32; the operands of every product through the MXU are the stated
type.

The forward's two results carry names (`checkpoint_name`): a caller that
rematerialises a block may save them by name (`model_zoo/common/
decoder.py: remat_block`), and its remat then has no use for the forward
kernel.  `SAVED_NAMES` is what that one policy keeps of them: NOTHING.
The output alone buys nothing (the backward needs the boundary states,
which only the forward kernel makes), and output and states together are
671 MB a layer (134 MB of bfloat16 output, 537 MB of float32 states at
(2, 8192, 32 heads)): 2.7 GB over four layers beside a compiled step
that already peaks at 15.3e9 of the chip's 16.9e9 bytes, to save four
forward calls of 11 ms (`PERF.md` section 6, PR 42).
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops.flash_attention import use_interpret

_LANES = 128
# Tokens of a chunk, and rows of a sub-block of it.
CHUNK = 64
_SUB = 16
# Heads of 128 a grid step takes at most (`_heads_a_step`): the step's
# linear systems are solved side by side, and four heads' fill the vector
# unit (1 | 2 | 4 read 11.6 | 9.9 | 8.1 ms forward and 22.5 | 21.3 | 17.6
# backward at the Kimi cell's shape; eight pass the kernel's 16 MiB of
# scoped VMEM).
_HEADS = 4
# The names of the forward's output and of the states the chunks start
# from, and which of them a block's remat keeps from the forward.
RESULT_NAMES = ("kda_core_out", "kda_core_states")
SAVED_NAMES = ()


def kda_shapes_ok(q_shape, k_shape, v_shape) -> bool:
    """Whether the kernels take (B, L, H, D) operands: q and k alike,
    heads of whole lane tiles, whole chunks."""
    return (
        len(q_shape) == 4 and tuple(q_shape) == tuple(k_shape)
        and tuple(q_shape[:3]) == tuple(v_shape[:3])
        and q_shape[3] % _LANES == 0 and v_shape[3] % _LANES == 0
        and q_shape[1] % CHUNK == 0
    )


# ---- the plain chunked form ------------------------------------------------


def _dot_last(a, b, dtype):
    """a (..., m, c) . b (..., n, c) -> (..., m, n), operands in `dtype`."""
    return jnp.einsum(
        "...mc,...nc->...mn", a.astype(dtype), b.astype(dtype),
        preferred_element_type=jnp.float32,
    )


def _jnp_chunk(state, chunk, dtype):
    """One chunk of every (batch, head): q, k (..., C, dk), v (..., C, dv),
    g (..., C, dk), b (..., C, 1); state (..., dv, dk) -> (state, o)."""
    q, k, v, g, b = (t.astype(jnp.float32) for t in chunk)
    size = q.shape[-2]
    G = jnp.cumsum(g, axis=-2)
    rows = jnp.arange(size)
    seen = rows[:, None] >= rows[None, :]
    decay = jnp.exp(jnp.where(
        seen[..., None], G[..., :, None, :] - G[..., None, :, :], -jnp.inf
    ))                                                  # (..., i, j, dk)
    M = jnp.einsum("...ic,...jc,...ijc->...ij", k, k, decay)
    P = jnp.einsum("...ic,...jc,...ijc->...ij", q, k, decay)
    A = jnp.where(rows[:, None] > rows[None, :], M * b, 0.0)
    eq = jnp.exp(G)
    R = b * (v - _dot_last(k * eq, state, dtype))
    U = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(size), R, lower=True, unit_diagonal=True
    )
    out = _dot_last(q * eq, state, dtype) + jnp.einsum(
        "...ij,...jv->...iv", P.astype(dtype), U.astype(dtype),
        preferred_element_type=jnp.float32,
    )
    last = G[..., -1:, :]
    state = state * jnp.exp(last) + jnp.einsum(
        "...iv,...ic->...vc", U.astype(dtype),
        (k * jnp.exp(last - G)).astype(dtype),
        preferred_element_type=jnp.float32,
    )
    return state, out


def l2_normed(x, eps: float, scale: float = 1.0):
    """x / sqrt(|x|^2 + eps) * scale over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * (scale * jax.lax.rsqrt(
        jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps
    ))


def chunked_kda(q, k, v, g, beta, qk_norm=None, chunk: int = CHUNK):
    """The plain form: a `lax.scan` over chunks of `chunk` tokens, every
    (batch, head) at once, each chunk rebuilt in the backward from the
    state it starts from.  A length that is no whole number of chunks is
    padded with tokens that leave the state as it is (k = 0, b = 0,
    g = 0) and whose outputs are dropped."""
    batch, length, heads, dim = q.shape
    dtype = q.dtype
    if qk_norm is not None:
        q, k = l2_normed(q, *qk_norm), l2_normed(k, qk_norm[0])
    pad = -length % chunk
    if pad:
        q, k, v, g = (
            jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
            for t in (q, k, v, g)
        )
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))

    def chunks(t):
        """(B, L, H, D) -> (N, B, H, chunk, D)."""
        return t.reshape(batch, -1, chunk, heads, t.shape[-1]).transpose(
            1, 0, 3, 2, 4
        )

    step = jax.checkpoint(functools.partial(_jnp_chunk, dtype=dtype))
    _, out = jax.lax.scan(
        step, jnp.zeros((batch, heads, v.shape[-1], dim), jnp.float32),
        tuple(chunks(t) for t in (q, k, v, g, beta[..., None])),
    )
    out = out.transpose(1, 0, 3, 2, 4).reshape(batch, length + pad, heads, -1)
    return out[:, :length].astype(dtype)


# ---- the kernels' mathematics, on one chunk's values -----------------------


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _dot(a, b, contract, dtype):
    return jax.lax.dot_general(
        a.astype(dtype), b.astype(dtype), (contract, ((), ())),
        preferred_element_type=jnp.float32,
    )


_ROWS, _COLS, _ROW_COL = ((0,), (0,)), ((1,), (1,)), ((1,), (0,))


def _prefix(x, reverse: bool = False):
    """Running sums down the rows (up them with `reverse`), inclusive, by
    doubling shifts: log2(rows) adds."""
    rows = x.shape[0]
    at = _iota(x.shape, 0)
    shift = 1
    while shift < rows:
        if reverse:
            moved = jnp.where(
                at < rows - shift, pltpu.roll(x, rows - shift, 0), 0.0
            )
        else:
            moved = jnp.where(at >= shift, pltpu.roll(x, shift, 0), 0.0)
        x = x + moved
        shift *= 2
    return x


def _sub_blocks(G):
    """[(first row, exp(G_I - G_ref), exp(min(G_ref - G, 0)))] a sub-block
    I of `_SUB` rows, G_ref its first row: the two sides of a product of
    the sub-block's rows with the rows before it."""
    return [
        (r0, jnp.exp(G[r0:r0 + _SUB] - G[r0:r0 + 1]),
         jnp.exp(jnp.minimum(G[r0:r0 + 1] - G, 0.0)))
        for r0 in range(0, G.shape[0], _SUB)
    ]


def _diagonal_decay(G, r0, j):
    """exp(G_i - G_j) of a sub-block's rows i against its row j, 1 where
    i < j (masked by the caller)."""
    return jnp.exp(jnp.minimum(
        G[r0:r0 + _SUB] - G[r0 + j:r0 + j + 1], 0.0
    ))


def _tri(q, k, G, dtype):
    """(M, P): sum_c k_ic k_jc D_ijc below the diagonal and sum_c q_ic
    k_jc D_ijc on and below it, (C, C) float32 from float32 q, k, G."""
    size = G.shape[0]
    m_rows, p_rows = [], []
    for r0, e_in, e_out in _sub_blocks(G):
        col = _iota((_SUB, size), 1)
        q_blk, k_blk = q[r0:r0 + _SUB], k[r0:r0 + _SUB]
        if r0:
            before = k * e_out
            m_blk = jnp.where(
                col < r0, _dot(k_blk * e_in, before, _COLS, dtype), 0.0
            )
            p_blk = jnp.where(
                col < r0, _dot(q_blk * e_in, before, _COLS, dtype), 0.0
            )
        else:
            m_blk = p_blk = jnp.zeros((_SUB, size), jnp.float32)
        for j in range(_SUB):
            t = _diagonal_decay(G, r0, j) * k[r0 + j:r0 + j + 1]
            hot = col == r0 + j
            m_blk = m_blk + jnp.where(
                hot, (k_blk * t).sum(axis=1, keepdims=True), 0.0
            )
            p_blk = p_blk + jnp.where(
                hot, (q_blk * t).sum(axis=1, keepdims=True), 0.0
            )
        m_rows.append(m_blk)
        p_rows.append(p_blk)
    row, col = _iota((size, size), 0), _iota((size, size), 1)
    return (
        jnp.where(row > col, jnp.concatenate(m_rows, axis=0), 0.0),
        jnp.where(row >= col, jnp.concatenate(p_rows, axis=0), 0.0),
    )


def _tri_backward(dP, dM, q, k, G, dtype):
    """Gradients through `_tri`: (dq, the part of dk that comes to row i
    as a ROW of M, the part that comes to row j as a COLUMN of M and P).
    dP is lower with its diagonal, dM strictly lower."""
    size = G.shape[0]
    dq_rows, dk_rows, diag_cols = [], [], []
    dk_col = jnp.zeros_like(k)
    for r0, e_in, e_out in _sub_blocks(G):
        col = _iota((_SUB, size), 1)
        at = _iota((_SUB, k.shape[1]), 0)
        q_blk, k_blk = q[r0:r0 + _SUB], k[r0:r0 + _SUB]
        dP_blk, dM_blk = dP[r0:r0 + _SUB], dM[r0:r0 + _SUB]
        if r0:
            before = k * e_out
            dP_off = jnp.where(col < r0, dP_blk, 0.0)
            dM_off = jnp.where(col < r0, dM_blk, 0.0)
            dq_blk = _dot(dP_off, before, _ROW_COL, dtype) * e_in
            dk_blk = _dot(dM_off, before, _ROW_COL, dtype) * e_in
            dk_col = dk_col + e_out * (
                _dot(dM_off, k_blk * e_in, _ROWS, dtype)
                + _dot(dP_off, q_blk * e_in, _ROWS, dtype)
            )
        else:
            dq_blk = dk_blk = jnp.zeros_like(k_blk)
        diag = jnp.zeros_like(k_blk)
        for j in range(_SUB):
            decay = _diagonal_decay(G, r0, j)
            hot = col == r0 + j
            wp = jnp.where(hot, dP_blk, 0.0).sum(axis=1, keepdims=True)
            wm = jnp.where(hot, dM_blk, 0.0).sum(axis=1, keepdims=True)
            t = decay * k[r0 + j:r0 + j + 1]
            dq_blk = dq_blk + wp * t
            dk_blk = dk_blk + wm * t
            diag = diag + jnp.where(
                at == j,
                ((wm * k_blk + wp * q_blk) * decay).sum(
                    axis=0, keepdims=True
                ),
                0.0,
            )
        dq_rows.append(dq_blk)
        dk_rows.append(dk_blk)
        diag_cols.append(diag)
    return (
        jnp.concatenate(dq_rows, axis=0), jnp.concatenate(dk_rows, axis=0),
        dk_col + jnp.concatenate(diag_cols, axis=0),
    )


def _column(rows, j: int):
    """Column j of some rows of A as (rows, 1)."""
    return jnp.where(_iota(rows.shape, 1) == j, rows, 0.0).sum(
        axis=1, keepdims=True
    )


def _solve(systems, dtype):
    """[X of (I + A) X = R] for every (A, R, upper) of `systems`: A (C, C)
    strictly lower, or strictly UPPER with `upper` (a backward's
    transposed system, handed over as the transpose so that both kinds
    are ONE substitution, run from opposite ends).  Over the sub-blocks
    the rows found so far, with zeros for the rest, meet A whole through
    the MXU, so nothing is sliced along the lanes and the diagonal block
    meets zeros; inside a sub-block a row at a time in float32 on the
    vector unit: once row j stands, every row after it (before it, with
    `upper`) sheds A[i, j] X_j.  No inverse is formed, so nothing cancels
    where keys align (|A| near 1), and a row rounded to the stated type
    for the MXU is what the rows after it are corrected by.

    The systems advance TOGETHER, a row step of each after a row step of
    the others: one system is a chain of C dependent steps of a few
    cycles' work, and chains side by side in program order are what
    fills the vector unit (the heads of a grid step written one after the
    other are not interleaved by the compiler: `PERF.md` section 6, PR
    54)."""
    size = systems[0][1].shape[0]
    starts = list(range(0, size, _SUB))
    found = [{} for _ in systems]
    for n in range(len(starts)):
        first, own, rhs = [], [], []
        for (A, R, upper), mine in zip(systems, found):
            r0 = starts[-1 - n] if upper else starts[n]
            block = R[r0:r0 + _SUB]
            if mine:
                so_far = jnp.concatenate([
                    mine.get(at, jnp.zeros_like(block)) for at in starts
                ], axis=0)
                block = block - _dot(A, so_far, _ROW_COL, dtype)[
                    r0:r0 + _SUB
                ]
            first.append(r0)
            own.append(A[r0:r0 + _SUB])
            rhs.append(block)
        for step in range(_SUB - 1):
            for s, (_, _, upper) in enumerate(systems):
                j = _SUB - 1 - step if upper else step
                rhs[s] = rhs[s] - _column(own[s], first[s] + j) * rhs[s][
                    j:j + 1
                ]
        for mine, r0, block in zip(found, first, rhs):
            mine[r0] = block
    return [
        jnp.concatenate([mine[at] for at in starts], axis=0)
        for mine in found
    ]


# What a chunk's two halves share: the (normed) q and k, b, and the
# intermediates made before the linear system is solved.
_Chunk = collections.namedtuple(
    "_Chunk", "q k b G M P eq Qg Kb Z ed Kd e_last normed"
)


def _rebuild(q, k, v, g, b, state, dtype, qk_norm=None):
    """A chunk up to its linear system, from its inputs (float32 values)
    and the (dv, dk) state it starts from: (what the rest of the chunk
    reads, the system (A, R) whose solution is U)."""
    normed = None
    if qk_norm is not None:
        q, q_unit, q_factor = _l2(q, *qk_norm)
        k, k_unit, k_factor = _l2(k, qk_norm[0], 1.0)
        normed = (q_unit, q_factor, k_unit, k_factor)
    G = _prefix(g)
    M, P = _tri(q, k, G, dtype)
    eq = jnp.exp(G)
    Qg, Kb = q * eq, k * eq
    Z = v - _dot(Kb, state, _COLS, dtype)
    last = G[-1:]
    ed = jnp.exp(last - G)
    rebuilt = _Chunk(
        q, k, b, G, M, P, eq, Qg, Kb, Z, ed, k * ed, jnp.exp(last), normed
    )
    return rebuilt, (M * b, b * Z)


def _l2(x, eps: float, scale: float):
    """(x / sqrt(|x|^2 + eps) * scale a row, the unit row, the factor of
    its gradient)."""
    r = jax.lax.rsqrt((x * x).sum(axis=1, keepdims=True) + eps)
    unit = x * r
    return unit * scale, unit, r * scale


def _l2_backward(dy, unit, factor):
    return factor * (dy - unit * (unit * dy).sum(axis=1, keepdims=True))


def _chunk_forward(rebuilt, U, state, dtype):
    """(o (C, dv), the next state (dv, dk)), float32."""
    out = _dot(rebuilt.Qg, state, _COLS, dtype) + _dot(
        rebuilt.P, U, _ROW_COL, dtype
    )
    return out, state * rebuilt.e_last + _dot(U, rebuilt.Kd, _ROWS, dtype)


def _written_gradient(rebuilt, d_out, d_next, dtype):
    """dU, the gradient of the rows the chunk writes, from the gradients
    of its output (O = Qg S + P U) and of the state it leaves (S' = S
    e^{G_C} + U^T Kd): it does not wait for U."""
    return _dot(rebuilt.P, d_out, _ROWS, dtype) + _dot(
        rebuilt.Kd, d_next, _COLS, dtype
    )


def _chunk_backward(rebuilt, U, dR, state, d_out, d_next, dtype):
    """(dq, dk, dv, dg, db, the gradient of the chunk's starting state)
    from the gradients of its output and of the state it leaves, U and
    dR = (I + A)^-T dU."""
    q, k, b, G, M, P, eq, Qg, Kb, Z, ed, Kd, e_last, normed = rebuilt
    size = G.shape[0]
    row, col = _iota((size, size), 0), _iota((size, size), 1)
    # O = Qg S + P U
    dQg = _dot(d_out, state, _ROW_COL, dtype)
    d_state = _dot(d_out, Qg, _ROWS, dtype)
    dP = jnp.where(row >= col, _dot(d_out, U, _COLS, dtype), 0.0)
    # S' = S e^{G_C} + U^T Kd
    d_state = d_state + d_next * e_last
    d_last = (state * d_next).sum(axis=0, keepdims=True)
    dKd = _dot(U, d_next, _ROW_COL, dtype)
    # U = T R, R = b Z, T = (I + b M)^-1, Z = V - Kb S
    dA = -jnp.where(row > col, _dot(dR, U, _COLS, dtype), 0.0)
    db = (dR * Z).sum(axis=1, keepdims=True) + (dA * M).sum(
        axis=1, keepdims=True
    )
    dZ = b * dR
    dKb = -_dot(dZ, state, _ROW_COL, dtype)
    d_state = d_state - _dot(dZ, Kb, _ROWS, dtype)
    dq_in, dk_row, dk_col = _tri_backward(dP, dA * b, q, k, G, dtype)
    dq = dq_in + dQg * eq
    dk = dk_row + dk_col + dKb * eq + dKd * ed
    dG = (
        q * dq_in + k * (dk_row - dk_col) + dQg * Qg + dKb * Kb - dKd * Kd
    )
    # every g of the chunk is in G_C: e^{G_C} and Kd's exponent
    d_sum = (dKd * Kd).sum(axis=0, keepdims=True) + d_last * e_last
    if normed is not None:
        dq = _l2_backward(dq, normed[0], normed[1])
        dk = _l2_backward(dk, normed[2], normed[3])
    return dq, dk, dZ, _prefix(dG, reverse=True) + d_sum, db, d_state


# ---- the kernels -----------------------------------------------------------


def _head(ref, h: int, dim: int):
    """Head `h` of a (1, C, heads * dim) block, float32."""
    return ref[0, :, h * dim:(h + 1) * dim].astype(jnp.float32)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, states_ref,
                state_sc, *, dk: int, dv: int, qk_norm):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state_sc[...] = jnp.zeros(state_sc.shape, jnp.float32)

    # the heads of a step are independent chains: their systems are
    # solved side by side (`_solve`)
    dtype = q_ref.dtype
    heads = range(state_sc.shape[0])
    states = [state_sc[h] for h in heads]
    rebuilt, systems = zip(*(
        _rebuild(
            _head(q_ref, h, dk), _head(k_ref, h, dk), _head(v_ref, h, dv),
            _head(g_ref, h, dk), b_ref[0, h], states[h], dtype, qk_norm,
        )
        for h in heads
    ))
    solved = _solve([(A, R, False) for A, R in systems], dtype)
    for h in heads:
        states_ref[0, h, 0] = states[h]
        out, state_sc[h] = _chunk_forward(
            rebuilt[h], solved[h], states[h], dtype
        )
        o_ref[0, :, h * dv:(h + 1) * dv] = out.astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, d_state_sc, *,
                dk: int, dv: int, qk_norm):
    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state_sc[...] = jnp.zeros(d_state_sc.shape, jnp.float32)

    dtype = q_ref.dtype
    heads = range(d_state_sc.shape[0])
    states = [states_ref[0, h, 0] for h in heads]
    d_outs = [_head(do_ref, h, dv) for h in heads]
    d_nexts = [d_state_sc[h] for h in heads]
    rebuilt, systems = zip(*(
        _rebuild(
            _head(q_ref, h, dk), _head(k_ref, h, dk), _head(v_ref, h, dv),
            _head(g_ref, h, dk), b_ref[0, h], states[h], dtype, qk_norm,
        )
        for h in heads
    ))
    # a head's two systems, U's and its gradient's transposed one, wait
    # for nothing of each other: all of a step's are solved side by side
    solved = _solve(
        [(A, R, False) for A, R in systems] + [
            (A.T, _written_gradient(rebuilt[h], d_outs[h], d_nexts[h],
                                    dtype), True)
            for h, (A, _) in enumerate(systems)
        ],
        dtype,
    )
    for h in heads:
        keys = slice(h * dk, (h + 1) * dk)
        values = slice(h * dv, (h + 1) * dv)
        dq, dk_, dv_, dg, db, d_state_sc[h] = _chunk_backward(
            rebuilt[h], solved[h], solved[len(heads) + h], states[h],
            d_outs[h], d_nexts[h], dtype,
        )
        dq_ref[0, :, keys] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, keys] = dk_.astype(dk_ref.dtype)
        dv_ref[0, :, values] = dv_.astype(dv_ref.dtype)
        dg_ref[0, :, keys] = dg
        db_ref[0, h] = db


def _call(kernel, grid, in_specs, out_specs, out_shape, scratch, vma,
          interpret, name):
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
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret, name=name,
    )


def _vma(operands):
    return frozenset().union(*(jax.typeof(x).vma for x in operands))


def _heads_a_step(heads: int, width: int = _LANES, most=None) -> int:
    """`most` (`_HEADS`) heads of `_LANES`, as many fewer as a wider head
    asks, or the largest half of that which divides the head count."""
    most = max(1, (most or _HEADS) * _LANES // width)
    while heads % most:
        most //= 2
    return most


def _specs(chunks: int, group: int, dk: int, dv: int, reverse: bool):
    """Block specs of a grid (batch, group of heads, step) by role: a
    chunk of a (B, L, H*D) operand's `group` heads, of the (B, H, L, 1)
    row scalars, and the (B, H, N, dv, dk) states; `reverse` walks the
    chunks from the last."""
    def at(n):
        return chunks - 1 - n if reverse else n

    def rows(dim):
        return pl.BlockSpec(
            (1, CHUNK, group * dim), lambda b, h, n: (b, at(n), h)
        )

    scalars = pl.BlockSpec(
        (1, group, CHUNK, 1), lambda b, h, n: (b, h, at(n), 0)
    )
    states = pl.BlockSpec(
        (1, group, 1, dv, dk), lambda b, h, n: (b, h, at(n), 0, 0)
    )
    return rows, scalars, states


def _flat(t):
    return t.reshape(*t.shape[:2], -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kda(q, k, v, g, beta, qk_norm=None):
    return _kda_fwd(q, k, v, g, beta, qk_norm)[0]


# A layer's call of a kernel is the call of every layer of that shape: the
# callable is built once a shape, so jax traces the kernel's body (the
# unrolled substitution: seconds of host time) once a process and not
# once a layer and pass (a five-layer step called it twelve times).
@functools.lru_cache(maxsize=None)
def _forward_call(batch, length, heads, dk, dv, dtype, qk_norm, vma,
                  interpret):
    chunks = length // CHUNK
    group = _heads_a_step(heads, max(dk, dv))
    rows, scalars, states = _specs(chunks, group, dk, dv, reverse=False)
    return _call(
        functools.partial(_fwd_kernel, dk=dk, dv=dv, qk_norm=qk_norm),
        (batch, heads // group, chunks),
        [rows(dk), rows(dk), rows(dv), rows(dk), scalars],
        [rows(dv), states],
        [((batch, length, heads * dv), dtype),
         ((batch, heads, chunks, dv, dk), jnp.float32)],
        [pltpu.VMEM((group, dv, dk), jnp.float32)],
        vma, interpret, "kda_chunk_fwd",
    )


@functools.lru_cache(maxsize=None)
def _backward_call(batch, length, heads, dk, dv, dtypes, qk_norm, vma,
                   interpret):
    chunks = length // CHUNK
    group = _heads_a_step(heads, max(dk, dv))
    rows, scalars, states = _specs(chunks, group, dk, dv, reverse=True)
    return _call(
        functools.partial(_bwd_kernel, dk=dk, dv=dv, qk_norm=qk_norm),
        (batch, heads // group, chunks),
        [rows(dk), rows(dk), rows(dv), rows(dk), scalars, states, rows(dv)],
        [rows(dk), rows(dk), rows(dv), rows(dk), scalars],
        [((batch, length, heads * dk), dtypes[0]),
         ((batch, length, heads * dk), dtypes[1]),
         ((batch, length, heads * dv), dtypes[2]),
         ((batch, length, heads * dk), jnp.float32),
         ((batch, heads, length, 1), jnp.float32)],
        [pltpu.VMEM((group, dv, dk), jnp.float32)],
        vma, interpret, "kda_chunk_bwd",
    )


def _kda_fwd(q, k, v, g, beta, qk_norm):
    batch, length, heads, dk = q.shape
    dv = v.shape[3]
    b = beta.astype(jnp.float32).transpose(0, 2, 1)[..., None]
    operands = [_flat(q), _flat(k), _flat(v), _flat(g), b]
    out, boundary = _forward_call(
        batch, length, heads, dk, dv, jnp.dtype(q.dtype), qk_norm,
        _vma(operands), use_interpret(),
    )(*operands)
    out, boundary = (
        checkpoint_name(t, name)
        for t, name in zip((out, boundary), RESULT_NAMES)
    )
    return out.reshape(batch, length, heads, dv), (
        q, k, v, g, beta, boundary
    )


def _kda_bwd(qk_norm, residuals, d_out):
    q, k, v, g, beta, boundary = residuals
    batch, length, heads, dk = q.shape
    dv = v.shape[3]
    b = beta.astype(jnp.float32).transpose(0, 2, 1)[..., None]
    operands = [_flat(q), _flat(k), _flat(v), _flat(g), b, boundary,
                _flat(d_out.astype(q.dtype))]
    dq, dk_, dv_, dg, db = _backward_call(
        batch, length, heads, dk, dv,
        tuple(jnp.dtype(t.dtype) for t in (q, k, v)), qk_norm,
        _vma(operands), use_interpret(),
    )(*operands)
    return (
        dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
        dg.reshape(g.shape).astype(g.dtype),
        db[..., 0].transpose(0, 2, 1).astype(beta.dtype),
    )


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda(q, k, v, g, beta, qk_norm=None):
    """The gated delta rule of q, k (B, L, H, dk), v (B, L, H, dv) in the
    stated type under the log-decay g (B, L, H, dk) <= 0 and the write
    strength beta (B, L, H), both float32 -> o (B, L, H, dv) in q's type
    (module docstring): the Pallas kernels where the shapes tile
    (`kda_shapes_ok`), the chunked `jnp` form elsewhere, which pads a
    length that is no whole number of chunks.  With `qk_norm` = (eps,
    q's scale), q and k are first L2-normalised a head, x / sqrt(|x|^2 +
    eps), and q scaled, in float32 INSIDE the op (a chunk's rows are in
    the kernel's registers anyway; as passes of their own over (B, L, H,
    dk) the two norms and their backward cost a layer more than the
    conv before them)."""
    from elasticdl_tpu.parallel.mesh import in_export_mode

    g = g.astype(jnp.float32)
    if qk_norm is not None:
        qk_norm = (float(qk_norm[0]), float(qk_norm[1]))
    if kda_shapes_ok(q.shape, k.shape, v.shape) and not in_export_mode():
        return _kda(q, k, v, g, beta, qk_norm)
    return chunked_kda(q, k, v, g, beta, qk_norm)
