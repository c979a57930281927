"""The delta rule with ONE decay a head (the gated delta rule of a linear
attention layer whose forget gate is a scalar), chunked, forward and
backward, with value heads that SHARE key heads:

    S_t = exp(g_t) S_{t-1};  S_t += k_t (b_t (v_t - S_t^T k_t))^T
    o_t = S_t^T q_t            S in R^{dk x dv} a VALUE head, S_0 = 0
    g, b one number a token and value head, float32, g <= 0
    value head h reads the q and k of key head h // r,  r = H_v / H_k

which is `ops/kda.py`'s recurrence with Diag(a_t) = exp(g_t) I.  `gdn` is
the one entry a model calls: two Pallas kernels where the shapes tile
(`gdn_shapes_ok`), named `gdn_chunk_fwd` and `gdn_chunk_bwd` so that a
device trace tells them from KDA's and from every other fusion, and
`chunked_gdn`, the same chunked mathematics in plain `jnp` under autodiff,
elsewhere: the arrangement of `ops/kda.py: kda`.  Key and value heads may
differ in width (the state is (dv, dk), not square), and a width that is
no whole lane tile (96, 192) reaches the kernels padded with zero columns
to the next one (`_lane_padded`): exact, and a quarter of what they then
process is padding (`padded_lanes_ratio`).

The chunked form is `ops/kda.py`'s header with D a (C, C) MATRIX a head
and not a (C, C, dk) object: with G_i the running sum of g over the
chunk's tokens up to i, D_ij = exp(G_i - G_j) (j <= i),

    M = (K K^T) o D  (j < i)        P = (Q K^T) o D  (j <= i)
    U = (I + Diag(b) M)^-1 (b * (V - e^G o (K S)))
    O = e^G o (Q S) + P U
    S' = e^{G_C} S + (K o e^{G_C - G})^T U

so M and P are ONE product through the MXU each and one mask: no exponent
over channels, no sub-block references, and nothing that can overflow
(every exponent is a difference G_i - G_j with j <= i, so every D_ij <=
1).  K K^T and Q K^T, and the optional L2 norms of q and k (`qk_norm`),
are computed once a KEY head and serve its r value heads; U and, in the
backward, dR of (I + A)^T dR = dU are found by `ops/kda.py`'s substitution
(`_solve`: nothing of (I + A)^-1 is formed, its header says why), the
systems of all of a grid step's value heads side by side.

Kernel shape: the grid walks (batch, a group of heads, chunk), the chunk
axis sequential; a step takes up to `_HEADS` value heads, whole key heads
each with its r value heads, so q and k are read from HBM once a key head
(the index map picks the key head's column block: no repeated copy of q
or k exists).  Operands stay (B, L, H*D), the state
lives TRANSPOSED (dv, dk) in float32 scratch, and the forward writes the
state each chunk starts from; the backward walks the chunks from the
last, rebuilds a chunk from its inputs and its boundary state, and sums
dq and dk over a key head's value heads before it writes them.  g and b
travel as ROWS, (B, H_v, chunks, 2, C) float32 (g over b): a (B, H_v, L,
1) column a scalar, as KDA hands beta over, is padded to 128 lanes in HBM
(268 MB an array at (2, 8192, 32) where the row form is 34 MB), and a
row becomes the column the algebra wants by one masked reduce of a (C, C)
tile.  The state, the running sums, the substitution and the norms are
float32; the operands of every product through the MXU are the stated
type.

A block's remat keeps NOTHING of the forward (`SAVED_NAMES`), for
`ops/kda.py`'s reason: the backward needs the boundary states, which only
the forward kernel makes.
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
from elasticdl_tpu.ops.kda import (
    _COLS,
    _ROW_COL,
    _ROWS,
    _call,
    _dot,
    _dot_last,
    _heads_a_step,
    _iota,
    _l2,
    _l2_backward,
    _solve,
    _vma,
    l2_normed,
)

_LANES = 128
# Tokens of a chunk (the substitution's sub-blocks are `ops/kda.py`'s).
CHUNK = 64
# Value heads of 128 a grid step takes at most, whole key heads each with
# its r value heads (`_groups`): their linear systems are solved side by
# side (1 | 2 | 4 key heads of two value heads read 5.4 | 3.7 | 3.4 ms
# forward and 9.5 | 9.2 | 8.4 backward at the Qwen3-Next cell's shape;
# eight pass the kernel's 16 MiB of scoped VMEM).
_HEADS = 8
RESULT_NAMES = ("gdn_core_out", "gdn_core_states")
SAVED_NAMES = ()


def _whole_tiles(width: int) -> int:
    """`width` columns in whole lane tiles."""
    return -(-width // _LANES) * _LANES


def _pads_little(width: int) -> bool:
    """Whether at most a quarter of a head's whole lane tiles is padding
    (96 of 128, 192 of 256; not 64 of 128, nor a test model's 8): a
    narrower head goes the plain form, where the kernels would mostly
    multiply zeros."""
    return 4 * width >= 3 * _whole_tiles(width)


def gdn_shapes_ok(q_shape, k_shape, v_shape) -> bool:
    """Whether the kernels take q, k (B, L, H_k, dk) and v (B, L, H_v,
    dv): q and k alike, whole value heads a key head and no more of them
    than a grid step takes (`_HEADS` of `_LANES`), whole chunks, and heads
    of whole lane tiles or little short of them (`_pads_little`), which
    `gdn` pads to whole tiles (96 -> 128, 192 -> 256); dk and dv need not
    be equal."""
    return (
        len(q_shape) == 4 and len(v_shape) == 4
        and tuple(q_shape) == tuple(k_shape)
        and tuple(q_shape[:2]) == tuple(v_shape[:2])
        and v_shape[2] % q_shape[2] == 0
        and v_shape[2] // q_shape[2] * max(
            _whole_tiles(q_shape[3]), _whole_tiles(v_shape[3])
        ) <= _HEADS * _LANES
        and _pads_little(q_shape[3]) and _pads_little(v_shape[3])
        and q_shape[1] % CHUNK == 0
    )


def padded_lanes_ratio(q_shape, k_shape, v_shape) -> float:
    """The share of the q, k and v columns the kernels process that is
    padding: 0.25 at heads of 96 | 192, 0 at heads of whole lane tiles and
    where the shapes go the plain form (no kernel processes anything)."""
    if not gdn_shapes_ok(q_shape, k_shape, v_shape):
        return 0.0
    (key_heads, dk), (heads, dv) = q_shape[2:], v_shape[2:]
    real = 2 * key_heads * dk + heads * dv
    processed = 2 * key_heads * _whole_tiles(dk) + heads * _whole_tiles(dv)
    return 1.0 - real / processed


def _lane_padded(t):
    """(B, L, H, D) with zero columns up to whole lane tiles a head.
    Zero q and k columns add nothing to Q K^T, K K^T or |x|^2 (the L2
    norm's scale is handed over, not taken from the width), zero v columns
    give zero state rows and zero output columns: the kernels' result on
    the real columns is the unpadded one, and autodiff slices the
    gradients back."""
    extra = _whole_tiles(t.shape[3]) - t.shape[3]
    if not extra:
        return t
    return jnp.pad(t, ((0, 0), (0, 0), (0, 0), (0, extra)))


# ---- the plain chunked form ------------------------------------------------


def _jnp_chunk(state, chunk, dtype):
    """One chunk of every (batch, value head): q, k (..., C, dk), v (...,
    C, dv), g and b (..., C, 1); state (..., dv, dk) -> (state, o)."""
    q, k, v, g, b = (t.astype(jnp.float32) for t in chunk)
    size = q.shape[-2]
    G = jnp.cumsum(g, axis=-2)                              # (..., C, 1)
    rows = jnp.arange(size)
    seen = rows[:, None] >= rows[None, :]
    decay = jnp.exp(jnp.where(seen, G - jnp.swapaxes(G, -1, -2), -jnp.inf))
    M = _dot_last(k, k, dtype) * decay
    P = _dot_last(q, k, dtype) * decay
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


def chunked_gdn(q, k, v, g, beta, qk_norm=None, chunk: int = CHUNK):
    """The plain form: a `lax.scan` over chunks of `chunk` tokens, every
    (batch, value head) at once, each chunk rebuilt in the backward from
    the state it starts from; q and k are repeated to the value heads (a
    copy the kernels never make).  A length that is no whole number of
    chunks is padded with tokens that leave the state as it is (k = 0,
    b = 0, g = 0) and whose outputs are dropped."""
    batch, length, key_heads, dim = q.shape
    heads = v.shape[2]
    dtype = q.dtype
    if qk_norm is not None:
        q, k = l2_normed(q, *qk_norm), l2_normed(k, qk_norm[0])
    if heads != key_heads:
        q, k = (jnp.repeat(t, heads // key_heads, axis=2) for t in (q, k))
    pad = -length % chunk
    if pad:
        q, k, v = (
            jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0))) for t in (q, k, v)
        )
        g, beta = (
            jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (g, beta)
        )

    def chunks(t):
        """(B, L, H, D) -> (N, B, H, chunk, D)."""
        return t.reshape(batch, -1, chunk, heads, t.shape[-1]).transpose(
            1, 0, 3, 2, 4
        )

    step = jax.checkpoint(functools.partial(_jnp_chunk, dtype=dtype))
    _, out = jax.lax.scan(
        step, jnp.zeros((batch, heads, v.shape[-1], dim), jnp.float32),
        tuple(chunks(t) for t in (q, k, v, g[..., None], beta[..., None])),
    )
    out = out.transpose(1, 0, 3, 2, 4).reshape(batch, length + pad, heads, -1)
    return out[:, :length].astype(dtype)


# ---- the kernels' mathematics, on one chunk's values -----------------------


def _as_column(row):
    """A (1, C) row as the (C, 1) column: one masked reduce of a (C, C)
    tile (the row's C is no whole lane tile, so nothing transposes it)."""
    size = row.shape[1]
    eye = _iota((size, size), 0) == _iota((size, size), 1)
    return jnp.where(eye, row, 0.0).sum(axis=1, keepdims=True)


def _as_row(column):
    size = column.shape[0]
    eye = _iota((size, size), 0) == _iota((size, size), 1)
    return jnp.where(eye, column, 0.0).sum(axis=0, keepdims=True)


def _decays(g_row):
    """(G the running sums as a (C, 1) column, D (C, C) = exp(G_i - G_j)
    on and below the diagonal and 0 above it) of a chunk's g (1, C)."""
    size = g_row.shape[1]
    row, col = _iota((size, size), 0), _iota((size, size), 1)
    seen = row >= col
    G = jnp.where(seen, g_row, 0.0).sum(axis=1, keepdims=True)
    G_row = jnp.where(row <= col, _as_column(g_row), 0.0).sum(
        axis=0, keepdims=True
    )
    # g <= 0, so G_i <= G_j wherever j <= i; the clamp is for the masked
    # half, whose exponent would else be positive without bound
    D = jnp.where(seen, jnp.exp(jnp.minimum(G - G_row, 0.0)), 0.0)
    return G, D


# What a value head's two halves of a chunk share: the intermediates made
# before the linear system is solved.
_Chunk = collections.namedtuple(
    "_Chunk", "D b M P eq Qg Kb Z ed Kd e_last"
)


def _rebuild(q, k, qk, kk, v, g_row, b_row, state, dtype):
    """A chunk of ONE value head up to its linear system, from its inputs
    (float32 values; `qk`, `kk` the key head's Q K^T and K K^T) and the
    (dv, dk) state it starts from: (what the rest of the chunk reads, the
    system (A, R) whose solution is U)."""
    size = q.shape[0]
    row, col = _iota((size, size), 0), _iota((size, size), 1)
    G, D = _decays(g_row)
    b = _as_column(b_row)
    M = jnp.where(row > col, kk * D, 0.0)
    P = qk * D
    eq = jnp.exp(G)
    Qg, Kb = q * eq, k * eq
    Z = v - _dot(Kb, state, _COLS, dtype)
    last = G[-1:]
    ed = jnp.exp(last - G)
    rebuilt = _Chunk(D, b, M, P, eq, Qg, Kb, Z, ed, k * ed, jnp.exp(last))
    return rebuilt, (M * b, b * Z)


def _key_head(q, k, dtype, qk_norm):
    """(q, k normed where asked, Q K^T, K K^T, what the norms' backward
    needs) of one key head: shared by its value heads."""
    normed = None
    if qk_norm is not None:
        q, q_unit, q_factor = _l2(q, *qk_norm)
        k, k_unit, k_factor = _l2(k, qk_norm[0], 1.0)
        normed = (q_unit, q_factor, k_unit, k_factor)
    return (
        q, k, _dot(q, k, _COLS, dtype), _dot(k, k, _COLS, dtype), normed
    )


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


def _chunk_backward(q, k, rebuilt, U, dR, state, d_out, d_next, dtype):
    """(dq, dk, dv, dg (1, C), db (1, C), the gradient of the chunk's
    starting state) of ONE value head from the gradients of its output
    and of the state it leaves, U and dR = (I + A)^-T dU; dq and dk are
    by the (normed) q and k the key head handed over."""
    D, b, M, P, eq, Qg, Kb, Z, ed, Kd, e_last = rebuilt
    size = q.shape[0]
    row, col = _iota((size, size), 0), _iota((size, size), 1)
    # O = Qg S + P U
    dQg = _dot(d_out, state, _ROW_COL, dtype)
    d_state = _dot(d_out, Qg, _ROWS, dtype)
    dP = jnp.where(row >= col, _dot(d_out, U, _COLS, dtype), 0.0)
    # S' = S e^{G_C} + U^T Kd
    d_state = d_state + d_next * e_last
    d_last = (state * d_next).sum(axis=1, keepdims=True).sum(
        axis=0, keepdims=True
    )
    dKd = _dot(U, d_next, _ROW_COL, dtype)
    # U = T R, R = b Z, T = (I + b M)^-1, Z = V - Kb S
    dA = -jnp.where(row > col, _dot(dR, U, _COLS, dtype), 0.0)
    db = (dR * Z).sum(axis=1, keepdims=True) + (dA * M).sum(
        axis=1, keepdims=True
    )
    dZ = b * dR
    dKb = -_dot(dZ, state, _ROW_COL, dtype)
    d_state = d_state - _dot(dZ, Kb, _ROWS, dtype)
    # M = (K K^T) o D below the diagonal, P = (Q K^T) o D on and below it
    dM = dA * b
    d_kk, d_qk = dM * D, dP * D
    dq = _dot(d_qk, k, _ROW_COL, dtype) + dQg * eq
    dk = (
        _dot(d_kk, k, _ROW_COL, dtype) + _dot(d_kk, k, _ROWS, dtype)
        + _dot(d_qk, q, _ROWS, dtype) + dKb * eq + dKd * ed
    )
    # D_ij = exp(G_i - G_j): a row's sum comes to G_i, a column's leaves G_j
    W = dM * M + dP * P
    dG = (
        W.sum(axis=1, keepdims=True) - _as_column(
            W.sum(axis=0, keepdims=True)
        )
        + (dQg * Qg + dKb * Kb - dKd * Kd).sum(axis=1, keepdims=True)
    )
    # every g of the chunk is in G_C: e^{G_C} and Kd's exponent
    d_sum = (dKd * Kd).sum(axis=1, keepdims=True).sum(
        axis=0, keepdims=True
    ) + d_last * e_last
    # g_j is in every G_i with i >= j
    dg = jnp.where(row >= col, dG, 0.0).sum(axis=0, keepdims=True) + d_sum
    return dq, dk, dZ, dg, _as_row(db), d_state


# ---- the kernels -----------------------------------------------------------


def _head(ref, h: int, dim: int):
    """Head `h` of a (1, C, heads * dim) block, float32."""
    return ref[0, :, h * dim:(h + 1) * dim].astype(jnp.float32)


def _fwd_kernel(q_ref, k_ref, v_ref, gb_ref, o_ref, states_ref, state_sc,
                *, dk: int, dv: int, ratio: int, qk_norm):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state_sc[...] = jnp.zeros(state_sc.shape, jnp.float32)

    dtype = q_ref.dtype
    heads = range(state_sc.shape[0])
    states = [state_sc[h] for h in heads]
    rebuilt, systems = [], []
    for kh in range(len(heads) // ratio):
        q, k, qk, kk, _ = _key_head(
            _head(q_ref, kh, dk), _head(k_ref, kh, dk), dtype, qk_norm
        )
        for h in range(kh * ratio, (kh + 1) * ratio):
            chunk, (A, R) = _rebuild(
                q, k, qk, kk, _head(v_ref, h, dv), gb_ref[0, h, 0, 0:1],
                gb_ref[0, h, 0, 1:2], states[h], dtype,
            )
            rebuilt.append(chunk)
            systems.append((A, R, False))
    # the value heads of a step are independent chains: their systems are
    # solved side by side (`ops/kda.py: _solve`)
    solved = _solve(systems, dtype)
    for h in heads:
        states_ref[0, h, 0] = states[h]
        out, state_sc[h] = _chunk_forward(
            rebuilt[h], solved[h], states[h], dtype
        )
        o_ref[0, :, h * dv:(h + 1) * dv] = out.astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, gb_ref, states_ref, do_ref, dq_ref,
                dk_ref, dv_ref, dgb_ref, d_state_sc, *, dk: int, dv: int,
                ratio: int, qk_norm):
    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state_sc[...] = jnp.zeros(d_state_sc.shape, jnp.float32)

    dtype = q_ref.dtype
    heads = range(d_state_sc.shape[0])
    states = [states_ref[0, h, 0] for h in heads]
    d_outs = [_head(do_ref, h, dv) for h in heads]
    d_nexts = [d_state_sc[h] for h in heads]
    key_heads, rebuilt, systems, transposed = [], [], [], []
    for kh in range(len(heads) // ratio):
        key_heads.append(_key_head(
            _head(q_ref, kh, dk), _head(k_ref, kh, dk), dtype, qk_norm
        ))
        q, k, qk, kk, _ = key_heads[-1]
        for h in range(kh * ratio, (kh + 1) * ratio):
            chunk, (A, R) = _rebuild(
                q, k, qk, kk, _head(v_ref, h, dv), gb_ref[0, h, 0, 0:1],
                gb_ref[0, h, 0, 1:2], states[h], dtype,
            )
            rebuilt.append(chunk)
            systems.append((A, R, False))
            transposed.append((
                A.T, _written_gradient(chunk, d_outs[h], d_nexts[h], dtype),
                True,
            ))
    # a head's two systems, U's and its gradient's transposed one, wait
    # for nothing of each other: all of a step's are solved side by side
    solved = _solve(systems + transposed, dtype)
    for kh, (q, k, _, _, normed) in enumerate(key_heads):
        keys = slice(kh * dk, (kh + 1) * dk)
        dq = dk_ = None
        for h in range(kh * ratio, (kh + 1) * ratio):
            values = slice(h * dv, (h + 1) * dv)
            dq_h, dk_h, dv_, dg, db, d_state_sc[h] = _chunk_backward(
                q, k, rebuilt[h], solved[h], solved[len(heads) + h],
                states[h], d_outs[h], d_nexts[h], dtype,
            )
            dq = dq_h if dq is None else dq + dq_h
            dk_ = dk_h if dk_ is None else dk_ + dk_h
            dv_ref[0, :, values] = dv_.astype(dv_ref.dtype)
            dgb_ref[0, h, 0, 0:1] = dg
            dgb_ref[0, h, 0, 1:2] = db
        if normed is not None:
            dq = _l2_backward(dq, normed[0], normed[1])
            dk_ = _l2_backward(dk_, normed[2], normed[3])
        dq_ref[0, :, keys] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, keys] = dk_.astype(dk_ref.dtype)


def _groups(key_heads: int, ratio: int, width: int = _LANES):
    """(key heads, value heads) a grid step takes: `_HEADS` value heads
    of `_LANES`, as many fewer as a wider head asks, or the largest half
    of that whose key heads divide the key heads' count (`ops/kda.py:
    _heads_a_step`, a key head as wide as its value heads together)."""
    keys = _heads_a_step(key_heads, width * ratio, _HEADS)
    return keys, keys * ratio


def _specs(chunks: int, keys: int, group: int, dk: int, dv: int,
           reverse: bool):
    """Block specs of a grid (batch, group of heads, step) by role: a
    chunk of a (B, L, H*D) operand's heads (`keys` key heads or `group`
    value heads), of the (B, H_v, N, 2, C) rows of g over b, and the (B,
    H_v, N, dv, dk) states; `reverse` walks the chunks from the last."""
    def at(n):
        return chunks - 1 - n if reverse else n

    def rows(count, dim):
        return pl.BlockSpec(
            (1, CHUNK, count * dim), lambda b, h, n: (b, at(n), h)
        )

    scalars = pl.BlockSpec(
        (1, group, 1, 2, CHUNK), lambda b, h, n: (b, h, at(n), 0, 0)
    )
    states = pl.BlockSpec(
        (1, group, 1, dv, dk), lambda b, h, n: (b, h, at(n), 0, 0)
    )
    return rows(keys, dk), rows(group, dv), scalars, states


def _flat(t):
    return t.reshape(*t.shape[:2], -1)


def _rows_of(g, beta):
    """g and beta (B, L, H_v) -> (B, H_v, N, 2, C) float32, g over b."""
    batch, length, heads = g.shape
    both = jnp.stack(
        [g.astype(jnp.float32), beta.astype(jnp.float32)], axis=-1
    )                                                   # (B, L, H, 2)
    return both.reshape(batch, length // CHUNK, CHUNK, heads, 2).transpose(
        0, 3, 1, 4, 2
    )


def _from_rows(rows, like):
    """(B, H_v, N, C) -> (B, L, H_v) in `like`'s type."""
    batch, heads = rows.shape[:2]
    return rows.reshape(batch, heads, -1).transpose(0, 2, 1).astype(
        like.dtype
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gdn(q, k, v, g, beta, qk_norm=None):
    return _gdn_fwd(q, k, v, g, beta, qk_norm)[0]


# Built once a shape (`ops/kda.py: _forward_call` says why).
@functools.lru_cache(maxsize=None)
def _forward_call(batch, length, key_heads, heads, dk, dv, dtype, qk_norm,
                  vma, interpret):
    chunks, ratio = length // CHUNK, heads // key_heads
    keys, group = _groups(key_heads, ratio, max(dk, dv))
    qk_rows, v_rows, scalars, states = _specs(
        chunks, keys, group, dk, dv, reverse=False
    )
    return _call(
        functools.partial(
            _fwd_kernel, dk=dk, dv=dv, ratio=ratio, qk_norm=qk_norm
        ),
        (batch, heads // group, chunks),
        [qk_rows, qk_rows, v_rows, scalars],
        [v_rows, states],
        [((batch, length, heads * dv), dtype),
         ((batch, heads, chunks, dv, dk), jnp.float32)],
        [pltpu.VMEM((group, dv, dk), jnp.float32)],
        vma, interpret, "gdn_chunk_fwd",
    )


@functools.lru_cache(maxsize=None)
def _backward_call(batch, length, key_heads, heads, dk, dv, dtypes, qk_norm,
                   vma, interpret):
    chunks, ratio = length // CHUNK, heads // key_heads
    keys, group = _groups(key_heads, ratio, max(dk, dv))
    qk_rows, v_rows, scalars, states = _specs(
        chunks, keys, group, dk, dv, reverse=True
    )
    return _call(
        functools.partial(
            _bwd_kernel, dk=dk, dv=dv, ratio=ratio, qk_norm=qk_norm
        ),
        (batch, heads // group, chunks),
        [qk_rows, qk_rows, v_rows, scalars, states, v_rows],
        [qk_rows, qk_rows, v_rows, scalars],
        [((batch, length, key_heads * dk), dtypes[0]),
         ((batch, length, key_heads * dk), dtypes[1]),
         ((batch, length, heads * dv), dtypes[2]),
         ((batch, heads, chunks, 2, CHUNK), jnp.float32)],
        [pltpu.VMEM((group, dv, dk), jnp.float32)],
        vma, interpret, "gdn_chunk_bwd",
    )


def _gdn_fwd(q, k, v, g, beta, qk_norm):
    batch, length, key_heads, dk = q.shape
    heads, dv = v.shape[2:]
    operands = [_flat(q), _flat(k), _flat(v), _rows_of(g, beta)]
    out, boundary = _forward_call(
        batch, length, key_heads, heads, dk, dv, jnp.dtype(q.dtype),
        qk_norm, _vma(operands), use_interpret(),
    )(*operands)
    out, boundary = (
        checkpoint_name(t, name)
        for t, name in zip((out, boundary), RESULT_NAMES)
    )
    return out.reshape(batch, length, heads, dv), (
        q, k, v, g, beta, boundary
    )


def _gdn_bwd(qk_norm, residuals, d_out):
    q, k, v, g, beta, boundary = residuals
    batch, length, key_heads, dk = q.shape
    heads, dv = v.shape[2:]
    operands = [_flat(q), _flat(k), _flat(v), _rows_of(g, beta), boundary,
                _flat(d_out.astype(q.dtype))]
    dq, dk_, dv_, dgb = _backward_call(
        batch, length, key_heads, heads, dk, dv,
        tuple(jnp.dtype(t.dtype) for t in (q, k, v)), qk_norm,
        _vma(operands), use_interpret(),
    )(*operands)
    return (
        dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
        _from_rows(dgb[:, :, :, 0], g), _from_rows(dgb[:, :, :, 1], beta),
    )


_gdn.defvjp(_gdn_fwd, _gdn_bwd)


def gdn(q, k, v, g, beta, qk_norm=None):
    """The gated delta rule of q, k (B, L, H_k, dk) and v (B, L, H_v, dv)
    in the stated type, H_v a whole multiple of H_k (value head h reads
    key head h // (H_v / H_k)), under the log-decay g (B, L, H_v) <= 0 and
    the write strength beta (B, L, H_v), both float32 -> o (B, L, H_v, dv)
    in q's type (module docstring): the Pallas kernels where the shapes
    tile (`gdn_shapes_ok`), the chunked `jnp` form elsewhere, which pads
    a length that is no whole number of chunks.  With `qk_norm` = (eps,
    q's scale), q and k are first L2-normalised a head, x / sqrt(|x|^2 +
    eps), and q scaled, in float32 INSIDE the op, once a key head.  Heads
    that are no whole lane tile wide reach the kernels padded to one and
    the output is sliced back (`_lane_padded`).  Nothing here bounds beta:
    at beta in (1, 2) the transition exp(g)(I - beta k k^T) has a negative
    eigenvalue along k, and the chunk's linear system is solved as it
    is."""
    from elasticdl_tpu.parallel.mesh import in_export_mode

    g = g.astype(jnp.float32)
    if qk_norm is not None:
        qk_norm = (float(qk_norm[0]), float(qk_norm[1]))
    if gdn_shapes_ok(q.shape, k.shape, v.shape) and not in_export_mode():
        out = _gdn(
            _lane_padded(q), _lane_padded(k), _lane_padded(v), g, beta,
            qk_norm,
        )
        return out[..., :v.shape[3]]
    return chunked_gdn(q, k, v, g, beta, qk_norm)
