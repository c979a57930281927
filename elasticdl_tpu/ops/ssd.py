"""The state-space scan with ONE scalar decay a head and token (Mamba-2's
token mixer: its state is carried along the whole sequence), chunked,
forward and backward:

    S_t = a_t S_{t-1} + dt_t x_t B_t^T       S in R^{P x N} a head, S_0 = 0
    y_t = S_t C_t + D x_t                    a_t = exp(A dt_t) in (0, 1)

x (P,) is a head's own, B_t and C_t (N,) are shared by every head of a
GROUP (all 64 where `n_groups` is 1), A < 0 and D are a scalar a head, dt
> 0 a scalar a head and token.

`ssd` is the one entry a model calls: two Pallas kernels where the shapes
tile (`ssd_shapes_ok`), named `ssd_fwd` and `ssd_bwd` so that a device
trace tells them from every other fusion, and `chunked_ssd`, the same
chunked mathematics in plain `jnp` under autodiff, elsewhere (the
arrangement of `ops/kda.py: kda`).

The chunked form (the state-space dual).  Inside a chunk of Q tokens, with
G_i the running sum of log a = A dt from the chunk's first token to token
i (float32, every exponent below is <= 0) and S the state the chunk
starts from:

    M_ij = (C_i . B_j) exp(G_i - G_j)  (j <= i)     C B^T ONCE a chunk for
    Y    = M (dt * X) + e^G * (C S^T) + D X         all heads of a group
    S'   = e^{G_Q} S + ((e^{G_Q - G} dt) * X)^T B

Nothing is solved and nothing is per channel: beside `ops/kda.py`, whose
decay is a vector a head, whose chunk ends in a triangular solve and
whose q and k are a head's own, this is one masked product a head and
three products ALL heads share an operand of.

Kernel shape: the grid walks (batch, chunk, lane tile), the chunk axis
sequential and the lane tiles inside it, a GROUP's tiles in a row, so that
C B^T is formed ONCE a chunk and group (at the group's first tiles, into
scratch) and dB and dC, which are sums over the heads of a group, are
summed in scratch over the group's tiles and written at its last into the
group's columns.  Operands stay (B, L, H*P) and (B, L, G*N) (the free
views of the model's layout); a program takes `_TILES` lane tiles of them
(two heads of 64 a tile) and its group's N columns of B and C, each head's
masked product taken against its whole tile and kept in its own lanes.
The tiles a program takes must divide the tiles of a group
(`ssd_shapes_ok`): eight groups of eight heads of 64 are four tiles each,
and grid step t IS group t.  The tiles as a grid axis, not one loop in the
body, keep the kernel's code small (all 32 unrolled were 52 MB of the
step's executable, a quarter of the machine's compile cache).  The states
of all heads live in one (tiles, 128, N) float32 scratch, and the forward
writes the state each chunk starts from.  The backward walks the chunks
from the last to the first with the state's gradient in scratch and
rebuilds a chunk from its inputs and its boundary state.  The running
sums G are taken OUTSIDE the kernels (a cumulative sum over (B, L, H)
float32: nothing beside x) and come in both layouts, tokens down and
tokens across, all heads' a chunk, so that neither kernel transposes (a
tile picks its heads' columns by a masked lane sum and their rows by a
dynamic sublane index); their gradient leaves in both and is summed
outside.

The forward saves nothing by name (`SAVED_NAMES`): a block's remat runs
the forward kernel again for the boundary states, as `ops/kda.py` says of
its own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops.flash_attention import use_interpret

_LANES = 128
# Tokens of a chunk: the published kernel's tiling (`mamba_chunk_size`).
CHUNK = 256
# Lane tiles a grid step takes (where the tile count divides).  On the chip
# at the granite cell's shape, the scan's kernels a train step (nine
# layers, two forwards and a backward each) | the step's executable in the
# compile cache | the cell's cold `setup_s` (PERF.md section 6, PR 46): all
# 32 tiles unrolled in one program 20.5 ms | 52.6 MB | 115 s; one tile a
# program 27.8 ms | 34.1 MB | 93 s (a grid step costs ~0.27 us).
_TILES = 4
# the states of all heads, two (Q, Q) and two (Q, N) accumulators, the
# tile's blocks double-buffered
_VMEM_LIMIT = 64 * 1024 * 1024
# What a block's remat keeps from the forward, by name: nothing.
SAVED_NAMES = ()


def ssd_shapes_ok(x_shape, b_shape, chunk: int = CHUNK) -> bool:
    """Whether the kernels take x (B, L, H, P) under B and C (B, L, G, N):
    heads that fill whole lane tiles (a head half a tile or a whole one),
    groups whose heads fill whole grid steps (the lane tiles a step takes
    divide the tiles of a group), state columns of whole lane tiles, whole
    chunks."""
    if len(x_shape) != 4 or len(b_shape) != 4:
        return False
    _, length, heads, dim = x_shape
    groups = b_shape[2]
    if groups < 1 or heads % groups or (heads * dim) % _LANES:
        return False
    step = _tiles_a_step(heads * dim // _LANES) * _LANES
    return (
        tuple(b_shape[:2]) == tuple(x_shape[:2])
        and dim <= _LANES and _LANES % dim == 0 and dim % 8 == 0
        and (heads // groups * dim) % step == 0
        and b_shape[3] % _LANES == 0
        and chunk % _LANES == 0 and length % chunk == 0
    )


# ---- the plain chunked form ------------------------------------------------


def _jnp_chunk(state, chunk, A, D, dtype):
    """One chunk of every (batch, group, head of the group): x (b, Q, g, r,
    P), dt (b, Q, g, r), B and C (b, Q, g, N); state (b, g, r, P, N) ->
    (state, y)."""
    x, dt, B, C = chunk
    x = x.astype(jnp.float32)
    size = x.shape[1]
    G = jnp.cumsum(A * dt, axis=1)                      # (b, Q, g, r)
    rows = jnp.arange(size)
    seen = rows[:, None] >= rows[None, :]
    decay = jnp.exp(jnp.where(
        seen[None, :, :, None, None], G[:, :, None] - G[:, None], -jnp.inf
    ))                                                  # (b, i, j, g, r)
    cb = jnp.einsum(
        "bign,bjgn->bijg", C.astype(dtype), B.astype(dtype),
        preferred_element_type=jnp.float32,
    )
    xd = dt[..., None] * x
    y = jnp.einsum(
        "bijgr,bjgrp->bigrp", (cb[..., None] * decay).astype(dtype),
        xd.astype(dtype), preferred_element_type=jnp.float32,
    )
    y = y + jnp.exp(G)[..., None] * jnp.einsum(
        "bign,bgrpn->bigrp", C.astype(dtype), state.astype(dtype),
        preferred_element_type=jnp.float32,
    )
    last = G[:, -1:]
    state = state * jnp.exp(last[:, 0])[..., None, None] + jnp.einsum(
        "bjgrp,bjgn->bgrpn", (jnp.exp(last - G)[..., None] * xd).astype(dtype),
        B.astype(dtype), preferred_element_type=jnp.float32,
    )
    return state, y + D[..., None] * x


def chunked_ssd(x, dt, A, B, C, D, chunk: int = CHUNK):
    """The plain form: a `lax.scan` over chunks of `chunk` tokens, every
    (batch, head) at once, each chunk rebuilt in the backward from the
    state it starts from.  x (B, L, H, P) in the stated type, dt (B, L, H)
    float32, A and D (H,) float32, B and C (B, L, G, N), H a multiple of
    G.  A length that is no whole number of chunks is padded with tokens
    that leave the state as it is (dt = 0) and whose outputs are
    dropped."""
    batch, length, heads, dim = x.shape
    groups, columns = B.shape[2:]
    dtype = x.dtype
    each = heads // groups
    pad = -length % chunk
    if pad:
        x, B, C = (
            jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0))) for t in (x, B, C)
        )
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))

    def chunks(t, *tail):
        """(B, L, ...) -> (chunks, B, chunk, ...)."""
        return jnp.moveaxis(t.reshape(batch, -1, chunk, *tail), 1, 0)

    step = jax.checkpoint(functools.partial(
        _jnp_chunk, A=A.astype(jnp.float32).reshape(groups, each),
        D=D.astype(jnp.float32).reshape(groups, each), dtype=dtype,
    ))
    _, out = jax.lax.scan(
        step, jnp.zeros((batch, groups, each, dim, columns), jnp.float32),
        (chunks(x, groups, each, dim),
         chunks(dt.astype(jnp.float32), groups, each),
         chunks(B, groups, columns), chunks(C, groups, columns)),
    )
    out = jnp.moveaxis(out, 0, 1).reshape(batch, length + pad, heads, dim)
    return out[:, :length].astype(dtype)


# ---- the kernels -----------------------------------------------------------


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _dot(a, b, contract, dtype):
    return jax.lax.dot_general(
        a.astype(dtype), b.astype(dtype), (contract, ((), ())),
        preferred_element_type=jnp.float32,
    )


_ROWS, _COLS, _ROW_COL = ((0,), (0,)), ((1,), (1,)), ((1,), (0,))


class _Tile:
    """The heads of one lane tile: `per` heads of `dim` lanes each."""

    def __init__(self, dim: int, size: int):
        self.per = _LANES // dim
        self.dim = dim
        self.lane = _iota((size, _LANES), 1)
        self.row = _iota((_LANES, 1), 0)

    def own(self, r: int, lane=None):
        """The lanes (rows, with `lane` = self.row) of the tile's head r."""
        lane = self.lane if lane is None else lane
        return (lane >= r * self.dim) & (lane < (r + 1) * self.dim)

    def mine(self, r: int, values):
        """`values` (Q, 128) in head r's lanes, zero in the others."""
        return values if self.per == 1 else jnp.where(
            self.own(r), values, 0.0
        )

    def across(self, columns):
        """[(Q, 1) a head] -> (Q, 128): each lane its head's."""
        out = columns[0]
        for r in range(1, self.per):
            out = jnp.where(self.own(r), columns[r], out)
        return out

    def down(self, scalars):
        """[(1, 1) a head] -> (128, 1): each of the state's rows its
        head's."""
        out = scalars[0]
        for r in range(1, self.per):
            out = jnp.where(self.own(r, self.row), scalars[r], out)
        return out

    def heads(self, tile_id):
        """The numbers of the tile's heads."""
        return [tile_id * self.per + r for r in range(self.per)]

    def of_head(self, r: int, values):
        """The sum over head r's lanes of (Q, 128) -> (Q, 1)."""
        return self.mine(r, values).sum(axis=1, keepdims=True)


def _masked_product(c_ref, b_ref, dtype):
    """C B^T of the chunk, zero above the diagonal: every head of the
    group reads it."""
    cb = _dot(c_ref[0], b_ref[0], _COLS, dtype)
    return jnp.where(_iota(cb.shape, 0) >= _iota(cb.shape, 1), cb, 0.0)


def _column(by_head, h):
    """Head h (traced) of (Q, H) a token and head -> (Q, 1)."""
    return jnp.where(_iota(by_head.shape, 1) == h, by_head, 0.0).sum(
        axis=1, keepdims=True
    )


def _decay(down, across):
    """exp(G_i - G_j) of one head from its G tokens down (Q, 1) and
    tokens across (1, Q); 1 above the diagonal (masked by C B^T)."""
    return jnp.exp(jnp.minimum(down - across, 0.0))


def _place(per: int, steps: int):
    """The grid step's place among the `per` steps of its group of heads
    (the step itself where one group has all `steps`)."""
    step = pl.program_id(2)
    return step if per == steps else step % per


def _fwd_kernel(x_ref, dt_ref, g_ref, gt_ref, b_ref, c_ref, d_ref,
                y_ref, states_ref, state_sc, cb_sc, *, dim: int, group: int,
                per: int, steps: int):
    dtype = x_ref.dtype

    @pl.when(_place(per, steps) == 0)
    def _():
        cb_sc[...] = _masked_product(c_ref, b_ref, dtype)

    size = x_ref.shape[1]
    tile = _Tile(dim, size)
    cb = cb_sc[...]
    for k in range(group):
        tile_id = pl.program_id(2) * group + k
        lanes = slice(k * _LANES, (k + 1) * _LANES)

        @pl.when(pl.program_id(1) == 0)
        def _():
            state_sc[tile_id] = jnp.zeros(state_sc.shape[1:], jnp.float32)

        heads = tile.heads(tile_id)
        G = [_column(g_ref[0], h) for h in heads]
        x = x_ref[0, :, lanes].astype(jnp.float32)
        state = state_sc[tile_id]
        states_ref[0, 0, lanes, :] = state
        G_l = tile.across(G)
        xd = tile.across([_column(dt_ref[0], h) for h in heads]) * x
        y = jnp.exp(G_l) * _dot(c_ref[0], state, _COLS, dtype)
        for r, h in enumerate(heads):
            decay = _decay(G[r], gt_ref[0, pl.ds(h, 1), :])
            y = y + tile.mine(r, _dot(cb * decay, xd, _ROW_COL, dtype))
        y_ref[0, :, lanes] = (y + d_ref[:, lanes] * x).astype(y_ref.dtype)
        state_sc[tile_id] = (
            state * jnp.exp(tile.down([g[size - 1:size] for g in G]))
            + _dot(
                jnp.exp(G_l[size - 1:size] - G_l) * xd, b_ref[0], _ROWS, dtype
            )
        )


def _bwd_kernel(x_ref, dt_ref, g_ref, gt_ref, b_ref, c_ref, d_ref,
                states_ref, dy_ref, dx_ref, ddt_ref, dg_ref, dgt_ref,
                db_ref, dc_ref, dd_ref, d_state_sc, cb_sc, d_cb_sc, db_sc,
                dc_sc, *, dim: int, group: int, per: int, steps: int):
    dtype = x_ref.dtype

    def chunk_starts():
        # all heads' a chunk: summed over every step of it
        ddt_ref[...] = jnp.zeros(ddt_ref.shape, jnp.float32)
        dg_ref[...] = jnp.zeros(dg_ref.shape, jnp.float32)

    @pl.when(_place(per, steps) == 0)
    def _():
        cb_sc[...] = _masked_product(c_ref, b_ref, dtype)
        d_cb_sc[...] = jnp.zeros(d_cb_sc.shape, jnp.float32)
        db_sc[...] = jnp.zeros(db_sc.shape, jnp.float32)
        dc_sc[...] = jnp.zeros(dc_sc.shape, jnp.float32)
        if per == steps:
            chunk_starts()

    if per != steps:
        pl.when(pl.program_id(2) == 0)(chunk_starts)

    size = x_ref.shape[1]
    tile = _Tile(dim, size)
    cb = cb_sc[...]
    B, C = b_ref[0], c_ref[0]
    head_lane = _iota(dg_ref.shape[1:], 1)
    at_last = _iota((1, size), 1) == size - 1
    for k in range(group):
        tile_id = pl.program_id(2) * group + k
        lanes = slice(k * _LANES, (k + 1) * _LANES)

        @pl.when(pl.program_id(1) == 0)
        def _():
            d_state_sc[tile_id] = jnp.zeros(
                d_state_sc.shape[1:], jnp.float32
            )

        heads = tile.heads(tile_id)
        G = [_column(g_ref[0], h) for h in heads]
        x = x_ref[0, :, lanes].astype(jnp.float32)
        dy = dy_ref[0, :, lanes].astype(jnp.float32)
        state = states_ref[0, 0, lanes, :]
        d_next = d_state_sc[tile_id]
        G_l = tile.across(G)
        dt_l = tile.across([_column(dt_ref[0], h) for h in heads])
        xd = dt_l * x
        e_l = jnp.exp(G_l)
        w_l = jnp.exp(G_l[size - 1:size] - G_l)
        keep = jnp.exp(tile.down([g[size - 1:size] for g in G]))  # (128, 1)
        # Y = M (dt X) + e^G (C S^T) + D X;  S' = e^{G_Q} S + (w dt X)^T B
        dye = e_l * dy
        read = dye * _dot(C, state, _COLS, dtype)
        dc_sc[...] += _dot(dye, state, _ROW_COL, dtype)
        reach = _dot(B, d_next, _COLS, dtype)
        dxd = w_l * reach
        wx = w_l * xd
        db_sc[...] += _dot(wx, d_next, _ROW_COL, dtype)
        written = wx * reach
        d_state_sc[tile_id] = keep * d_next + _dot(dye, C, _ROWS, dtype)
        kept = (keep * state * d_next).sum(axis=1, keepdims=True)  # (128, 1)
        d_cb = jnp.zeros((size, size), jnp.float32)
        dG = jnp.zeros(head_lane.shape, jnp.float32)
        for r, h in enumerate(heads):
            decay = _decay(G[r], gt_ref[0, pl.ds(h, 1), :])
            m = cb * decay
            d_m = _dot(tile.mine(r, dy), xd, _COLS, dtype)
            dxd = dxd + tile.mine(r, _dot(m, dy, _ROWS, dtype))
            d_cb = d_cb + d_m * decay
            g_m = d_m * m
            wrote = tile.of_head(r, written)
            dG = dG + jnp.where(
                head_lane == h,
                g_m.sum(axis=1, keepdims=True) + tile.of_head(r, read)
                - wrote,
                0.0,
            )
            # every G of the chunk reaches G_Q: through w and e^{G_Q}
            to_last = wrote.sum(axis=0, keepdims=True) + jnp.where(
                tile.own(r, tile.row), kept, 0.0
            ).sum(axis=0, keepdims=True)
            dgt_ref[0, pl.ds(h, 1), :] = (
                jnp.where(at_last, to_last, 0.0)
                - g_m.sum(axis=0, keepdims=True)
            )
        d_cb_sc[...] += d_cb
        ddt = jnp.zeros(head_lane.shape, jnp.float32)
        for r, h in enumerate(heads):
            ddt = ddt + jnp.where(
                head_lane == h, tile.of_head(r, dxd * x), 0.0
            )
        ddt_ref[0] += ddt
        dg_ref[0] += dG
        dx_ref[0, :, lanes] = (dt_l * dxd + d_ref[:, lanes] * dy).astype(
            dx_ref.dtype
        )
        dd_ref[0, 0, :, lanes] = (dy * x).sum(axis=0, keepdims=True)

    @pl.when(_place(per, steps) == per - 1)
    def _():
        # the decay is 1 above the diagonal, where C B^T is not read
        total = d_cb_sc[...]
        total = jnp.where(
            _iota(total.shape, 0) >= _iota(total.shape, 1), total, 0.0
        )
        db_ref[0] = (
            db_sc[...] + _dot(total, C, _ROWS, dtype)
        ).astype(db_ref.dtype)
        dc_ref[0] = (
            dc_sc[...] + _dot(total, B, _ROW_COL, dtype)
        ).astype(dc_ref.dtype)


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
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret, name=name,
    )


def _vma(operands):
    return frozenset().union(*(jax.typeof(x).vma for x in operands))


def _tiles_a_step(tiles: int) -> int:
    return _TILES if tiles % _TILES == 0 else 1


def _specs(chunks: int, chunk: int, heads: int, columns: int, wide: int,
           per: int, steps: int, reverse: bool):
    """Block specs of a grid (batch, chunk step, step of lane tiles) by
    role, `wide` the step's lanes: a chunk of the step's lanes of a (B,
    L, H*P) operand, of its group's N columns of a (B, L, G*N) one (a
    group has `per` of the `steps`; one group has them all), of ALL
    heads' scalars with tokens down (B, L, H) and across (B, H, L) (they
    stay in place over a chunk's tiles, which pick their
    heads' columns and rows), the tile's rows of the (B, chunks, H*P, N)
    states, its lanes of the (1, H*P) skip and of the (B, chunks, 1, H*P)
    partial of its gradient; `reverse` walks the chunks from the last."""
    def at(n):
        return chunks - 1 - n if reverse else n

    return dict(
        lanes=pl.BlockSpec((1, chunk, wide), lambda b, n, t: (b, at(n), t)),
        shared=pl.BlockSpec(
            (1, chunk, columns),
            lambda b, n, t: (b, at(n), 0 if per == steps else t // per),
        ),
        down=pl.BlockSpec((1, chunk, heads), lambda b, n, t: (b, at(n), 0)),
        across=pl.BlockSpec((1, heads, chunk), lambda b, n, t: (b, 0, at(n))),
        states=pl.BlockSpec(
            (1, 1, wide, columns), lambda b, n, t: (b, at(n), t, 0)
        ),
        skip=pl.BlockSpec((1, wide), lambda b, n, t: (0, t)),
        partial=pl.BlockSpec(
            (1, 1, 1, wide), lambda b, n, t: (b, at(n), 0, t)
        ),
    )


# A layer's call of a kernel is the call of every layer of that shape: the
# callable is built once a shape, so jax traces the kernel's body once a
# process and not once a layer and pass (`ops/kda.py: _forward_call`).
@functools.lru_cache(maxsize=None)
def _forward_call(batch, length, heads, dim, columns, groups, chunk, dtype,
                  vma, interpret):
    chunks, width = length // chunk, heads * dim
    tiles = width // _LANES
    group = _tiles_a_step(tiles)
    steps = tiles // group
    per = steps // groups
    spec = _specs(
        chunks, chunk, heads, columns, group * _LANES, per, steps,
        reverse=False,
    )
    return _call(
        functools.partial(
            _fwd_kernel, dim=dim, group=group, per=per, steps=steps
        ),
        (batch, chunks, steps),
        [spec["lanes"], spec["down"], spec["down"], spec["across"],
         spec["shared"], spec["shared"], spec["skip"]],
        [spec["lanes"], spec["states"]],
        [((batch, length, width), dtype),
         ((batch, chunks, width, columns), jnp.float32)],
        [pltpu.VMEM((tiles, _LANES, columns), jnp.float32),
         pltpu.VMEM((chunk, chunk), jnp.float32)],
        vma, interpret, "ssd_fwd",
    )


@functools.lru_cache(maxsize=None)
def _backward_call(batch, length, heads, dim, columns, groups, chunk, dtypes,
                   vma, interpret):
    chunks, width = length // chunk, heads * dim
    tiles = width // _LANES
    group = _tiles_a_step(tiles)
    steps = tiles // group
    per = steps // groups
    spec = _specs(
        chunks, chunk, heads, columns, group * _LANES, per, steps,
        reverse=True,
    )
    down = ((batch, length, heads), jnp.float32)
    return _call(
        functools.partial(
            _bwd_kernel, dim=dim, group=group, per=per, steps=steps
        ),
        (batch, chunks, steps),
        [spec["lanes"], spec["down"], spec["down"], spec["across"],
         spec["shared"], spec["shared"], spec["skip"], spec["states"],
         spec["lanes"]],
        [spec["lanes"], spec["down"], spec["down"], spec["across"],
         spec["shared"], spec["shared"], spec["partial"]],
        [((batch, length, width), dtypes[0]), down, down,
         ((batch, heads, length), jnp.float32),
         ((batch, length, groups * columns), dtypes[1]),
         ((batch, length, groups * columns), dtypes[2]),
         ((batch, chunks, 1, width), jnp.float32)],
        [pltpu.VMEM((tiles, _LANES, columns), jnp.float32),
         pltpu.VMEM((chunk, chunk), jnp.float32),
         pltpu.VMEM((chunk, chunk), jnp.float32),
         pltpu.VMEM((chunk, columns), jnp.float32),
         pltpu.VMEM((chunk, columns), jnp.float32)],
        vma, interpret, "ssd_bwd",
    )


def _running_sums(dt, A, chunk: int):
    """G (B, L, H): the sums of A dt from each chunk's first token on."""
    batch, length, heads = dt.shape
    return jnp.cumsum(
        (A * dt).reshape(batch, length // chunk, chunk, heads), axis=2
    ).reshape(batch, length, heads)


def _by_column(t):
    """B or C (B, L, G, N) as the kernels read it, (B, L, G*N): a free
    view (of one group the slice it was, so that a one-group call traces
    to what it traced to before groups entered)."""
    if t.shape[2] == 1:
        return t[:, :, 0]
    return t.reshape(*t.shape[:2], -1)


def _by_group(t, shape):
    """`_by_column`'s way back."""
    return t[:, :, None] if shape[2] == 1 else t.reshape(shape)


def _operands(x, dt, A, B, C, D, chunk):
    batch, length, heads, dim = x.shape
    G = _running_sums(dt, A, chunk)
    return [
        x.reshape(batch, length, heads * dim), dt, G, G.transpose(0, 2, 1),
        _by_column(B), _by_column(C), jnp.repeat(D, dim)[None],
    ]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd(x, dt, A, B, C, D, chunk=CHUNK):
    return _ssd_fwd(x, dt, A, B, C, D, chunk)[0]


def _ssd_fwd(x, dt, A, B, C, D, chunk):
    batch, length, heads, dim = x.shape
    operands = _operands(x, dt, A, B, C, D, chunk)
    out, boundary = _forward_call(
        batch, length, heads, dim, B.shape[3], B.shape[2], chunk,
        jnp.dtype(x.dtype), _vma(operands), use_interpret(),
    )(*operands)
    return out.reshape(x.shape), (x, dt, A, B, C, D, boundary)


def _ssd_bwd(chunk, residuals, d_out):
    x, dt, A, B, C, D, boundary = residuals
    batch, length, heads, dim = x.shape
    operands = _operands(x, dt, A, B, C, D, chunk) + [
        boundary,
        d_out.astype(x.dtype).reshape(batch, length, heads * dim),
    ]
    dx, ddt, dG, dG_t, dB, dC, dD = _backward_call(
        batch, length, heads, dim, B.shape[3], B.shape[2], chunk,
        tuple(jnp.dtype(t.dtype) for t in (x, B, C)), _vma(operands),
        use_interpret(),
    )(*operands)
    # G is a running sum inside its chunk: log a's gradient is the sum of
    # G's from its token to the chunk's last
    dG = (dG + dG_t.transpose(0, 2, 1)).reshape(
        batch, length // chunk, chunk, heads
    )
    d_log = jnp.flip(
        jnp.cumsum(jnp.flip(dG, axis=2), axis=2), axis=2
    ).reshape(batch, length, heads)
    return (
        dx.reshape(x.shape), ddt + A * d_log,
        (d_log * dt).sum(axis=(0, 1)).astype(A.dtype),
        _by_group(dB, B.shape), _by_group(dC, C.shape),
        dD.reshape(-1, heads, dim).sum(axis=(0, 2)).astype(D.dtype),
    )


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(x, dt, A, B, C, D):
    """The scalar-decay state-space scan of x (B, L, H, P) in the stated
    type under the step dt (B, L, H) > 0, the rate A (H,) < 0 and the skip
    D (H,), all float32, with B and C (B, L, G, N) in the stated type
    shared by the H / G heads of a group -> y (B, L, H, P) in x's type
    (module docstring): the Pallas kernels where the shapes tile
    (`ssd_shapes_ok`), the chunked `jnp` form elsewhere, which pads a
    length that is no whole number of chunks."""
    from elasticdl_tpu.parallel.mesh import in_export_mode

    dt, A, D = (t.astype(jnp.float32) for t in (dt, A, D))
    if ssd_shapes_ok(x.shape, B.shape) and not in_export_mode():
        return _ssd(x, dt, A, B, C, D)
    return chunked_ssd(x, dt, A, B, C, D)
