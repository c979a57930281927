"""The gated short convolution of a conv/attention hybrid decoder (LFM2's
`conv` layers): between the operator's two projections,

    [B, C, u] = split3(bcu)                          bcu: (batch, L, 3d)
    z_t = sum_{k=0..K-1} w_k * (B * u)_{t-(K-1)+k}   w: (K, d), depthwise,
                                                     zeros left of t = 0
    y   = C * z                                      (batch, L, d)

a pass over four streams of tokens x d that the memory bounds, not the
MXU.  `gated_short_conv` is the one entry a model calls: two Pallas
kernels where the shapes tile (`short_conv_shapes_ok`), named
`short_conv_fwd` and `short_conv_bwd` so that a device trace tells them
from every other fusion, and `shifted_short_conv`, the same mathematics
as K shifted multiply-adds in plain `jnp`, elsewhere (the arrangement of
`ops/flash_attention.py: causal_attention`).

`silu_short_conv` is the UNGATED form a linear-attention or state-space
layer puts after its input projections, `y = silu(conv_K(u) + b)` over all
the columns of u at once, the bias b (d,) optional (`silu_short_conv_fwd`
/ `silu_short_conv_bwd`, the shifted form `shifted_silu_conv` elsewhere):
its columns are independent, so its grid also walks blocks of columns, and
its backward rebuilds z in the tile and in the `_HALO` rows after it
(whose taps reach back into the tile) for silu's slope; d(bias), a column
sum of dz, leaves in the row after the taps' of the d(weight) partial.

Kernel shape: the grid walks (batch, L / tile); a program holds `tile`
whole rows of `bcu` (all 3d columns: one contiguous read) and the
`_HALO` rows before them, from which the K - 1 rows left of the tile
come, and works a block of columns at a time in float32.  Forward reads
the three streams once and writes y once.  Backward saves nothing
(batch, L, d)-shaped beyond `bcu` itself: it rebuilds z for dC, reads
the `_HALO` rows AFTER the tile of g and C for the taps that reach
forward, writes d(bcu) whole rows at a time, and leaves d(weight) as one
(K, d) partial a program, summed outside.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops.flash_attention import use_interpret

# Rows a program reads beside its tile: one bfloat16 sublane tile, the
# least a block may hold.
_HALO = 16
_LANES = 128
# Rows of a program's d(weight) partial, one float32 sublane tile: the
# most taps a kernel takes (they reach K - 1 < _HALO rows).
_PARTIAL_ROWS = 8
# the tile's blocks are whole rows of 3d columns, double-buffered
_VMEM_LIMIT = 64 * 1024 * 1024


def _tile(length: int):
    for cand in (256, 128, 64, 32, 16):
        if length % cand == 0:
            return cand
    return None


def _columns(width: int) -> int:
    for cand in (512, 256):
        if width % cand == 0:
            return cand
    return _LANES


def short_conv_shapes_ok(bcu_shape, weight_shape) -> bool:
    """Whether the kernels take (batch, L, 3d) under a (K, d) kernel: L
    whole halo tiles, d whole lane tiles, at most `_PARTIAL_ROWS` taps."""
    taps, width = weight_shape
    return (
        len(bcu_shape) == 3 and bcu_shape[2] == 3 * width
        and width % _LANES == 0
        and _tile(bcu_shape[1]) is not None
        and 1 <= taps <= _PARTIAL_ROWS
    )


def shifted_short_conv(bcu, weight):
    """The plain form: K shifted multiply-adds, float32 inside."""
    taps = weight.shape[0]
    length = bcu.shape[1]
    b, c, u = jnp.split(bcu.astype(jnp.float32), 3, axis=-1)
    x = jnp.pad(b * u, ((0, 0), (taps - 1, 0), (0, 0)))
    z = sum(
        weight[k].astype(jnp.float32) * x[:, k:k + length]
        for k in range(taps)
    )
    return (c * z).astype(bcu.dtype)


def _rows(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0)


def _shift_down(x, before, s: int):
    """x[t - s] at row t; the rows left of the tile come from the last
    rows of `before` (`_HALO` rows)."""
    if s == 0:
        return x
    main = pltpu.roll(x, s, 0)
    top = jnp.where(
        _rows(before.shape) >= s, main[:_HALO], pltpu.roll(before, s, 0)
    )
    if x.shape[0] == _HALO:
        return top
    return jnp.concatenate([top, main[_HALO:]], axis=0)


def _shift_up(x, after, s: int):
    """x[t + s] at row t; the rows right of the tile come from the first
    rows of `after` (`_HALO` rows)."""
    if s == 0:
        return x
    tile = x.shape[0]
    main = pltpu.roll(x, tile - s, 0)
    bottom = jnp.where(
        _rows(after.shape) < _HALO - s, main[tile - _HALO:],
        pltpu.roll(after, _HALO - s, 0),
    )
    if tile == _HALO:
        return bottom
    return jnp.concatenate([main[:tile - _HALO], bottom], axis=0)


def _third(ref, part: int, width: int, lo: int, block: int):
    """Columns [lo, lo + block) of third `part` (B, C, u = 0, 1, 2) of a
    block of rows, in float32; `part` 0 also reads a d-wide operand."""
    lo = part * width + lo
    return ref[0, :, lo:lo + block].astype(jnp.float32)


def _gated(ref, width, lo, block):
    """B * u: what the taps read."""
    return _third(ref, 0, width, lo, block) * _third(ref, 2, width, lo, block)


def _fwd_kernel(bcu_ref, before_ref, w_ref, y_ref, *, width: int,
                block: int, taps: int):
    first = pl.program_id(1) == 0
    for lo in range(0, width, block):
        x = _gated(bcu_ref, width, lo, block)
        before = jnp.where(first, 0.0, _gated(before_ref, width, lo, block))
        # tap k reads x shifted K - 1 - k rows down
        z = sum(
            w_ref[k:k + 1, lo:lo + block].astype(jnp.float32)
            * _shift_down(x, before, taps - 1 - k)
            for k in range(taps)
        )
        y_ref[0, :, lo:lo + block] = (
            _third(bcu_ref, 1, width, lo, block) * z
        ).astype(y_ref.dtype)


def _bwd_kernel(bcu_ref, before_ref, after_ref, g_ref, g_after_ref, w_ref,
                dbcu_ref, dw_ref, *, width: int, block: int, taps: int,
                tiles: int):
    first = pl.program_id(1) == 0
    last = pl.program_id(1) == tiles - 1
    for lo in range(0, width, block):
        b = _third(bcu_ref, 0, width, lo, block)
        c = _third(bcu_ref, 1, width, lo, block)
        u = _third(bcu_ref, 2, width, lo, block)
        g = _third(g_ref, 0, width, lo, block)
        x = b * u
        before = jnp.where(first, 0.0, _gated(before_ref, width, lo, block))
        dz = g * c
        dz_after = jnp.where(
            last, 0.0, _third(g_after_ref, 0, width, lo, block)
            * _third(after_ref, 1, width, lo, block),
        )
        z, dx = 0.0, 0.0
        for k in range(taps):
            s = taps - 1 - k
            w = w_ref[k:k + 1, lo:lo + block].astype(jnp.float32)
            shifted = _shift_down(x, before, s)
            z = z + w * shifted
            dx = dx + w * _shift_up(dz, dz_after, s)
            dw_ref[0, 0, k:k + 1, lo:lo + block] = (dz * shifted).sum(
                axis=0, keepdims=True
            )
        if taps < _PARTIAL_ROWS:
            dw_ref[0, 0, taps:, lo:lo + block] = jnp.zeros(
                (_PARTIAL_ROWS - taps, block), jnp.float32
            )
        for part, grad in enumerate((dx * u, g * z, dx * b)):
            at = part * width + lo
            dbcu_ref[0, :, at:at + block] = grad.astype(dbcu_ref.dtype)


def _call(kernel, grid, in_specs, out_specs, out_shape, operands, name):
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct(shape, dtype, vma=vma)
            for shape, dtype in out_shape
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * len(grid),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=use_interpret(), name=name,
    )(*operands)


def _specs(tile: int, tiles: int):
    """Block specs of a (batch, L, columns) operand by role: the
    program's own rows, the halo before them (tile 0's is masked in the
    kernel) and the halo after them (the last tile's is masked)."""
    per_tile = tile // _HALO

    def own(columns):
        return pl.BlockSpec((1, tile, columns), lambda b, i: (b, i, 0))

    def before(columns):
        return pl.BlockSpec(
            (1, _HALO, columns),
            lambda b, i: (b, jnp.maximum(i * per_tile - 1, 0), 0),
        )

    def after(columns):
        return pl.BlockSpec(
            (1, _HALO, columns),
            lambda b, i: (
                b, jnp.minimum((i + 1) * per_tile, tiles * per_tile - 1), 0
            ),
        )

    return own, before, after


@jax.custom_vjp
def _short_conv(bcu, weight):
    return _short_conv_fwd(bcu, weight)[0]


def _short_conv_fwd(bcu, weight):
    batch, length, wide = bcu.shape
    taps, width = weight.shape
    tile = _tile(length)
    tiles = length // tile
    own, before, _ = _specs(tile, tiles)
    (y,) = _call(
        functools.partial(
            _fwd_kernel, width=width, block=_columns(width), taps=taps
        ),
        (batch, tiles),
        [own(wide), before(wide),
         pl.BlockSpec((taps, width), lambda b, i: (0, 0))],
        [own(width)],
        [((batch, length, width), bcu.dtype)],
        [bcu, bcu, weight], "short_conv_fwd",
    )
    return y, (bcu, weight)


def _short_conv_bwd(residuals, g):
    bcu, weight = residuals
    batch, length, wide = bcu.shape
    taps, width = weight.shape
    tile = _tile(length)
    tiles = length // tile
    own, before, after = _specs(tile, tiles)
    g = g.astype(bcu.dtype)
    dbcu, partials = _call(
        functools.partial(
            _bwd_kernel, width=width, block=_columns(width), taps=taps,
            tiles=tiles,
        ),
        (batch, tiles),
        [own(wide), before(wide), after(wide), own(width), after(width),
         pl.BlockSpec((taps, width), lambda b, i: (0, 0))],
        [own(wide),
         pl.BlockSpec(
             (1, 1, _PARTIAL_ROWS, width), lambda b, i: (b, i, 0, 0)
         )],
        [((batch, length, wide), bcu.dtype),
         ((batch, tiles, _PARTIAL_ROWS, width), jnp.float32)],
        [bcu, bcu, bcu, g, g, weight], "short_conv_bwd",
    )
    return dbcu, partials.sum(axis=(0, 1))[:taps].astype(weight.dtype)


_short_conv.defvjp(_short_conv_fwd, _short_conv_bwd)


# ---- the ungated form: silu(conv_K(u)) ------------------------------------

# columns of a program's block
_SILU_COLUMNS = 512


def silu_conv_shapes_ok(u_shape, weight_shape, biased: bool = False) -> bool:
    """Whether the kernels take (batch, L, d) under a (K, d) kernel; a
    bias wants a row of the d(weight) partial after the taps'."""
    taps, width = weight_shape
    return (
        len(u_shape) == 3 and u_shape[2] == width
        and width % _LANES == 0
        and _tile(u_shape[1]) is not None
        and 1 <= taps <= _PARTIAL_ROWS - biased
    )


def shifted_silu_conv(u, weight, bias=None):
    """The plain form: K shifted multiply-adds, float32 inside."""
    taps = weight.shape[0]
    length = u.shape[1]
    x = jnp.pad(u.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    z = sum(
        weight[k].astype(jnp.float32) * x[:, k:k + length]
        for k in range(taps)
    )
    if bias is not None:
        z = z + bias.astype(jnp.float32)
    return jax.nn.silu(z).astype(u.dtype)


def _conv(x, before, w, taps: int):
    """sum_k w_k x[t - (K - 1) + k] of a tile's rows."""
    return sum(
        w[k:k + 1] * _shift_down(x, before, taps - 1 - k)
        for k in range(taps)
    )


def _silu_slope(z):
    s = jax.nn.sigmoid(z)
    return s * (1.0 + z * (1.0 - s))


def _silu_fwd_kernel(u_ref, before_ref, w_ref, *rest, taps: int):
    """`rest` is (y,) or, with a bias, (bias, y)."""
    *bias, y_ref = rest
    x = u_ref[0].astype(jnp.float32)
    before = jnp.where(
        pl.program_id(1) == 0, 0.0, before_ref[0].astype(jnp.float32)
    )
    z = _conv(x, before, w_ref[...].astype(jnp.float32), taps)
    if bias:
        z = z + bias[0][...].astype(jnp.float32)
    y_ref[0] = (z * jax.nn.sigmoid(z)).astype(y_ref.dtype)


def _silu_bwd_kernel(u_ref, before_ref, after_ref, g_ref, g_after_ref,
                     w_ref, *rest, taps: int, tiles: int):
    """`rest` is (du, dw) or, with a bias, (bias, du, dw): d(bias) is the
    row after the taps' of the dw partial."""
    *bias, du_ref, dw_ref = rest
    x = u_ref[0].astype(jnp.float32)
    tile = x.shape[0]
    before = jnp.where(
        pl.program_id(1) == 0, 0.0, before_ref[0].astype(jnp.float32)
    )
    w = w_ref[...].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    z = _conv(x, before, w, taps)
    if bias:
        z = z + bias[0][...].astype(jnp.float32)
    dz = g * _silu_slope(z)
    # the rows after the tile: their taps reach back into the tile
    z_after = _conv(
        after_ref[0].astype(jnp.float32), x[tile - _HALO:], w, taps
    )
    if bias:
        z_after = z_after + bias[0][...].astype(jnp.float32)
    dz_after = jnp.where(
        pl.program_id(1) == tiles - 1, 0.0,
        g_after_ref[0].astype(jnp.float32) * _silu_slope(z_after),
    )
    du = 0.0
    for k in range(taps):
        s = taps - 1 - k
        du = du + w[k:k + 1] * _shift_up(dz, dz_after, s)
        dw_ref[0, 0, k:k + 1, :] = (dz * _shift_down(x, before, s)).sum(
            axis=0, keepdims=True
        )
    used = taps
    if bias:
        dw_ref[0, 0, taps:taps + 1, :] = dz.sum(axis=0, keepdims=True)
        used += 1
    if used < _PARTIAL_ROWS:
        dw_ref[0, 0, used:, :] = jnp.zeros(
            (_PARTIAL_ROWS - used, x.shape[1]), jnp.float32
        )
    du_ref[0] = du.astype(du_ref.dtype)


def _silu_specs(tile: int, tiles: int, columns: int):
    """`_specs` over a grid (batch, tile, column block)."""
    per_tile = tile // _HALO
    own = pl.BlockSpec((1, tile, columns), lambda b, i, c: (b, i, c))
    before = pl.BlockSpec(
        (1, _HALO, columns),
        lambda b, i, c: (b, jnp.maximum(i * per_tile - 1, 0), c),
    )
    after = pl.BlockSpec(
        (1, _HALO, columns),
        lambda b, i, c: (
            b, jnp.minimum((i + 1) * per_tile, tiles * per_tile - 1), c
        ),
    )
    return own, before, after


def _silu_grid(u, weight):
    batch, length, width = u.shape
    tile = _tile(length)
    columns = next(
        c for c in (_SILU_COLUMNS, 256, _LANES) if width % c == 0
    )
    tiles = length // tile
    taps = pl.BlockSpec((weight.shape[0], columns), lambda b, i, c: (0, c))
    offset = pl.BlockSpec((1, columns), lambda b, i, c: (0, c))
    return (
        (batch, tiles, width // columns), tiles, columns, taps, offset,
        _silu_specs(tile, tiles, columns),
    )


def _silu_forward(u, weight, bias=None):
    """`bias` (1, d) or None: the same kernel with or without the ref."""
    grid, _, _, taps, offset, (own, before, _) = _silu_grid(u, weight)
    biased = bias is not None
    (y,) = _call(
        functools.partial(_silu_fwd_kernel, taps=weight.shape[0]),
        grid, [own, before, taps] + [offset] * biased, [own],
        [(u.shape, u.dtype)], [u, u, weight] + [bias] * biased,
        "silu_short_conv_fwd",
    )
    return y


def _silu_backward(u, weight, g, bias=None):
    """(du, the (8, d) float32 sum of the programs' partials: the taps'
    rows, then the bias's)."""
    grid, tiles, columns, taps, offset, (own, before, after) = _silu_grid(
        u, weight
    )
    biased = bias is not None
    g = g.astype(u.dtype)
    du, partials = _call(
        functools.partial(
            _silu_bwd_kernel, taps=weight.shape[0], tiles=tiles
        ),
        grid,
        [own, before, after, own, after, taps] + [offset] * biased,
        [own,
         pl.BlockSpec(
             (1, 1, _PARTIAL_ROWS, columns), lambda b, i, c: (b, i, 0, c)
         )],
        [(u.shape, u.dtype),
         ((u.shape[0], tiles, _PARTIAL_ROWS, u.shape[2]), jnp.float32)],
        [u, u, u, g, g, weight] + [bias] * biased,
        "silu_short_conv_bwd",
    )
    return du, partials.sum(axis=(0, 1))


@jax.custom_vjp
def _silu_conv(u, weight):
    return _silu_forward(u, weight)


def _silu_conv_fwd(u, weight):
    return _silu_forward(u, weight), (u, weight)


def _silu_conv_bwd(residuals, g):
    u, weight = residuals
    du, partial = _silu_backward(u, weight, g)
    return du, partial[:weight.shape[0]].astype(weight.dtype)


_silu_conv.defvjp(_silu_conv_fwd, _silu_conv_bwd)


@jax.custom_vjp
def _silu_conv_biased(u, weight, bias):
    return _silu_forward(u, weight, bias[None])


def _silu_conv_biased_fwd(u, weight, bias):
    return _silu_forward(u, weight, bias[None]), (u, weight, bias)


def _silu_conv_biased_bwd(residuals, g):
    u, weight, bias = residuals
    taps = weight.shape[0]
    du, partial = _silu_backward(u, weight, g, bias[None])
    return (
        du, partial[:taps].astype(weight.dtype),
        partial[taps].astype(bias.dtype),
    )


_silu_conv_biased.defvjp(_silu_conv_biased_fwd, _silu_conv_biased_bwd)


def silu_short_conv(u, weight, bias=None):
    """silu(causal_depthwise_conv_K(u) + bias) of u (batch, L, d) under
    weight (K, d) and the optional bias (d,) -> (batch, L, d), in `u`'s
    type with float32 inside: the Pallas kernels where the shapes tile
    (`silu_conv_shapes_ok`), the shifted `jnp` form elsewhere."""
    from elasticdl_tpu.parallel.mesh import in_export_mode

    biased = bias is not None
    if silu_conv_shapes_ok(u.shape, weight.shape, biased) and (
        not in_export_mode()
    ):
        if biased:
            return _silu_conv_biased(u, weight, bias)
        return _silu_conv(u, weight)
    return shifted_silu_conv(u, weight, bias)


def gated_short_conv(bcu, weight):
    """C * causal_depthwise_conv_K(B * u) of bcu (batch, L, 3d) = [B, C,
    u] under weight (K, d) -> (batch, L, d), in `bcu`'s type with
    float32 inside: the Pallas kernels where the shapes tile
    (`short_conv_shapes_ok`), the shifted `jnp` form elsewhere."""
    from elasticdl_tpu.parallel.mesh import in_export_mode

    if short_conv_shapes_ok(bcu.shape, weight.shape) and not in_export_mode():
        return _short_conv(bcu, weight)
    return shifted_short_conv(bcu, weight)
