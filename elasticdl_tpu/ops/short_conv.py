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
            dimension_semantics=("parallel", "parallel"),
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


def gated_short_conv(bcu, weight):
    """C * causal_depthwise_conv_K(B * u) of bcu (batch, L, 3d) = [B, C,
    u] under weight (K, d) -> (batch, L, d), in `bcu`'s type with
    float32 inside: the Pallas kernels where the shapes tile
    (`short_conv_shapes_ok`), the shifted `jnp` form elsewhere."""
    from elasticdl_tpu.parallel.mesh import in_export_mode

    if short_conv_shapes_ok(bcu.shape, weight.shape) and not in_export_mode():
        return _short_conv(bcu, weight)
    return shifted_short_conv(bcu, weight)
