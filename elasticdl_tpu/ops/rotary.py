"""Rotary position embedding's turn, in one pass where the columns lie.

    out[.., i] = x[.., i] * cos2[t, i] + x[.., partner(i)] * sin2[t, i]

over the `columns` = R turned columns of every head of (B, L, H, D), which
start at column `first` of the head: the HALVES pairing (column i turns
with column i +- R/2), position t = the index along L, R/2 frequencies
`inv_freq`, cos and sin times `factor`; a head's other D - R columns pass.
`cos2` = [cos | cos] and `sin2` = [-sin | sin] on a head's turned run, 1 and
0 beside it.

`rotary_turn` is the one entry a model calls: a Pallas kernel
(`rotary_turn` / `rotary_turn_bwd` in a device trace) over the lane-dense
(B, L, H x D) view the projections write and the attention kernels read,
where the shapes tile (`one_pass_ok`), and `halves_turn`, the same
mathematics as two half-head products a half in plain `jnp`, elsewhere (the
arrangement of `ops/short_conv.py: gated_short_conv`).  The kernel reads x
once in its own dtype, forms the same float32 products and the one sum an
element that `halves_turn` forms, and writes the input's dtype: no float32
copy of x, no half-filled (.., R/2) arrays, no relayout.  It is a
`jax.custom_vjp` whose backward IS the kernel with `sin2` negated (the turn
is orthogonal; swapping the halves of [-sin | sin] negates it), so the
backward reads the cotangent once, too, and saves nothing but the tables.

Kernel shape: the grid walks (batch, rows / block, columns / block); a
program holds a (rows, columns) block of x and the (rows, period) blocks of
the two float32 tables, `period` = lcm(D, 128) lanes: the least run of
columns after which a row's pattern of turned lanes repeats.  It works a
128-lane tile at a time: a tile that turns nothing is copied; in any other,
`partner` is one lane rotation by R/2 where R fills the tile and two (R/2
back, R/2 ahead) under a lane select where it does not (R = 64 of D = 128
or 256; two heads of 64 a tile).  A turned run that would cross two lane
tiles is not taken.  An array NARROWER than a lane tile (MLA's one shared
64-wide key part) is read `fold` = 128 / (H x D) tokens a row, the tables
folded alike: a token is then what a head is to the lanes.

Why a kernel (`scripts/probe_rotary.py`; ONE Laguna attention layer under
the zoo's remat, (2, 8192, 2048) bfloat16 in, value and gradient; PR 66).
The compile for a described v5e estimates the layer OUTSIDE its attention
kernels (the entry's `estimated_cycles`, 1e6: all of it | what is no
product), and the chip's traced layer reads (ms a call: the `laguna/attn_*`
scope | the whole layer):
  window layer, 64 heads, 128 of 128 columns turned
    halves (float32 halves apart)     128.69 |  82.11    72.67 |  78.10
    this kernel                        79.05 |  32.54    48.24 |  53.66
    `jnp.roll` on the 4-D array       155.21 | 109.53    95.17 | 100.64
    two whole-row rolls and a select
    on the flat view                  129.45 |  82.95    73.43 |  78.87
  full layer, 48 heads, YaRN over 64 of 128 columns
    halves                            100.20 |  68.50   100.38 | 104.56
    this kernel                        61.14 |  24.35    82.74 |  86.85
    `jnp.roll` on the 4-D array       114.58 |  80.81   108.72 | 112.93
    rolls and select, flat view        97.91 |  63.52   102.10 | 106.28
The products are 46.5e6 (window) and 31.7-36.8e6 (full) in every form.  What
the halves cost beside them: the q product writes float32 (f32[2,8192,8192]),
the cotangent is converted whole, four half-filled f32[.., 64, 64] copies a
pass feed three `pad_maximum_fusion`s, and two (2048, 8, 64, 128) relayouts
stand between the turn and the attention kernels' view; XLA has no cheap lane
rotation inside a loop fusion, so the two `jnp` rewrites are no better.  The
kernel's own calls (q and k, forward, rebuilt forward and backward: six a
layer) take 2.75 of the window layer's 53.65 ms (four `rotary_turn` 1.82,
two `rotary_turn_bwd` 0.93: 1.21e9 + 0.60e9 bytes read and written, 660
GB/s, four fifths of the memory's 819) and 2.10 of the full layer's 86.79.
On the chip the kernel's results are `halves_turn`'s bit for bit, forward
and VJP, at every cell's shape.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops.flash_attention import use_interpret

_LANES = 128
# the most columns a program's block of x holds (of whole periods)
_COLUMNS = 1024
# the most turned runs a lane tile may hold (two heads of 64): the select
# between the two rotations costs two compares a run
_RUNS = 2


class _Plan(NamedTuple):
    """How the kernel reads a (B, L, H, D) array."""

    fold: int                    # tokens a row of the view (1 unless narrow)
    period: int                  # lanes after which a row's pattern repeats
    rows: int                    # rows a block
    block: int                   # columns a block
    half: int                    # R / 2
    # a lane tile of the period -> the FIRST halves of its turned runs, as
    # (first lane, lane after the last); () where the tile turns nothing
    tiles: Tuple[Tuple[Tuple[int, int], ...], ...]


def _row_block(rows: int) -> Optional[int]:
    for cand in (512, 256, 128, 64, 32, 16):
        if rows % cand == 0:
            return cand
    return None


def _plan(shape, columns: int, first: int) -> Optional[_Plan]:
    if len(shape) != 4:
        return None
    _, length, heads, dim = shape
    if columns < 2 or columns % 2 or first < 0 or first + columns > dim:
        return None
    width, fold = heads * dim, 1
    if width < _LANES:
        if _LANES % width or length % (_LANES // width):
            return None
        fold = _LANES // width
    period = math.lcm(dim, _LANES)
    rows = _row_block(length // fold)
    if width * fold % period or rows is None:
        return None
    tiles = [[] for _ in range(period // _LANES)]
    for start in range(first, period, dim):
        tile, lane = divmod(start, _LANES)
        if lane + columns > _LANES:
            return None
        tiles[tile].append((lane, lane + columns // 2))
    if max(map(len, tiles)) > _RUNS:
        return None
    block = max(
        size for size in range(period, width * fold + 1, period)
        if width * fold % size == 0 and (size <= _COLUMNS or size == period)
    )
    return _Plan(
        fold, period, rows, block, columns // 2,
        tuple(tuple(spans) for spans in tiles),
    )


def one_pass_ok(shape, columns: int, first: int = 0) -> bool:
    """Whether the kernel takes (B, L, H, D) = `shape` turned over
    `columns` of a head from column `first`: H x D whole periods of
    lcm(D, 128) lanes (or a whole fraction of one lane tile), every turned
    run inside one lane tile and at most `_RUNS` of them in it, L whole
    blocks of at least 16 rows."""
    return _plan(tuple(shape), columns, first) is not None


def _tables(length: int, dim: int, first: int, inv_freq, factor: float):
    """(cos2, sin2) a head: (L, D) float32."""
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    pad = ((0, 0), (first, dim - first - 2 * inv_freq.shape[0]))
    return (
        jnp.pad(jnp.concatenate([cos, cos], axis=-1), pad, constant_values=1),
        jnp.pad(jnp.concatenate([-sin, sin], axis=-1), pad),
    )


def halves_turn(x, inv_freq, factor: float = 1.0, first: int = 0):
    """The turn in plain `jnp`: float32 inside, the halves split apart and
    joined again."""
    columns = 2 * inv_freq.shape[0]
    cuts = [c for c in (first, first + columns) if 0 < c < x.shape[-1]]
    if cuts:
        parts = jnp.split(x, cuts, axis=-1)
        at = 1 if first else 0
        parts[at] = halves_turn(parts[at], inv_freq, factor)
        return jnp.concatenate(parts, axis=-1)
    length = x.shape[1]
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def _turn_kernel(x_ref, cos_ref, sin_ref, out_ref, *, plan: _Plan,
                 back: bool):
    half, tiles = plan.half, plan.tiles
    first_halves = {}          # a lane tile of the period -> its select
    for t in range(x_ref.shape[1] // _LANES):
        at = slice(t * _LANES, (t + 1) * _LANES)
        which = t % len(tiles)
        if not tiles[which]:
            out_ref[:, at] = x_ref[:, at]
            continue
        table = slice(which * _LANES, (which + 1) * _LANES)
        x = x_ref[:, at].astype(jnp.float32)
        # column i's partner, for a second half: column i - R/2
        partner = pltpu.roll(x, half, 1)
        if 2 * half < _LANES:
            if which not in first_halves:
                lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
                first_halves[which] = functools.reduce(
                    jnp.logical_or,
                    [(lane >= lo) & (lane < hi) for lo, hi in tiles[which]],
                )
            partner = jnp.where(
                first_halves[which], pltpu.roll(x, _LANES - half, 1), partner
            )
        kept, crossed = x * cos_ref[:, table], partner * sin_ref[:, table]
        out_ref[:, at] = (
            kept - crossed if back else kept + crossed
        ).astype(out_ref.dtype)


def _call(x, cos2, sin2, plan: _Plan, back: bool):
    batch, rows, columns = x.shape
    vma = frozenset().union(*(jax.typeof(t).vma for t in (x, cos2, sin2)))
    block = pl.BlockSpec(
        (None, plan.rows, plan.block), lambda b, i, j: (b, i, j)
    )
    table = pl.BlockSpec((plan.rows, plan.period), lambda b, i, j: (i, 0))
    return pl.pallas_call(
        functools.partial(_turn_kernel, plan=plan, back=back),
        grid=(batch, rows // plan.rows, columns // plan.block),
        in_specs=[block, table, table], out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype, vma=vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
        ),
        interpret=use_interpret(),
        name="rotary_turn_bwd" if back else "rotary_turn",
    )(x, cos2, sin2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _one_pass(x, cos2, sin2, plan):
    return _call(x, cos2, sin2, plan, False)


def _one_pass_fwd(x, cos2, sin2, plan):
    return _call(x, cos2, sin2, plan, False), (cos2, sin2)


def _one_pass_bwd(plan, tables, g):
    return _call(g, *tables, plan, True), None, None


_one_pass.defvjp(_one_pass_fwd, _one_pass_bwd)


def rotary_turn(x, inv_freq, factor: float = 1.0, first: int = 0):
    """Turn columns [first, first + R) of every head of (B, L, H, D) by
    position = index along L at the R / 2 frequencies `inv_freq`, cos and
    sin times `factor`.  float32 inside, x's dtype out."""
    batch, length, heads, dim = x.shape
    plan = _plan(x.shape, 2 * inv_freq.shape[0], first)
    if plan is None:
        return halves_turn(x, inv_freq, factor, first)
    # a row of the view: `fold` tokens' heads, its tables a period's
    cos2, sin2 = (
        jnp.tile(t, (1, plan.period // dim // plan.fold)).reshape(
            length // plan.fold, plan.period
        )
        for t in _tables(length, dim, first, inv_freq, factor)
    )
    return _one_pass(
        x.reshape(batch, length // plan.fold, -1), cos2, sin2, plan
    ).reshape(x.shape)
