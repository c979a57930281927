"""Fused embedding arena: every same-`dim` feature table as ONE array.

Motivation (docs/PERF.md): a model with F separate
`DistributedEmbedding` tables issues F gather kernels forward and F
scatter-add kernels backward per step.  Each kernel pays its own
dispatch/fusion boundary, and on the row-sharded layout each pays its own
cross-shard routing.  Stacking all same-dimension tables into one
row-sharded **arena** — per-feature row ranges, addressed by
`offset + hash(id) % capacity` — collapses that to ONE gather and ONE
scatter-add over the concatenated ids, regardless of feature count.

Per-feature capacities survive: feature i owns rows
[offset_i, offset_i + capacity_i), and its ids are hashed mod its OWN
capacity before the offset shift, so collision behavior is identical to
an isolated table of that capacity.  The arena parameter is named
"embedding" so `embedding_param_sharding` row-shards it over the mesh
`model` axis exactly like individual tables.

The VJP is `embedding.py:_lookup`'s: a PROMISE_IN_BOUNDS gather forward
(of the batch's DISTINCT rows, expanded from a compact buffer, where the
rows are narrow and the ids many: PR 48, `embedding.py: _gather_rows`)
and `scatter_add_rows` backward, which combines the batch's duplicate
rows and scatters the DISTINCT ones in chunks (PR 32,
docs/embedding_design_note.md: the chip's scatter costs ~100 ns per
update of its static update count, so 1.7M updates cost 178 ms whatever
the ids, and the distinct 2% of them cost 33 with the combine).  A
(rows, 1) arena and rows of 512 bytes and more keep XLA's plain scatter.
The round-5 finding also stands: do NOT fuse tables of DIFFERENT dims
into one padded arena — lane padding eats the win.  One arena per
distinct dim.

Quantized storage (`arena_dtype="int8"`, docs/PERF.md "Quantized
arena"): rows live as int8 codes with a per-row fp32 scale — a second
plane alongside the arena — and are dequantized INSIDE the fused
gather, so the step still issues one (code+scale) gather and one
backward regardless of feature count while the dominant
bytes-accessed term shrinks ~4x.  The gradient/optimizer path stays
fp32: a zero fp32 "carrier" parameter keeps the trainable name/shape,
`_grad_tap` routes the scatter-add gradient into it, and
`fold_quantized_updates` folds the optimizer's per-step delta back into
the codes with STOCHASTIC rounding (seeded from the step counter) so
low-magnitude updates are unbiased rather than truncated.  All int8
plane arithmetic lives in this module — graftlint GL-QUANT
(docs/LINTS.md) rejects raw-plane math anywhere else.
"""

from __future__ import annotations

import zlib
from collections.abc import Mapping
from typing import Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.layers.embedding import (
    _PIB,
    hash_ids,
    hash_ids_host,
    lookup_rows,
    scatter_add_rows,
)

ARENA_DTYPES = ("float32", "int8")

# int8 code range is symmetric [-127, 127]: -128 is unused so negation
# round-trips and scale = max|row| / 127 covers the row exactly.
_Q_MAX = 127.0

# RNG namespace for the training write-back rounding; folded with the
# step counter and the plane path so every data-parallel replica — and
# every re-trace — rounds identically (deterministic RNG plumbing).
_FOLD_SEED = 0x51A7

# ---- quantization numerics (ALL int8 plane math lives here) ------------


def quantize_rows(table):
    """fp32 (R, D) -> (int8 codes (R, D), fp32 scales (R, 1)).

    Per-row symmetric quantization: scale = max|row| / 127 (all-zero
    rows get scale 1.0 so they round-trip exactly), codes round to
    nearest.  Deterministic — used by converters and arena init; the
    TRAINING write-back uses `stochastic_round` so repeated
    low-magnitude updates are unbiased instead of truncated."""
    table = jnp.asarray(table, jnp.float32)
    max_abs = jnp.max(jnp.abs(table), axis=1, keepdims=True)
    scale = jnp.where(max_abs > 0, max_abs / _Q_MAX, 1.0)
    q8 = jnp.clip(jnp.round(table / scale), -_Q_MAX, _Q_MAX).astype(jnp.int8)
    return q8, scale


def dequantize_rows(q8, scale):
    """int8 codes + per-row scales -> the fp32 view the math runs on."""
    return q8.astype(jnp.float32) * scale


def quantize_rows_host(table: "np.ndarray"):
    """numpy mirror of `quantize_rows` for the tiered store's host tier
    (elasticdl_tpu/store/host_tier.py): fp32 (R, D) -> (int8 codes,
    fp32 (R, 1) scales), bit-identical numerics to the device version.
    Lives HERE because GL-QUANT sanctions plane arithmetic only in this
    module — the host tier stores and indexes the planes but never does
    math on them."""
    table = np.asarray(table, np.float32)
    max_abs = np.max(np.abs(table), axis=1, keepdims=True) \
        if table.size else np.zeros((table.shape[0], 1), np.float32)
    scale = np.where(max_abs > 0, max_abs / _Q_MAX, 1.0).astype(np.float32)
    q8 = np.clip(
        np.round(table / scale), -_Q_MAX, _Q_MAX
    ).astype(np.int8)
    return q8, scale


def dequantize_rows_host(q8: "np.ndarray", scale: "np.ndarray"):
    """numpy mirror of `dequantize_rows` (see quantize_rows_host)."""
    return q8.astype(np.float32) * np.asarray(scale, np.float32)


def stochastic_round(x, key):
    """Unbiased integer rounding: floor(x + U[0,1)), so E[result] == x
    and exact integers return exactly (floor(k + u) == k for u < 1) —
    codes that didn't move round-trip bit-stable."""
    u = jax.random.uniform(key, x.shape, x.dtype)
    return jnp.clip(jnp.floor(x + u), -_Q_MAX, _Q_MAX).astype(jnp.int8)


@jax.custom_vjp
def _grad_tap(carrier, flat_ids, order=None):
    """Gradient collector for the quantized arena.

    Forward contributes exact ZEROS shaped like the gather output —
    built from the carrier's shape/dtype only, so XLA folds the add
    away and never reads the fp32 carrier's bytes; the int8 planes are
    the only table bytes the forward touches.  Backward scatter-adds
    the output cotangent into the carrier's shape — the same
    scatter-add `_lookup` produces for an fp32 table
    (`scatter_add_rows`; `order` as `_lookup`'s) — so the optimizer
    sees an ordinary fp32 embedding gradient on the zero carrier and
    `fold_quantized_updates` later folds the resulting delta into the
    codes."""
    return jnp.zeros(flat_ids.shape + (carrier.shape[1],), carrier.dtype)


def _grad_tap_fwd(carrier, flat_ids, order):
    return _grad_tap(carrier, flat_ids), (carrier, flat_ids, order)


def _grad_tap_bwd(residuals, g):
    carrier, flat_ids, order = residuals
    dcarrier = scatter_add_rows(carrier.shape, flat_ids, g, order)
    return dcarrier.astype(carrier.dtype), None, None


_grad_tap.defvjp(_grad_tap_fwd, _grad_tap_bwd)


def arena_offsets(features: Tuple[Tuple[str, int], ...]) -> Dict[str, int]:
    """{feature name: first arena row} for a (name, capacity) tuple."""
    offsets, total = {}, 0
    for name, capacity in features:
        offsets[name] = total
        total += int(capacity)
    return offsets


def arena_rows(features: Tuple[Tuple[str, int], ...]) -> int:
    return sum(int(capacity) for _, capacity in features)


def _looked_up(arena, ids, prehashed, lookup):
    """`EmbeddingArena.__call__` past its parameters: the ids hashed
    into arena rows, ONE `lookup` over all of them, the pads masked."""
    if prehashed:
        rows = jnp.asarray(ids)
        return lookup(rows.reshape(-1)).reshape(
            rows.shape + (arena.output_dim,)
        )
    if set(ids) != {name for name, _ in arena.features}:
        raise ValueError(
            f"arena expects ids for {[n for n, _ in arena.features]}, "
            f"got {sorted(ids)}"
        )
    # Per-feature hashed rows, flattened per example and concatenated:
    # the single gather's id stream.  Pure index arithmetic — XLA
    # fuses it into the gather; no extra kernels.
    batch = None
    parts, valids, shapes = [], [], []
    offset = 0
    for name, capacity in arena.features:
        x = jnp.asarray(ids[name])
        if batch is None:
            batch = x.shape[0]
        valid = x != arena.pad_id
        rows = hash_ids(
            jnp.where(valid, x, 0), capacity, mix=arena.hash_input
        ) + jnp.int32(offset)
        parts.append(rows.reshape(batch, -1))
        valids.append(valid.reshape(batch, -1))
        shapes.append(x.shape)
        offset += int(capacity)
    all_rows = jnp.concatenate(parts, axis=1)          # (B, sum k_i)
    all_valid = jnp.concatenate(valids, axis=1)
    vecs = lookup(all_rows.reshape(-1)).reshape(
        all_rows.shape + (arena.output_dim,)
    )
    vecs = jnp.where(all_valid[..., None], vecs, 0.0)
    out, col = {}, 0
    for (name, _), shape in zip(arena.features, shapes):
        k = int(np.prod(shape[1:], dtype=np.int64)) if len(shape) > 1 \
            else 1
        out[name] = vecs[:, col: col + k].reshape(
            shape + (arena.output_dim,)
        )
        col += k
    return out


class EmbeddingArena(nn.Module):
    """N per-feature embedding tables fused into one parameter.

    features:   ordered ((name, capacity), ...) — one entry per logical
                table; order fixes the row layout.
    output_dim: shared embedding dimension (one arena per dim).
    hash_input: multiplicative-mix ids before the per-feature mod
                (same semantics as DistributedEmbedding).

    Call with a dict {name: int ids of any shape (..., )}; returns
    {name: (..., output_dim)} vectors.  All features' ids are hashed
    into arena rows, concatenated, and looked up with ONE `_lookup`
    (one gather forward, one `scatter_add_rows` backward).

    Call with `prehashed=True` and a single int32 array of arena rows
    (host-hashed via `arena_rows_host` / the dedup'd wire format) to
    skip the on-device hashing entirely.

    arena_dtype: "float32" (default — bit-identical to the PR 3 path)
    or "int8" (quantized storage: int8 codes + per-row fp32 scales in
    the mutable "quantized" collection, a zero fp32 carrier param for
    the gradient; see the module docstring).
    """

    features: Tuple[Tuple[str, int], ...]
    output_dim: int
    pad_id: int = -1
    hash_input: bool = True
    param_dtype: jnp.dtype = jnp.float32
    arena_dtype: str = "float32"

    @nn.compact
    def __call__(self, ids, prehashed: bool = False):
        if self.arena_dtype not in ARENA_DTYPES:
            raise ValueError(
                f"arena_dtype must be one of {ARENA_DTYPES}, got "
                f"{self.arena_dtype!r}"
            )
        shape = (arena_rows(self.features), self.output_dim)
        if self.arena_dtype == "int8":
            # Trainable ZERO carrier: same name/shape as the fp32 table,
            # so sharding, opt_state structure, and checkpoint paths are
            # identical across modes.  It holds this step's optimizer
            # delta between apply_updates and fold_quantized_updates.
            carrier = self.param(
                "embedding", nn.initializers.zeros, shape, jnp.float32
            )

            def _init_planes():
                sample = nn.initializers.normal(stddev=0.05)(
                    self.make_rng("params"), shape, jnp.float32
                )
                q8, scale = quantize_rows(sample)
                return {"q8": q8, "scale": scale}

            planes = self.variable("quantized", "embedding", _init_planes)
            q8 = planes.value["q8"]
            scale = planes.value["scale"]

            def lookup(flat_rows):
                # dequantize INSIDE the fused gather: code gather +
                # scale gather + one multiply; `_grad_tap` adds exact
                # zeros forward and collects the scatter-add backward.
                deq = dequantize_rows(
                    q8.at[flat_rows].get(mode=_PIB),
                    scale.at[flat_rows].get(mode=_PIB),
                )
                return deq + lookup_rows(
                    self, carrier, flat_rows, _grad_tap
                )
        else:
            table = self.param(
                "embedding",
                nn.initializers.normal(stddev=0.05),
                shape,
                self.param_dtype,
            )

            def lookup(flat_rows):
                return lookup_rows(self, table, flat_rows)

        # hashing, the gather and the masks are one scope (they fuse):
        # profiler.DEVICE_SCOPES
        with jax.named_scope("arena/lookup"):
            return _looked_up(self, ids, prehashed, lookup)

    # ---- host-side helpers (packers / equivalence tests) ---------------

    def arena_rows_host(self, ids: Dict[str, "np.ndarray"]) -> np.ndarray:
        """numpy replica of the device row computation: {name: (B, k)}
        raw ids -> (B, sum k) int32 arena rows, bit-exact vs the traced
        path.  Used by host packers (dedup'd wire format) so the device
        consumes rows directly (`prehashed=True`)."""
        parts, offset = [], 0
        for name, capacity in self.features:
            x = np.asarray(ids[name])
            if np.any(x == self.pad_id):
                raise ValueError(
                    f"arena_rows_host: feature {name!r} contains pad ids "
                    f"({self.pad_id}); the prehashed fast path cannot "
                    "represent masked positions — use the per-feature path"
                )
            rows = hash_ids_host(x, capacity, mix=self.hash_input) + offset
            parts.append(rows.reshape(x.shape[0], -1).astype(np.int32))
            offset += int(capacity)
        return np.concatenate(parts, axis=1)


class TieredArena(nn.Module):
    """Device half of the tiered embedding store (elasticdl_tpu/store).

    Where `EmbeddingArena` holds the FULL vocabulary in HBM, this module
    holds only a `cache_rows`-row hot cache; the full (lazily grown)
    vocabulary lives in the store's host-RAM tier.  The cache table is
    the ONLY trainable storage — the store's admission plan guarantees
    every row a training batch touches is cache-resident before the step
    runs, so the jitted train step is structurally identical to the flat
    arena's (one gather forward, one scatter-add backward) and
    numerically identical on an all-hot working set.

    Call with `slots` (..., F) int32 CACHE slots (from
    TieredStore.prepare).  Training always passes resident slots
    (>= 0).  Serving may pass `slot == -1` for cold/unknown ids together
    with `overlay` — a (..., F, dim) plane of host-gathered values for
    exactly those positions; overlay values are stop_gradient'ed (cold
    rows train host-side via the store's fold path, never through the
    device optimizer).

    `cache_dtype="int8"` quantizes the CACHE storage exactly like
    `EmbeddingArena`'s int8 mode: q8 codes + per-row fp32 scales in the
    "quantized" collection, dequantized inside the same fused gather, a
    zero fp32 carrier param (same "embedding" name/shape, so sharding /
    opt_state / checkpoint structure are mode-invariant) collecting the
    scatter-add gradient via `_grad_tap`, and the per-step optimizer
    delta folded back into the codes by the SAME `fold_quantized_updates`
    the flat int8 arena uses — the trainer already calls it
    unconditionally.  Admissions quantize host values into the planes
    through `store/device.py` (the store-side GL-QUANT allowlist).
    """

    cache_rows: int
    output_dim: int
    param_dtype: jnp.dtype = jnp.float32
    cache_dtype: str = "float32"

    @nn.compact
    def __call__(self, slots, overlay=None):
        if self.cache_dtype not in ARENA_DTYPES:
            raise ValueError(
                f"cache_dtype must be one of {ARENA_DTYPES}, got "
                f"{self.cache_dtype!r}"
            )
        shape = (int(self.cache_rows), self.output_dim)
        if self.cache_dtype == "int8":
            carrier = self.param(
                "embedding", nn.initializers.zeros, shape, jnp.float32
            )

            def _init_planes():
                # Same init DISTRIBUTION as the fp32 cache (and the flat
                # arena): a never-admitted slot behaves like a fresh row,
                # modulo the one-shot quantization error.
                sample = nn.initializers.normal(stddev=0.05)(
                    self.make_rng("params"), shape, jnp.float32
                )
                q8, scale = quantize_rows(sample)
                return {"q8": q8, "scale": scale}

            planes = self.variable("quantized", "embedding", _init_planes)
            q8 = planes.value["q8"]
            scale = planes.value["scale"]

            def lookup(flat_rows):
                deq = dequantize_rows(
                    q8.at[flat_rows].get(mode=_PIB),
                    scale.at[flat_rows].get(mode=_PIB),
                )
                return deq + lookup_rows(
                    self, carrier, flat_rows, _grad_tap
                )
        else:
            # Same initializer as the flat arena: a slot that is never
            # admitted before first use behaves like a fresh flat-arena
            # row.
            table = self.param(
                "embedding",
                nn.initializers.normal(stddev=0.05),
                shape,
                self.param_dtype,
            )

            def lookup(flat_rows):
                return lookup_rows(self, table, flat_rows)

        rows = jnp.asarray(slots)
        flat = rows.reshape(-1)
        hot = lookup(jnp.maximum(flat, 0)).reshape(
            rows.shape + (self.output_dim,)
        )
        if overlay is None:
            return hot
        cold = jax.lax.stop_gradient(
            jnp.asarray(overlay).astype(hot.dtype)
        )
        return jnp.where((rows >= 0)[..., None], hot, cold)


def arena_table_from_feature_tables(
    features: Tuple[Tuple[str, int], ...], tables: Dict[str, jnp.ndarray]
) -> jnp.ndarray:
    """Stack per-feature tables (e.g. from trained DistributedEmbedding
    params) into the arena parameter, preserving row layout — the bridge
    for proving arena/per-feature numerical identity and for migrating
    checkpoints of per-table models."""
    parts = []
    for name, capacity in features:
        t = jnp.asarray(tables[name])
        if t.shape[0] != capacity:
            raise ValueError(
                f"table {name!r} has {t.shape[0]} rows, arena slot has "
                f"{capacity}"
            )
        parts.append(t)
    return jnp.concatenate(parts, axis=0)


# ---- quantized write-back + checkpoint migration -----------------------


def is_quantized_planes(node) -> bool:
    """True for the {"q8", "scale"} plane dict a quantized arena stores
    under model_state["quantized"]/<module path>/embedding."""
    return isinstance(node, Mapping) and set(node) == {"q8", "scale"}


def _path_seed(path: Tuple[str, ...]) -> int:
    return zlib.crc32("/".join(path).encode()) & 0x7FFFFFFF


def _requantize_plane(planes, delta, key):
    q8, scale = planes["q8"], planes["scale"]
    # Rows this step never touched have delta exactly 0 (adam's update
    # is 0 when m = v = 0) — keep their codes/scales BIT-stable rather
    # than re-rounding, so idle rows don't random-walk.
    touched = jnp.any(delta != 0.0, axis=1, keepdims=True)
    table = dequantize_rows(q8, scale) + delta
    max_abs = jnp.max(jnp.abs(table), axis=1, keepdims=True)
    new_scale = jnp.where(max_abs > 0, max_abs / _Q_MAX, 1.0)
    new_q8 = stochastic_round(table / new_scale, key)
    return {
        "q8": jnp.where(touched, new_q8, q8),
        "scale": jnp.where(touched, new_scale, scale),
    }


def fold_quantized_updates(params, model_state, step):
    """Post-`optax.apply_updates` write-back for quantized arenas.

    In int8 mode the trainable "embedding" param is a ZERO fp32
    carrier, so after the optimizer applies its update the carrier
    holds exactly this step's per-row fp32 delta.  Fold it: table =
    dequant(q8, scale) + delta, re-derive the per-row scale,
    stochastic-round back to int8 (keyed on (seed, step, plane path) so
    every data-parallel replica rounds identically), and zero the
    carrier for the next step.

    A trace-time no-op (returns the inputs unchanged) when the model
    has no "quantized" collection — the fp32 path stays bit-identical.
    """
    quant = (
        model_state.get("quantized")
        if isinstance(model_state, Mapping) else None
    )
    if not quant:
        return params, model_state
    step_key = jax.random.fold_in(
        jax.random.PRNGKey(_FOLD_SEED), jnp.asarray(step, jnp.uint32)
    )

    def walk(qt, ct, path):
        if is_quantized_planes(qt):
            key = jax.random.fold_in(step_key, _path_seed(path))
            return _requantize_plane(qt, ct, key), jnp.zeros_like(ct)
        new_q, new_c = {}, dict(ct)
        for k in qt:
            new_q[k], new_c[k] = walk(qt[k], ct[k], path + (k,))
        return new_q, new_c

    new_quant, new_inner = walk(quant, params["params"], ())
    new_params = dict(params)
    new_params["params"] = new_inner
    new_state = dict(model_state)
    new_state["quantized"] = new_quant
    return new_params, new_state


def quantized_planes_like(table):
    """Abstract plane template for one arena table leaf: the shapes and
    dtypes `arena_dtype="int8"` stores for a (R, D) table."""
    rows, dim = table.shape
    return {
        "q8": jax.ShapeDtypeStruct((rows, dim), jnp.int8),
        "scale": jax.ShapeDtypeStruct((rows, 1), jnp.float32),
    }


def quantize_arena_tree(params, quantized_template):
    """fp32 -> int8 checkpoint migration: params is the inner "params"
    dict of an fp32 restore, quantized_template the configured model's
    "quantized" collection (abstract or concrete — only its STRUCTURE
    is read).  Each table found at a template plane path is quantized
    deterministically and its param slot becomes the zero carrier.
    Returns (carrier params, concrete quantized collection).  The
    carrier keeps the table's name/shape, so adam m/v restored against
    the fp32 table carry over unchanged."""

    def walk(qt, pt, path):
        if is_quantized_planes(qt):
            q8, scale = quantize_rows(pt)
            return (
                {"q8": q8, "scale": scale},
                jnp.zeros(pt.shape, jnp.float32),
            )
        new_q, new_p = {}, dict(pt)
        for k in qt:
            new_q[k], new_p[k] = walk(qt[k], pt[k], path + (k,))
        return new_q, new_p

    quant, new_params = walk(quantized_template, params, ())
    return new_params, quant


def dequantize_arena_tree(params, quantized):
    """int8 -> fp32 export (serving on an fp32 config, un-quantized
    fine-tuning): rebuild each table as dequant(q8, scale) + carrier
    (the carrier is zero between steps, but folding it keeps the
    conversion exact even mid-step) and drop the planes.  Returns the
    fp32 inner "params" dict."""

    def walk(qt, pt):
        if is_quantized_planes(qt):
            return dequantize_rows(qt["q8"], qt["scale"]) + jnp.asarray(
                pt, jnp.float32
            )
        new_p = dict(pt)
        for k in qt:
            new_p[k] = walk(qt[k], pt[k])
        return new_p

    return walk(quantized, params)
