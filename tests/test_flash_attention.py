"""Pallas flash-attention kernel vs the O(L^2) reference: forward and
gradients, causal and full, odd shapes.  Off-TPU the SAME kernel runs in
Pallas interpret mode, so this exercises the real kernel code path."""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops.flash_attention import flash_attention
from elasticdl_tpu.ops.ring_attention import full_attention_reference
from model_zoo.common.decoder import remat_block


def _qkv(batch=2, length=256, heads=4, dim=32, seed=0):
    rng = np.random.RandomState(seed)
    shape = (batch, length, heads, dim)
    return tuple(
        jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.3)
        for _ in range(3)
    )


# (causal, q/k/v shape overrides): the resident kernel's shapes, and the
# streaming kernel's (causal, head width 256, three tiles of 128)
CASES = [
    pytest.param(False, {}, id="full"),
    pytest.param(True, {}, id="causal"),
    pytest.param(True, dict(batch=2, length=384, heads=2, dim=256),
                 id="causal-width256"),
]


@pytest.mark.parametrize("causal, shape", CASES)
def test_matches_reference_forward(causal, shape):
    q, k, v = _qkv(**shape)
    out = flash_attention(q, k, v, causal=causal)
    ref = full_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_short_sequence_single_tile():
    q, k, v = _qkv(length=64)
    out = flash_attention(q, k, v, causal=True)
    ref = full_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


# the streaming backward's whole-length scratch: four tiles of 128 at a
# head of 256, one K/V head under a group of two query heads
GRADIENT_CASES = CASES + [
    pytest.param(True, dict(batch=1, length=512, heads=2, dim=256,
                            kv_heads=1), id="causal-width256-four-tiles"),
]


@pytest.mark.parametrize("causal, shape", GRADIENT_CASES)
def test_gradients_match_reference(causal, shape):
    shape = dict(shape or dict(batch=1, length=128, heads=2, dim=16))
    kv_heads = shape.pop("kv_heads", shape.get("heads", 4))
    q, k, v = _qkv(**shape)
    k, v = k[:, :, :kv_heads], v[:, :, :kv_heads]
    group = q.shape[2] // kv_heads

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal) ** 2).sum()

    def loss_ref(q, k, v):
        k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
        return (full_attention_reference(q, k, v, causal=causal) ** 2).sum()

    grads = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    ref_grads = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(grads, ref_grads):
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_jit_and_bf16():
    q, k, v = _qkv(length=128)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = jax.jit(lambda a, b, c: flash_attention(a, b, c, causal=True))(
        q, k, v
    )
    ref = full_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=3e-2, atol=3e-2,
    )


def test_shape_validation():
    q, k, v = _qkv(length=100)  # not a multiple of the 128 tile
    with pytest.raises(ValueError, match="multiple of 128"):
        flash_attention(q, k, v)


def test_kv_length_validated():
    q, _, _ = _qkv(length=128)
    k, v, _ = _qkv(length=200)  # un-tileable K/V would drop tail keys
    with pytest.raises(ValueError, match="BOTH q and k"):
        flash_attention(q, k, v)


def test_ring_entry_preserves_sharding_when_seq_unsharded():
    """ring_self_attention's flash fast path must keep the batch-sharded
    layout under jit: a bare pallas_call would silently force full
    replication (every device computing the whole batch)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from elasticdl_tpu.parallel import mesh as mesh_lib
    from elasticdl_tpu.ops.ring_attention import ring_self_attention

    mesh = mesh_lib.create_mesh()  # data=n_devices, seq=1
    assert mesh.shape["seq"] == 1
    q, k, v = _qkv(batch=8, length=128, heads=2, dim=16)
    spec = P("data", "seq", None, None)
    sharding = NamedSharding(mesh, spec)
    q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))
    out = jax.jit(
        lambda a, b, c: ring_self_attention(a, b, c, mesh, causal=True)
    )(q, k, v)
    assert out.sharding.is_equivalent_to(sharding, out.ndim), out.sharding
    ref = full_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-4)


def test_flash_shapes_ok_bounds():
    """Dispatch predicate: tile rules AND the empirical K/V scoped-VMEM
    ceiling (k_len*H*D <= 1.25M — BERT-base L=2048 measured overflowing
    the 16MB scope; L=1024 fits)."""
    from elasticdl_tpu.ops.flash_attention import flash_shapes_ok

    ok = flash_shapes_ok
    assert ok((64, 512, 12, 64), (64, 512, 12, 64))
    assert ok((32, 1024, 12, 64), (32, 1024, 12, 64))      # 0.79M
    assert not ok((16, 2048, 12, 64), (16, 2048, 12, 64))  # 1.57M
    assert not ok((8, 520, 4, 64), (8, 520, 4, 64))        # L % 128
    assert not ok((8, 512, 4, 256), (8, 512, 4, 256))      # D > 128
    # ...which CAUSAL self-attention takes on the streaming kernel, at
    # any length of whole tiles (no K/V residency ceiling)
    wide = (4, 4096, 20, 256)
    assert ok(wide, wide, causal=True) and not ok(wide, wide)
    assert not ok((4, 4000, 20, 256), (4, 4000, 20, 256), causal=True)
    assert not ok((4, 4096, 20, 192), (4, 4096, 20, 192), causal=True)
    assert ok((8, 64, 4, 64), (8, 64, 4, 64))              # sub-128 L


@pytest.mark.parametrize("dims", [(16, 16), (6 + 4, 10)])
def test_blocked_causal_attention_any_width(dims):
    """The lax form `causal_attention` falls back to where the shapes do
    not tile: one row of query tiles at a time, v of another width."""
    from elasticdl_tpu.ops.flash_attention import (
        blocked_causal_attention,
        causal_attention,
    )

    rng = np.random.RandomState(3)
    q, k = (jnp.asarray(rng.randn(2, 96, 3, dims[0]).astype(np.float32))
            for _ in range(2))
    v = jnp.asarray(rng.randn(2, 96, 3, dims[1]).astype(np.float32))

    def grads(fn):
        return jax.grad(
            lambda q, k, v: (fn(q, k, v) ** 2).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    want = grads(lambda q, k, v: full_attention_reference(
        q, k, v, causal=True
    ))
    for fn in (causal_attention,
               lambda q, k, v: blocked_causal_attention(q, k, v, tile=40)):
        np.testing.assert_allclose(
            fn(q, k, v), full_attention_reference(q, k, v, causal=True),
            rtol=2e-4, atol=2e-5,
        )
        for got, ref in zip(grads(fn), want):
            np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-4)


# ---- grouped keys and values, and a window, through `causal_attention` ----


def _dense_band(q, k, v, window, scale=None):
    """Causal softmax attention as one dense masked product: q's head h
    over K/V head h // group, query t over keys s with t - window < s <= t
    (no window: every s <= t)."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    t = jnp.arange(q.shape[1])
    seen = t[:, None] >= t[None, :]
    if window is not None:
        seen &= t[:, None] - t[None, :] < window
    weights = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def _grouped_qkv(group, length=384, dim=128, kv_heads=1, seed=5):
    rng = np.random.RandomState(seed)

    def draw(heads):
        return jnp.asarray(
            rng.randn(1, length, heads, dim).astype(np.float32) * 0.3
        )

    return draw(group * kv_heads), draw(kv_heads), draw(kv_heads)


# three tiles of 128 a sequence: a window under a tile, of one tile, over
# a tile, and one that hides nothing
BANDS = [
    pytest.param(group, window, {}, id=f"group{group}-window{window}")
    for group in (1, 6, 8) for window in (None, 100, 128, 200, 384)
]
# four tiles and more under a group: a key tile's dK and dV are summed over
# the group's heads and over query tiles in the backward kernel's
# whole-length scratch, under the causal mask alone and under a window
# narrower than a tile (the band is two tiles, the first of them clipped);
# two K/V heads, so the scratch is cleared between them
LONG_BANDS = [
    pytest.param(3, None, dict(length=512, kv_heads=2),
                 id="group3-four-tiles"),
    pytest.param(3, 100, dict(length=512, kv_heads=2),
                 id="group3-four-tiles-window100"),
    pytest.param(2, 300, dict(length=2048, kv_heads=2),
                 id="group2-four-tiles-of-512-window300"),
]


@pytest.mark.parametrize("group, window, shape", BANDS)
def test_grouped_window_forward(group, window, shape):
    from elasticdl_tpu.ops.flash_attention import (
        causal_attention,
        stream_shapes_ok,
    )

    q, k, v = _grouped_qkv(group, **shape)
    assert stream_shapes_ok(q.shape, k.shape, v.shape)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            causal_attention(q, k, v, window=window),
            _dense_band(q, k, v, window), rtol=2e-4, atol=2e-4,
        )


@pytest.mark.parametrize("group, window, shape", BANDS + LONG_BANDS)
def test_grouped_window_gradients(group, window, shape):
    from elasticdl_tpu.ops.flash_attention import causal_attention

    q, k, v = _grouped_qkv(group, **shape)

    def grads(fn):
        return jax.grad(
            lambda q, k, v: (fn(q, k, v) ** 2).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    with jax.default_matmul_precision("highest"):
        got = grads(lambda q, k, v: causal_attention(q, k, v, window=window))
        want = grads(lambda q, k, v: _dense_band(q, k, v, window))
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(
            a, b, rtol=5e-4, atol=5e-4, err_msg=f"d{name}"
        )


@pytest.mark.parametrize("window", [None, 24, 40, 100])
def test_blocked_form_takes_groups_and_a_window(window):
    """Shapes that do not tile (width 16, 100 positions) go the blocked
    way, two K/V heads under six query heads, rows of 40."""
    from elasticdl_tpu.ops.flash_attention import (
        blocked_causal_attention,
        causal_attention,
        stream_shapes_ok,
    )

    q, k, v = _grouped_qkv(3, length=100, dim=16, kv_heads=2)
    assert not stream_shapes_ok(q.shape, k.shape, v.shape)

    def grads(fn):
        return jax.grad(
            lambda q, k, v: (fn(q, k, v) ** 2).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    want = grads(lambda q, k, v: _dense_band(q, k, v, window))
    for fn in (
        lambda q, k, v: causal_attention(q, k, v, window=window),
        lambda q, k, v: blocked_causal_attention(
            q, k, v, tile=40, window=window
        ),
    ):
        np.testing.assert_allclose(
            fn(q, k, v), _dense_band(q, k, v, window), rtol=2e-4, atol=2e-5
        )
        for got, ref in zip(grads(fn), want):
            np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-4)


def _kernel_names(q, k, v, window):
    import re

    from elasticdl_tpu.ops.flash_attention import causal_attention

    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: causal_attention(q, k, v, window=window).sum(),
        argnums=(0, 1, 2),
    ))(q, k, v)
    return sorted(set(re.findall(r"\b\w+_attention_\w+\b", str(jaxpr))))


def test_kernel_names_in_the_trace():
    """The metrics find the kernels by name: a decoder's shape of one
    head count and no window (width 256) keeps the names it had, a window
    that hides nothing is no window, and a windowed call is named apart.
    TWO kernels a call: `_dkv` is the whole backward and carries dQ too
    (no `_dq`), under the name the metrics' rules match."""
    plain = ["causal_attention_dkv", "causal_attention_fwd"]
    wide = _grouped_qkv(2, length=256, dim=256, kv_heads=2)[1:] * 2
    assert _kernel_names(*wide[:3], window=None) == plain
    q, k, v = _grouped_qkv(6, length=256)
    assert _kernel_names(q, k, v, window=None) == plain
    assert _kernel_names(q, k, v, window=256) == plain
    assert _kernel_names(q, k, v, window=200) == [
        "window_attention_dkv", "window_attention_fwd",
    ]


# a group of SEVEN query heads a K/V head (28 over 4: no cell before had an
# odd group), three tiles of 128: the causal mask alone, a band that ends
# inside a tile and one of whole tiles
@pytest.mark.parametrize("window", [None, 200, 256])
def test_a_group_of_seven_against_the_blocked_form(window):
    """The streaming kernels against `blocked_causal_attention` (the same
    mathematics in plain lax, a row of query tiles at a time), forward
    and all three gradients: a K/V head's dK and dV gather over seven
    query heads in the backward's scratch, which is cleared between the
    four K/V heads."""
    from elasticdl_tpu.ops.flash_attention import (
        blocked_causal_attention,
        causal_attention,
        stream_shapes_ok,
    )

    q, k, v = _grouped_qkv(7, kv_heads=4, seed=11)
    assert q.shape == (1, 384, 28, 128) and k.shape == (1, 384, 4, 128)
    assert stream_shapes_ok(q.shape, k.shape, v.shape)
    names = _kernel_names(q, k, v, window=window)
    kind = "causal" if window is None else "window"
    assert names == [f"{kind}_attention_dkv", f"{kind}_attention_fwd"]

    def through(fn):
        return jax.value_and_grad(
            lambda q, k, v: (fn(q, k, v, window=window) ** 2).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            causal_attention(q, k, v, window=window),
            blocked_causal_attention(q, k, v, window=window),
            rtol=2e-4, atol=2e-4,
        )
        (_, got), (_, want) = through(causal_attention), through(
            blocked_causal_attention
        )
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(
            a, b, rtol=5e-4, atol=5e-4, err_msg=f"d{name}"
        )


@pytest.mark.parametrize("window", [None, 4096])
def test_a_whole_16k_context_takes_the_streaming_kernels(window):
    """One sequence of 16,384 positions at 28 query heads over 4 K/V heads
    of 128, the longest the rule admits at that width (its backward's
    whole-length scratch is the limit less the tiles' room EXACTLY):
    `causal_attention` goes to the streaming kernels with the band and
    without, never silently the blocked form, whose operands are O(L^2);
    a band of 4,096 is nine key tiles of 512 a query tile."""
    from elasticdl_tpu.ops import flash_attention as fa

    shapes = [(1, 16384, heads, 128) for heads in (28, 4, 4)]
    assert fa.stream_shapes_ok(*shapes)
    assert fa.stream_backward_vmem_bytes(16384, 128) == (
        fa._STREAM_VMEM_LIMIT - fa._STREAM_TILE_ROOM
    )
    assert not fa.stream_shapes_ok(
        *[(1, 16384 + 512, heads, 128) for heads in (28, 4, 4)]
    )
    assert fa._stream_tiles(16384) == 512
    assert fa._band_steps(32, 512, 4096) == 9
    assert fa._band_steps(32, 512, None) == 32
    q, k, v = (jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes)
    kind = "causal" if window is None else "window"
    assert _kernel_names(q, k, v, window=window) == [
        f"{kind}_attention_dkv", f"{kind}_attention_fwd",
    ]


def test_grouped_admission_rule():
    from elasticdl_tpu.ops.flash_attention import (
        causal_attention,
        flash_shapes_ok,
        stream_shapes_ok,
    )

    q, kv = (2, 8192, 64, 128), (2, 8192, 8, 128)
    assert stream_shapes_ok(q, kv, kv) and flash_shapes_ok(q, kv, causal=True)
    assert not flash_shapes_ok(q, kv)                      # not causal
    assert not stream_shapes_ok(q, (2, 8192, 7, 128), (2, 8192, 7, 128))
    assert not stream_shapes_ok(q, kv, (2, 8192, 8, 64))   # v's width
    # a resident shape of unequal head counts is not the resident kernel's
    assert not flash_shapes_ok((2, 128, 8, 64), (2, 128, 2, 64))
    a, b, c = _grouped_qkv(3, length=64, dim=16)
    with pytest.raises(ValueError, match="sees nothing"):
        causal_attention(a, b, c, window=0)
    with pytest.raises(ValueError, match="grouped"):
        causal_attention(a, jnp.concatenate([b, b], axis=2)[:, :, :2], c)


# ---- a head of half a lane tile (64): the head-major streaming layout ----

HALF_HEADS = [
    pytest.param(group, window, {}, id=f"group{group}-window{window}")
    for group in (1, 4) for window in (None, 200)
]
# four tiles under a group of four, the window narrower than a tile: the
# whole-length scratch at a head of half a lane tile
LONG_HALF_HEADS = [
    pytest.param(4, None, dict(length=512), id="group4-four-tiles"),
    pytest.param(4, 100, dict(length=512),
                 id="group4-four-tiles-window100"),
]


@pytest.mark.parametrize("group, window, shape", HALF_HEADS)
def test_half_lane_head_forward(group, window, shape):
    from elasticdl_tpu.ops.flash_attention import (
        causal_attention,
        stream_shapes_ok,
    )

    q, k, v = _grouped_qkv(group, dim=64, kv_heads=2, **shape)
    assert stream_shapes_ok(q.shape, k.shape, v.shape)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            causal_attention(q, k, v, window=window),
            _dense_band(q, k, v, window), rtol=2e-4, atol=2e-4,
        )


@pytest.mark.parametrize("group, window, shape",
                         HALF_HEADS + LONG_HALF_HEADS)
def test_half_lane_head_gradients(group, window, shape):
    from elasticdl_tpu.ops.flash_attention import causal_attention

    q, k, v = _grouped_qkv(group, dim=64, kv_heads=2, **shape)

    def grads(fn):
        return jax.grad(
            lambda q, k, v: (fn(q, k, v) ** 2).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    with jax.default_matmul_precision("highest"):
        got = grads(lambda q, k, v: causal_attention(q, k, v, window=window))
        want = grads(lambda q, k, v: _dense_band(q, k, v, window))
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(
            a, b, rtol=5e-4, atol=5e-4, err_msg=f"d{name}"
        )


def test_half_lane_head_admission_and_names():
    """Heads of 64 run in the kernels the metrics read by name, not in
    the blocked form; other widths under a lane tile still go blocked."""
    from elasticdl_tpu.ops.flash_attention import (
        flash_shapes_ok,
        stream_shapes_ok,
    )

    q, kv = (4, 8192, 32, 64), (4, 8192, 8, 64)
    assert stream_shapes_ok(q, kv, kv) and flash_shapes_ok(q, kv, causal=True)
    assert not stream_shapes_ok((4, 8192, 32, 32), (4, 8192, 8, 32),
                                (4, 8192, 8, 32))
    assert not stream_shapes_ok((4, 8192, 32, 192), (4, 8192, 8, 192),
                                (4, 8192, 8, 192))
    assert not stream_shapes_ok((4, 8200, 32, 64), (4, 8200, 8, 64),
                                (4, 8200, 8, 64))
    q, k, v = _grouped_qkv(4, length=256, dim=64, kv_heads=2)
    assert _kernel_names(q, k, v, window=None) == [
        "causal_attention_dkv", "causal_attention_fwd",
    ]


# sha256 of str(make_jaxpr(value_and_grad(causal_attention ...))) at the
# cells' bfloat16 shapes: the GLM cell's (4, 4096, 20, 256), the Laguna
# cell's full and window layers, the LFM2 cell's (4, 8192, 32 over 8, 64)
# and the Kimi-Linear cell's keys of 192 over values of 128.  No cell that
# was there can move through `flash_attention.py` while these hold; a
# change that means to move one states the new text.
# RE-RECORDED ON PURPOSE by the change that holds the forward's running
# max and normaliser (tile, 1)-shaped, written as they are and not
# broadcast to 128 lanes at every key step (two scratch shapes and the
# reads and writes of them in the forward's body), and that puts the
# backward's three gathering products last, dQ's first: the same
# equations in another order (the commit before gave 1aa8b25f...,
# f13ce3df..., d3b59eb5..., 30a0a61e... for the first four and
# b8711448... at the Kimi cell's shape, which had no digest here).
# RE-RECORDED ON PURPOSE AGAIN by PR 60, which saves the log-sum-exp
# lane-major: the two kernels run inside the wrappers `_stream_fwd_rows` /
# `_stream_bwd_rows` (one more scratch each, a transpose once a query tile),
# their second output and fifth operand are float32 (B, H, 1, L) where they
# were (B, H, L, 1), and the tile bodies are the same equations (the commit
# before gave c5ef0d80..., 653ef899..., 8b2c32b9..., 34fc6206...,
# 16f9c3e0...; `test_the_lane_major_log_sum_exp_keeps_the_parents_bits`
# holds the results to the parent's bits).
CELL_JAXPRS = [
    pytest.param(
        (4, 4096, 20, 256), 20, 256, None,
        "1100bf7722163bcdf43b3c698f55c59b9a4e50b4ff9723a8c84f93d98269e1e9",
        id="glm-mla",
    ),
    pytest.param(
        (2, 8192, 48, 128), 8, 128, None,
        "54cdad47585364b375d429a8877ce2fa6cb80def94c0905d25776e6e15401e6c",
        id="laguna-full",
    ),
    pytest.param(
        (2, 8192, 64, 128), 8, 128, 512,
        "17457f41ea651c957b36226999608ff1af7b55465ae691f13b26493241b3fe7a",
        id="laguna-window",
    ),
    pytest.param(
        (4, 8192, 32, 64), 8, 64, None,
        "77d5ca0047f2bac358148809ef9e7c9b977d17b9619fb9ee49104da9fd4654b4",
        id="lfm2-gqa",
    ),
    pytest.param(
        (2, 8192, 32, 192), 32, 128, None,
        "0a29f40a19651b95d2682c1d34b1f56fee2a5ec8f01b929094128c79fe632e5d",
        id="kimi-mla",
    ),
]


def _cell_jaxpr_digest(q_shape, kv_heads, v_dim, window):
    import hashlib

    from elasticdl_tpu.ops.flash_attention import causal_attention

    def shaped(heads, dim):
        return jax.ShapeDtypeStruct((*q_shape[:2], heads, dim), jnp.bfloat16)

    text = str(jax.make_jaxpr(jax.value_and_grad(
        lambda q, k, v: causal_attention(
            q, k, v, window=window
        ).astype(jnp.float32).sum(),
        argnums=(0, 1, 2),
    ))(shaped(q_shape[2], q_shape[3]), shaped(kv_heads, q_shape[3]),
       shaped(kv_heads, v_dim)))
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("q_shape, kv_heads, v_dim, window, digest",
                         CELL_JAXPRS)
def test_cells_attention_jaxpr_is_the_parents(q_shape, kv_heads, v_dim,
                                              window, digest):
    assert _cell_jaxpr_digest(q_shape, kv_heads, v_dim, window) == digest


# ---- the forward's results saved across a block's remat -------------------
#
# (query heads, K/V heads, head width, window) over 256 positions: the
# three cells' attention calls in small (a head of 256, grouped heads of
# 128 without and with a window, heads of 64 head-major)
SAVED_SHAPES = [
    pytest.param(2, 2, 256, None, id="width256"),
    pytest.param(4, 2, 128, None, id="width128-grouped"),
    pytest.param(4, 2, 128, 128, id="width128-window"),
    pytest.param(4, 2, 64, None, id="width64-head-major"),
]


def _block_grad_jaxpr(remat, heads, kv_heads, dim, window, length=256):
    """The text of the gradient's jaxpr of projections + `causal_attention`
    + projection, the block rematerialised by `remat`."""
    from elasticdl_tpu.ops.flash_attention import causal_attention

    class Block(nn.Module):
        @nn.compact
        def __call__(self, x):
            def project(count, name):
                return nn.Dense(count * dim, use_bias=False, name=name)(
                    x
                ).reshape(*x.shape[:2], count, dim)

            out = causal_attention(
                project(heads, "q"), project(kv_heads, "k"),
                project(kv_heads, "v"), window=window,
            )
            return x + nn.Dense(x.shape[-1], use_bias=False, name="o")(
                out.reshape(*x.shape[:2], -1)
            )

    block = remat(Block)()
    x = jnp.zeros((1, length, 32), jnp.float32)
    params = jax.eval_shape(block.init, jax.random.PRNGKey(0), x)
    return str(jax.make_jaxpr(jax.grad(
        lambda params, x: block.apply(params, x).sum(), argnums=(0, 1)
    ))(params, x))


@pytest.mark.parametrize("remat, forwards", [
    pytest.param(remat_block, 1, id="names-saved"),
    pytest.param(nn.remat, 2, id="plain-remat"),
])
@pytest.mark.parametrize("heads, kv_heads, dim, window", SAVED_SHAPES)
def test_remat_block_runs_the_forward_kernel_once(
    heads, kv_heads, dim, window, remat, forwards
):
    """Under `remat_block`'s policy a block's gradient holds ONE forward
    kernel an attention layer, under a plain remat two: the guard that the
    names stay on the kernel's own outputs (a name further down, on the
    projected output say, would save nothing the backward reads)."""
    import re

    text = _block_grad_jaxpr(remat, heads, kv_heads, dim, window)
    calls = {
        kernel: len(re.findall(rf"name=\w+_attention_{kernel}\b", text))
        for kernel in ("fwd", "dkv", "dq")
    }
    assert calls == {"fwd": forwards, "dkv": 1, "dq": 0}


@pytest.mark.parametrize("remat, exps", [
    pytest.param(remat_block, 2, id="names-saved"),
    pytest.param(nn.remat, 3, id="plain-remat"),
])
def test_blocked_form_carries_the_same_names(remat, exps):
    """The blocked lax form (shapes that do not tile, export) names the
    same two results, so `remat_block` saves them there too: its one tile
    row's probabilities are built in the forward and in the backward, not
    a third time by the remat."""
    from elasticdl_tpu.ops.flash_attention import (
        SAVED_NAMES,
        blocked_causal_attention,
        causal_attention,
    )

    small, tiled = _grouped_qkv(2, length=64, dim=16), _grouped_qkv(1, 256)
    for attention, qkv, kernel in (
        (blocked_causal_attention, small, False),
        (causal_attention, small, False),
        (causal_attention, tiled, True),
    ):
        forward = str(jax.make_jaxpr(attention)(*qkv))
        assert ("causal_attention_fwd" in forward) == kernel
        for name in SAVED_NAMES:
            assert forward.count(f"name[name={name}]") == 1
    text = _block_grad_jaxpr(remat, 4, 2, 16, None, length=64)
    assert "pallas_call" not in text and text.count("= exp ") == exps


# ---- keys and values of different widths (latent attention) --------------
#
# (key width, value width, heads, K/V heads, window): 192 over 128 is the
# no-position MLA's (128 + 64 key columns, padded to 256 inside the op);
# 256 over 128 needs no padding; 192 grouped and under a window
UNEQUAL = [
    pytest.param(192, 128, 2, 2, None, id="192-over-128"),
    pytest.param(256, 128, 2, 1, None, id="256-over-128-grouped"),
    pytest.param(192, 128, 4, 2, 128, id="192-over-128-grouped-window"),
]


def _dense_unequal(q, k, v, window):
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    t = jnp.arange(q.shape[1])
    seen = t[:, None] >= t[None, :]
    if window is not None:
        seen &= t[:, None] - t[None, :] < window
    weights = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


@pytest.mark.parametrize("dk, dv, heads, kv_heads, window", UNEQUAL)
def test_streaming_kernels_take_a_key_width_of_their_own(dk, dv, heads,
                                                         kv_heads, window):
    """The streaming kernels in interpret mode against the dense masked
    softmax: forward and the three gradients, each at its own width."""
    from elasticdl_tpu.ops.flash_attention import (
        causal_attention,
        stream_shapes_ok,
    )

    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(keys[0], (1, 256, heads, dk)) * 0.3
    k = jax.random.normal(keys[1], (1, 256, kv_heads, dk)) * 0.3
    v = jax.random.normal(keys[2], (1, 256, kv_heads, dv))
    weight = jax.random.normal(keys[3], (1, 256, heads, dv))
    assert stream_shapes_ok(q.shape, k.shape, v.shape)
    assert _kernel_names(q, k, v, window=window) == [
        f"{'causal' if window is None else 'window'}_attention_dkv",
        f"{'causal' if window is None else 'window'}_attention_fwd",
    ]
    np.testing.assert_allclose(
        causal_attention(q, k, v, window=window),
        _dense_unequal(q, k, v, window), rtol=2e-5, atol=2e-5,
    )

    def grads(fn):
        return jax.grad(
            lambda q, k, v: (fn(q, k, v) * weight).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    got = grads(lambda q, k, v: causal_attention(q, k, v, window=window))
    want = grads(lambda q, k, v: _dense_unequal(q, k, v, window))
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(
            a, b, rtol=5e-4, atol=5e-4, err_msg=f"d{name}"
        )


def test_unequal_widths_admission_and_scratch():
    """A value width of whole lane tiles under any key width; a padded key
    counts at its padded width against the backward's whole-length
    scratch (8,192 positions are the most a 256-padded key takes over a
    value of 128 ... and of 256)."""
    from elasticdl_tpu.ops.flash_attention import (
        stream_backward_vmem_bytes,
        stream_shapes_ok,
    )

    q, v = (2, 8192, 32, 192), (2, 8192, 32, 128)
    assert stream_shapes_ok(q, q, v)                         # the cell's
    assert stream_backward_vmem_bytes(8192, 192, 128) == 36 * 1024 * 1024
    assert stream_backward_vmem_bytes(8192, 128) == (
        stream_backward_vmem_bytes(8192, 128, 128)
    )
    assert not stream_shapes_ok((2, 16384, 32, 192), (2, 16384, 32, 192),
                                (2, 16384, 32, 128))         # 72 MiB
    assert not stream_shapes_ok(q, q, (2, 8192, 32, 192))    # v off the lanes
    assert not stream_shapes_ok(q, q, (2, 8192, 32, 64))
    assert not stream_shapes_ok(q, (2, 8192, 32, 128), v)    # k is not q's


# ---- the tile body, bit for bit ------------------------------------------
#
# The reference is the tile body the kernels had before PR 43, as kernels of
# this file run through the module's own calls: every tile masked with both
# compares, every score tile scaled, the running max and normaliser read
# from lane 0 of their scratch and written back broadcast over all of it at
# every key step.  What a change moves out of the (tile, tile) body or
# stops broadcasting may not change a bit of the forward or of the three
# gradients.


def _every_edge_mask(s, i, j, tile, window):
    q_pos = i * tile + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = j * tile + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    seen = q_pos >= k_pos
    if window is not None:
        seen &= q_pos - k_pos < window
    return jnp.where(seen, s, -1e30)


def _every_tile_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc,
                           acc_sc, *, scale, tile, steps, window, **_):
    from jax.experimental import pallas as pl

    from elasticdl_tpu.ops import flash_attention as fa

    i, y = pl.program_id(2), pl.program_id(3)
    j = fa._key_tile(i, y, steps, window)

    @pl.when(y == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, -1e30, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when(fa._key_live(i, j, window))
    def _():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = _every_edge_mask(
            fa._dot(q, k, ((1,), (1,))) * scale, i, j, tile, window
        )
        m_prev = m_sc[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_new = l_sc[:, :1] * correction + p.sum(axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * correction + fa._dot(
            p.astype(v.dtype), v, ((1,), (0,))
        )
        m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[...] = jnp.broadcast_to(l_new, l_sc.shape)

    @pl.when(y == steps - 1)
    def _():
        l = l_sc[:, :1]
        o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_sc[:, :1] + jnp.log(l)


def _every_tile_bwd_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                           dq_ref, dk_ref, dv_ref, dq_sc, dk_sc, dv_sc, *,
                           scale, tile, num, steps, group, window, **_):
    from jax.experimental import pallas as pl

    from elasticdl_tpu.ops import flash_attention as fa

    x, y = pl.program_id(2), pl.program_id(3)
    i = x % num
    j = fa._key_tile(i, y, steps, window)

    @pl.when((x == 0) & (y == 0))
    def _():
        dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)

    @pl.when(y == 0)
    def _():
        dq_sc[...] = jnp.zeros(dq_sc.shape, jnp.float32)

    @pl.when(fa._key_live(i, j, window))
    def _():
        q, k, v, g = q_ref[0], k_ref[0], v_ref[0], g_ref[0]
        s = _every_edge_mask(
            fa._dot(q, k, ((1,), (1,))) * scale, i, j, tile, window
        )
        p = jnp.exp(s - lse_ref[0, 0])
        keys = pl.ds(pl.multiple_of(j * tile, tile), tile)
        dv_sc[keys, :] += fa._dot(p.astype(g.dtype), g, ((0,), (0,)))
        dp = fa._dot(g, v, ((1,), (1,)))
        ds = (p * (dp - delta_ref[0, 0]) * scale).astype(q.dtype)
        dk_sc[keys, :] += fa._dot(ds, q, ((0,), (0,)))
        dq_sc[...] += fa._dot(ds, k, ((1,), (0,)))

    @pl.when(y == steps - 1)
    def _():
        dq_ref[0] = dq_sc[...].astype(dq_ref.dtype)

    @pl.when((x == group * num - 1) & (y == steps - 1))
    def _():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _tile_case(length, window, heads, kv_heads, dk, dv, scale,
               dtype=jnp.bfloat16):
    name = f"L{length}-w{window}-h{heads}over{kv_heads}-d{dk}over{dv}"
    return pytest.param(
        length, window, heads, kv_heads, dk, dv, scale, dtype,
        id=name + (f"-scale{scale}" if scale else "")
        + ("" if dtype == jnp.bfloat16 else f"-{jnp.dtype(dtype).name}"),
    )


# tiles of 512: tiles wholly under the diagonal and the diagonal's own;
# windows with both edges in the diagonal tile (100, 300), an edge on the
# tile grid (512, 1024, 1536: whole tiles inside the band from 1024 on) and
# off it (700); heads of 64 (head-major), 128, 256 and keys of 192 over
# values of 128 (padded to 256 inside the op); scales that are powers of
# two (0.125, 0.0625) and that are not, by the head width's default and
# passed explicitly
TILE_BODY_CASES = [
    _tile_case(1024, None, 2, 1, 128, 128, None),
    _tile_case(2048, None, 2, 1, 128, 128, None),
    _tile_case(2048, None, 2, 1, 128, 128, 0.125),
    _tile_case(2048, None, 2, 2, 128, 128, None, dtype=jnp.float32),
    _tile_case(2048, 100, 2, 1, 128, 128, None),
    _tile_case(2048, 300, 4, 2, 128, 128, None),
    _tile_case(2048, 512, 2, 1, 128, 128, None),
    _tile_case(2048, 700, 2, 1, 128, 128, None),
    _tile_case(2048, 1024, 2, 1, 128, 128, None),
    _tile_case(2048, 1536, 2, 1, 128, 128, 0.125),
    _tile_case(2048, None, 4, 2, 64, 64, None),
    _tile_case(2048, 700, 2, 1, 64, 64, None),
    _tile_case(2048, 1536, 2, 1, 64, 64, 0.1),
    _tile_case(1024, None, 2, 2, 256, 256, None),
    _tile_case(2048, 300, 1, 1, 256, 256, None),
    _tile_case(2048, None, 2, 2, 192, 128, None),
    _tile_case(2048, 1024, 2, 1, 192, 128, 0.0625),
]


class _EagerRef:
    """A kernel's Ref as a plain array read and written operation by
    operation."""

    def __init__(self, value):
        self.value = value

    shape = property(lambda self: self.value.shape)
    dtype = property(lambda self: self.value.dtype)

    @staticmethod
    def _index(idx):
        from jax.experimental import pallas as pl

        idx = idx if isinstance(idx, tuple) else (idx,)
        return tuple(
            slice(t.start, t.start + t.size) if isinstance(t, pl.Slice)
            else t
            for t in idx
        )

    def __getitem__(self, idx):
        return self.value[self._index(idx)]

    def __setitem__(self, idx, new):
        new = jnp.asarray(new, self.value.dtype)
        self.value = self.value.at[self._index(idx)].set(new)


def _eager_stream_call(monkeypatch):
    """`_stream_call` as a Python loop over the grid with every operation
    of the kernel body a computation of its own.  Pallas's interpret mode
    compiles a body as ONE program, and XLA's CPU backend then fuses
    `dot * scale` into what reads it, one rounding for two, differently
    as the body around it differs: a last bit that is the CPU compiler's,
    not the kernels' (on the chip the kernels agree with the parent's to
    the bit at the cells' shapes: `PERF.md` section 6, PR 43).  Here
    nothing is fused, so equal means the same arithmetic."""
    from jax.experimental import pallas as pl

    from elasticdl_tpu.ops import flash_attention as fa

    point = []
    monkeypatch.setattr(pl, "program_id", lambda axis: point[axis])
    monkeypatch.setattr(
        pl, "when", lambda cond: lambda body: body() if bool(cond) else None
    )
    monkeypatch.setattr(pl, "multiple_of", lambda x, _: x)

    def blocks(spec):
        at = spec.index_map(*point)
        return tuple(
            slice(a, a + 1) if size is None else slice(a * size, (a + 1) * size)
            for a, size in zip(at, spec.block_shape)
        ), tuple(
            0 if size is None else slice(None) for size in spec.block_shape
        )

    def call(kernel, grid, in_specs, out_specs, out_shape, scratch, operands,
             name, inner_axes=1, vmem_limit=None):
        outs = [jnp.zeros(shape, dtype) for shape, dtype in out_shape]
        held = [_EagerRef(jnp.zeros(t.shape, t.dtype)) for t in scratch]
        for at in np.ndindex(*grid):
            point[:] = [int(a) for a in at]
            refs = []
            for spec, array in zip(in_specs + out_specs,
                                   list(operands) + outs):
                where, squeeze = blocks(spec)
                refs.append(_EagerRef(array[where][squeeze]))
            kernel(*refs, *held)
            for n, (spec, ref) in enumerate(
                zip(out_specs, refs[len(in_specs):])
            ):
                where, squeeze = blocks(spec)
                outs[n] = outs[n].at[where].set(
                    jnp.expand_dims(ref.value, [
                        axis for axis, how in enumerate(squeeze) if how == 0
                    ])
                )
        return outs

    monkeypatch.setattr(fa, "_stream_call", call)


@pytest.mark.parametrize(
    "length, window, heads, kv_heads, dk, dv, scale, dtype", TILE_BODY_CASES
)
def test_streaming_kernels_keep_the_tile_body_to_the_bit(
    monkeypatch, length, window, heads, kv_heads, dk, dv, scale, dtype
):
    """Forward (output and log-sum-exp) and all three gradients of the
    streaming kernels, BIT for bit against the tile body above, both run
    operation by operation (`_eager_stream_call`), as `causal_attention`
    calls them."""
    from elasticdl_tpu.ops import flash_attention as fa

    keys = jax.random.split(jax.random.PRNGKey(length + dk), 4)
    q = jax.random.normal(keys[0], (1, length, heads, dk), dtype)
    k = jax.random.normal(keys[1], (1, length, kv_heads, dk), dtype)
    v = jax.random.normal(keys[2], (1, length, kv_heads, dv), dtype)
    g = jax.random.normal(keys[3], (1, length, heads, dv), dtype)
    assert fa.stream_shapes_ok(q.shape, k.shape, v.shape)
    assert fa._stream_tiles(length) == 512
    _eager_stream_call(monkeypatch)
    scale = float(dk ** -0.5 if scale is None else scale)
    band = fa._band(window, q)
    padded = (*fa._padded_keys(q, k), v)

    def run():
        out, residuals = fa._stream_fwd(*padded, scale, band)
        return (out, residuals[-1], *fa._stream_bwd(scale, band, residuals, g))

    got = run()
    monkeypatch.setattr(fa, "_stream_fwd_kernel", _every_tile_fwd_kernel)
    monkeypatch.setattr(fa, "_stream_bwd_kernel", _every_tile_bwd_kernel)
    want = run()
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        a, b = (np.asarray(t.astype(jnp.float32)) for t in (a, b))
        assert np.isfinite(b).all() and np.abs(b).max() > 0, name
        assert np.array_equal(a, b), (
            f"{name}: {np.abs(a - b).max()} apart at most, "
            f"{(a != b).sum()} of {a.size} elements differ"
        )
    # and it is the attention the dense form computes
    np.testing.assert_allclose(
        np.asarray(got[0].astype(jnp.float32)),
        np.asarray(_dense_band(
            *(t.astype(jnp.float32) for t in (q, k, v)), window, scale
        )),
        rtol=3e-2, atol=3e-2,
    )


# ---- the saved log-sum-exp lane-major, bit for bit (PR 60) ----------------
#
# The reference is the PARENT's `_stream_fwd` and `_stream_bwd`: the same two
# kernels called bare, over a float32 (B, H, L, 1) column that the chip tiles
# to 128 times its values.  The module now wraps them (`_stream_fwd_rows`,
# `_stream_bwd_rows`) and saves a (B, H, 1, L) row; the turn between row and
# column may not change a bit of the output, the log-sum-exp's values or the
# three gradients.


def _column_fwd(fa, q, k, v, scale, window):
    from jax.experimental.pallas import tpu as pltpu

    batch, length, heads, dim = q.shape
    v_dim, group = v.shape[3], heads // k.shape[2]
    tile = fa._stream_tiles(length)
    num = length // tile
    steps = fa._band_steps(num, tile, window)
    tiles, per_row, _ = fa._stream_specs(tile)
    keys, kv_head = fa._streamed_keys(steps, window), fa._kv_head(group)
    out, lse = fa._stream_call(
        functools.partial(
            fa._stream_fwd_kernel, scale=scale, tile=tile, steps=steps,
            window=window,
        ),
        (batch, heads, num, steps),
        [tiles(dim, fa._resident_row), tiles(dim, keys, kv_head),
         tiles(v_dim, keys, kv_head)],
        [tiles(v_dim, fa._resident_row), per_row(fa._resident_row)],
        [(fa._stream_shape((batch, length, heads, v_dim)), q.dtype),
         ((batch, heads, length, 1), jnp.float32)],
        [pltpu.VMEM((tile, 1), jnp.float32),
         pltpu.VMEM((tile, 1), jnp.float32),
         pltpu.VMEM((tile, v_dim), jnp.float32)],
        [fa._stream_view(t) for t in (q, k, v)],
        fa._stream_names(window) + "_fwd",
    )
    return fa._stream_unview(out, (batch, length, heads, v_dim)), lse


def _column_bwd(fa, q, k, v, out, lse, g, scale, window):
    from jax.experimental.pallas import tpu as pltpu

    batch, length, heads, dim = q.shape
    kv_heads, v_dim = k.shape[2], v.shape[3]
    group = heads // kv_heads
    tile = fa._stream_tiles(length)
    num = length // tile
    steps = fa._band_steps(num, tile, window)
    tiles, per_row, _ = fa._stream_specs(tile)
    g = g.astype(q.dtype)
    delta = (
        (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
        .transpose(0, 2, 1)[..., None]
    )
    streamed = fa._streamed_keys(steps, window)

    def row(x, y):
        return x % num

    def q_head(h, x, y):
        return h * group + x // num

    def keys(x, y):
        return streamed(row(x, y), y)

    def whole(x, y):
        return 0

    dq, dk, dv = fa._stream_call(
        functools.partial(
            fa._stream_bwd_kernel, scale=scale, tile=tile, num=num,
            steps=steps, group=group, window=window,
        ),
        (batch, kv_heads, group * num, steps),
        [tiles(dim, row, q_head), tiles(dim, keys), tiles(v_dim, keys),
         tiles(v_dim, row, q_head), per_row(row, q_head),
         per_row(row, q_head)],
        [tiles(dim, row, q_head), tiles(dim, whole, rows=length),
         tiles(v_dim, whole, rows=length)],
        [(fa._stream_shape(q.shape), q.dtype),
         (fa._stream_shape(k.shape), k.dtype),
         (fa._stream_shape(v.shape), v.dtype)],
        [pltpu.VMEM((tile, dim), jnp.float32),
         pltpu.VMEM((length, dim), jnp.float32),
         pltpu.VMEM((length, v_dim), jnp.float32)],
        [fa._stream_view(t) for t in (q, k, v, g)] + [lse, delta],
        fa._stream_names(window) + "_dkv", inner_axes=2,
        vmem_limit=fa._STREAM_VMEM_LIMIT,
    )
    return (
        fa._stream_unview(dq, q.shape), fa._stream_unview(dk, k.shape),
        fa._stream_unview(dv, v.shape),
    )


# the cells' calls in small, two tiles of 512: a group of 1 (Ouro, the MLA
# cores), of 7 (SmallThinker) and of 8 (Laguna); a band and none; heads of
# 64 (head-major), 128 and 256; MLA's keys of 192, padded to 256, over
# values of 128; two sequences, so that a second batch's rows are their own
LANE_MAJOR_CASES = [
    _tile_case(1024, None, 2, 2, 128, 128, None),
    _tile_case(1024, 700, 7, 1, 128, 128, None),
    _tile_case(1024, None, 7, 1, 128, 128, None),
    _tile_case(1024, None, 8, 1, 128, 128, None),
    _tile_case(1024, 300, 8, 1, 128, 128, None),
    _tile_case(1024, None, 4, 1, 64, 64, None),
    _tile_case(1024, 600, 4, 2, 64, 64, None),
    _tile_case(1024, None, 2, 2, 256, 256, None),
    _tile_case(1024, 512, 2, 1, 256, 256, None),
    _tile_case(1024, None, 2, 2, 192, 128, None),
    _tile_case(1024, None, 2, 2, 128, 128, None, dtype=jnp.float32),
]


@pytest.mark.parametrize(
    "length, window, heads, kv_heads, dk, dv, scale, dtype", LANE_MAJOR_CASES
)
def test_the_lane_major_log_sum_exp_keeps_the_parents_bits(
    monkeypatch, length, window, heads, kv_heads, dk, dv, scale, dtype
):
    """`out`, the log-sum-exp's values, `dq`, `dk` and `dv` of the module's
    streaming forward and backward, BIT for bit against the same kernels
    over the parent's (B, H, L, 1) column, both run operation by
    operation; and what a block holds from forward to backward is the
    (B, H, 1, L) row: 8 times its values as the chip tiles it, not 128."""
    from elasticdl_tpu.ops import flash_attention as fa
    from model_zoo.common.decoder import tiled_bytes

    keys = jax.random.split(jax.random.PRNGKey(heads + dk), 4)
    q = jax.random.normal(keys[0], (2, length, heads, dk), dtype)
    k = jax.random.normal(keys[1], (2, length, kv_heads, dk), dtype)
    v = jax.random.normal(keys[2], (2, length, kv_heads, dv), dtype)
    g = jax.random.normal(keys[3], (2, length, heads, dv), dtype)
    assert fa.stream_shapes_ok(q.shape, k.shape, v.shape)
    _eager_stream_call(monkeypatch)
    scale, band = float(dk ** -0.5), fa._band(window, q)
    q, k = fa._padded_keys(q, k)

    out, residuals = fa._stream_fwd(q, k, v, scale, band)
    lse = residuals[-1]
    assert lse.shape == (2, heads, 1, length) and lse.dtype == jnp.float32
    got = (out, lse[:, :, 0], *fa._stream_bwd(scale, band, residuals, g))
    out, column = _column_fwd(fa, q, k, v, scale, band)
    assert column.shape == (2, heads, length, 1)
    want = (
        out, column[..., 0],
        *_column_bwd(fa, q, k, v, out, column, g, scale, band),
    )
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        a, b = (np.asarray(t.astype(jnp.float32)) for t in (a, b))
        assert np.isfinite(b).all() and np.abs(b).max() > 0, name
        assert np.array_equal(a, b), (
            f"{name}: {np.abs(a - b).max()} apart at most, "
            f"{(a != b).sum()} of {a.size} elements differ"
        )

    class Value:
        def __init__(self, array):
            self.aval = jax.ShapeDtypeStruct(array.shape, array.dtype)

    values = 2 * heads * length * 4
    assert tiled_bytes([Value(lse)]) == 8 * values
    assert tiled_bytes([Value(column)]) == 128 * values
