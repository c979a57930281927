"""The streaming attention kernels compiled for the chip WITHOUT the chip:
the TPU's compiler is installed here and compiles for a described v5e, so
what Mosaic refuses (a slice off the tiling, too much fast memory) fails
here and not in a chip call.  Interpret-mode tests cannot show that.
Nothing runs, so nothing here is a result or a time.

The topology is described inside a fixture, never at import: only one
process may hold the TPU's library, and every xdist worker imports every
test file.  Keep such tests in THIS file."""

import math
import re

import jax
import jax.numpy as jnp
import pytest

from elasticdl_tpu.ops import flash_attention as fa


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable for a described chip is written to the persistent
    # cache and cannot be read back without one: keep it out
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


# (q heads, K/V heads, head width, batch, length, window): the GLM cell's
# call, the Laguna cell's full layers and its window layers
SHAPES = [
    pytest.param(20, 20, 256, 4, 4096, None, id="glm-mla"),
    pytest.param(48, 8, 128, 2, 8192, None, id="laguna-full"),
    pytest.param(64, 8, 128, 2, 8192, 512, id="laguna-window"),
    # the Ouro cell's call, every application of 24: a group of ONE at
    # heads of 128 (MLA's cores are the only other group-of-one users)
    pytest.param(16, 16, 128, 1, 8192, None, id="ouro-mha"),
]
# the SmallThinker cell's calls: ONE sequence of 16,384, the last length the
# rule admits at heads of 128, a group of SEVEN query heads a K/V head, no
# band and a band of nine key tiles
SHAPES_16K = [
    pytest.param(28, 4, 128, 1, 16384, None, id="smallthinker-full"),
    pytest.param(28, 4, 128, 1, 16384, 4096, id="smallthinker-window"),
]
# the LFM2 cell's call (a head of half a lane tile, head-major)
LFM2_SHAPE = (32, 8, 64, 4, 8192, None)


def _backward_text(one_chip, heads, kv_heads, dim, batch, length, window,
                   dtype=jnp.bfloat16):
    """The compiled text of `causal_attention`'s gradient at a shape."""
    def shaped(h):
        return jax.ShapeDtypeStruct(
            (batch, length, h, dim), dtype, sharding=one_chip
        )

    def grads(q, k, v):
        return jax.grad(
            lambda q, k, v: fa.causal_attention(
                q, k, v, window=window
            ).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    return jax.jit(grads).lower(
        shaped(heads), shaped(kv_heads), shaped(kv_heads)
    ).compile().as_text()


def _assert_two_kernels(text, kind):
    """A forward kernel and ONE backward kernel, named `_dkv` for the
    metrics' rules although it carries dQ; no `_dq` call."""
    for kernel in ("fwd", "dkv"):
        assert f"{kind}_attention_{kernel}" in text
    assert "_attention_dq" not in text
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize(
    "heads, kv_heads, dim, batch, length, window", SHAPES + SHAPES_16K
)
def test_streaming_kernels_compile_for_the_chip(
    one_chip, monkeypatch, heads, kv_heads, dim, batch, length, window
):
    # the kernels ask the default backend (the CPU here) whether to run
    # interpreted: steer them to Mosaic for the described chip
    monkeypatch.setattr(fa, "use_interpret", lambda: False)
    text = _backward_text(
        one_chip, heads, kv_heads, dim, batch, length, window
    )
    _assert_two_kernels(text, "causal" if window is None else "window")
    # `causal_attention` takes the streaming kernels at this shape of its
    # own accord: no silent `blocked_causal_attention`
    assert fa.stream_shapes_ok(
        (batch, length, heads, dim), (batch, length, kv_heads, dim),
        (batch, length, kv_heads, dim),
    )
    # K/V stay at their own head count: nothing repeated to the query's
    assert f"bf16[{batch},{length},{kv_heads * dim}]" in text
    # (at 16,384 the backward holds 16 MiB of float32 dK and dV scratch
    # and, in bfloat16, 16 MiB of double-buffered output blocks, 32 of the
    # 48 MiB `stream_backward_vmem_bytes` reckons at float32: the compile
    # above ran under `_STREAM_VMEM_LIMIT`)
    assert fa.stream_backward_vmem_bytes(length, dim) <= (
        fa._STREAM_VMEM_LIMIT - fa._STREAM_TILE_ROOM
    )


def test_half_lane_head_kernels_compile_for_the_chip(one_chip, monkeypatch):
    """The LFM2 cell's attention: 32 query heads of 64 over 8 K/V heads at
    (4, 8192), the head-major layout of a head of half a lane tile."""
    monkeypatch.setattr(fa, "use_interpret", lambda: False)
    text = _backward_text(one_chip, *LFM2_SHAPE)
    _assert_two_kernels(text, "causal")
    # K/V go head-major at their own head count: nothing repeated to 32
    assert "bf16[4,8,8192,64]" in text and "bf16[4,32,8192,64]" in text


# (beside the attention kernels it holds and apart from the two other whole
# steps below: xdist deals the end of a run two tests at a time, and a pair
# then never holds two whole-step compiles)
def test_ouro_cell_step_compiles_for_the_chip(one_chip, monkeypatch):
    """The Ouro cell's whole train step (ONE sequence of 8,192, six layers
    at the published widths run four times over one set of weights, Adam,
    the lean remat policy: no room given) compiled for the described v5e,
    abstract: the streaming kernels at a group of ONE at heads of 128
    inside the trips' loop, and what the configuration's `eight_layers`
    states: the step's arguments and temporaries under ISSUE 59's line.
    (PR 59 measured this compile and left it out: the tier-1 run stood at
    1,414 s of its 1,470.)"""
    from model_zoo.ouro import ouro as zoo

    monkeypatch.setattr(fa, "use_interpret", lambda: False)
    compiled = _cell_train_step(
        one_chip, zoo, _cell_config("ouro-2.6b"), (1, 8192)
    )
    text = compiled.as_text()
    _assert_two_kernels(text, "causal")
    # q, k and v at their 16 heads of 128: one K/V head a query head
    kernel = next(
        line for line in text.splitlines()
        if "causal_attention_dkv" in line and "custom-call(" in line
    )
    assert kernel.count("bf16[1,8192,2048]") >= 3
    # the trips are a loop of the program, not four copies of the stack:
    # six layers' calls of each kernel, not twenty-four
    assert " while(" in text
    for kernel in ("fwd", "dkv"):
        assert len(re.findall(
            rf"custom-call\(.*causal_attention_{kernel}", text
        )) == 6, kernel
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    # `eight_layers`: 6.116e9 of arguments (12 bytes a parameter) + 5.432e9
    # of temporaries = 11.548e9 at six layers (6.070e9 of temporaries
    # before the final norm inside the loop was rematerialised too: PR 59),
    # under ISSUE 59's line of 14.5e9; 5.750e9 and 11.866e9 since PR 62,
    # whose cross-entropy holds the states' gradient in float32 from the
    # forward (with room, on the chip, the peak did not move: PERF.md)
    assert memory.argument_size_in_bytes == pytest.approx(6.116e9, rel=1e-3)
    assert 11.2e9 < held < 12.0e9, held


# a window of any width: both edges in the diagonal tile, an edge off the
# tile grid, whole tiles inside the band; at a head of 64 (head-major) and
# of 128
ANY_WINDOW = [
    pytest.param(window, dim, id=f"window{window}-width{dim}")
    for window in (100, 700, 1536) for dim in (64, 128)
]


@pytest.mark.parametrize("window, dim", ANY_WINDOW)
def test_a_window_of_any_width_compiles_for_the_chip(
    one_chip, monkeypatch, window, dim
):
    monkeypatch.setattr(fa, "use_interpret", lambda: False)
    text = _backward_text(one_chip, 8, 2, dim, 1, 4096, window)
    _assert_two_kernels(text, "window")


MIB = 1024 * 1024


@pytest.mark.parametrize(
    "heads, kv_heads, dim, batch, length, window",
    SHAPES + [pytest.param(*LFM2_SHAPE, id="lfm2-gqa")],
)
def test_backward_scratch_of_the_cells_under_the_vmem_limit(
    one_chip, monkeypatch, heads, kv_heads, dim, batch, length, window
):
    """The backward kernel holds dK and dV of a K/V head's WHOLE length in
    VMEM: 2 x L x D float32 of scratch and two (L, D) output blocks,
    double-buffered.  The four cells' shapes, reckoned from (L, D): 8 MiB
    of scratch each (LFM2's rows of 64 pad to a lane tile), 24 with the
    blocks at float32, under the limit the call names less the tiles'
    room.  The compiles above run under that limit: named 16 MiB, the
    compiler's own default, the same kernel is refused."""
    assert fa._STREAM_VMEM_LIMIT == 64 * MIB
    assert fa._STREAM_TILE_ROOM == 16 * MIB
    scratch = 2 * length * max(dim, 128) * 4
    assert scratch == 8 * MIB
    assert fa.stream_backward_vmem_bytes(length, dim) == 3 * scratch
    assert 3 * scratch <= fa._STREAM_VMEM_LIMIT - fa._STREAM_TILE_ROOM
    monkeypatch.setattr(fa, "use_interpret", lambda: False)
    # admitted all the same (the rule would send it the blocked way)
    monkeypatch.setattr(fa, "_STREAM_VMEM_LIMIT", 16 * MIB)
    monkeypatch.setattr(fa, "stream_shapes_ok", lambda *shapes: True)
    with pytest.raises(Exception, match="vmem"):
        _backward_text(one_chip, heads, kv_heads, dim, batch, length, window)


def test_a_longer_sequence_fails_here_and_not_on_the_chip(
    one_chip, monkeypatch
):
    """The longest the streaming kernels admit, 16,384 positions at a head
    of 128 in float32 (48 MiB for the whole length), compiles under the
    limit; twice that is refused by `stream_shapes_ok` (96 MiB), so the
    call takes the blocked form and no kernel is asked for what the chip
    would refuse."""
    monkeypatch.setattr(fa, "use_interpret", lambda: False)
    edge, past = (1, 16384, 2, 128), (1, 32768, 2, 128)
    assert fa.stream_backward_vmem_bytes(16384, 128) == 48 * MIB
    assert fa.stream_shapes_ok(edge, (1, 16384, 1, 128), (1, 16384, 1, 128))
    assert not fa.stream_shapes_ok(
        past, (1, 32768, 1, 128), (1, 32768, 1, 128)
    )
    assert fa.stream_shapes_ok((1, 8192, 2, 256), (1, 8192, 2, 256),
                               (1, 8192, 2, 256))
    assert not fa.stream_shapes_ok((1, 16384, 2, 256), (1, 16384, 2, 256),
                                   (1, 16384, 2, 256))
    text = _backward_text(one_chip, 2, 1, 128, 1, 16384, None, jnp.float32)
    _assert_two_kernels(text, "causal")


def test_short_conv_kernels_compile_for_the_chip(one_chip, monkeypatch):
    """The LFM2 cell's conv pass: (4, 8192, 3 x 2048) under 3 taps, the
    forward alone (a gradient drops it) and the backward."""
    from elasticdl_tpu.ops import short_conv

    monkeypatch.setattr(short_conv, "use_interpret", lambda: False)
    bcu = jax.ShapeDtypeStruct(
        (4, 8192, 6144), jnp.bfloat16, sharding=one_chip
    )
    weight = jax.ShapeDtypeStruct((3, 2048), jnp.float32, sharding=one_chip)
    assert short_conv.short_conv_shapes_ok(bcu.shape, weight.shape)
    forward = jax.jit(short_conv.gated_short_conv).lower(
        bcu, weight
    ).compile().as_text()
    assert "short_conv_fwd" in forward and "tpu_custom_call" in forward
    assert "bf16[4,8192,2048]" in forward

    def grads(bcu, weight):
        return jax.grad(
            lambda a, b: short_conv.gated_short_conv(a, b).astype(
                jnp.float32
            ).sum(), argnums=(0, 1),
        )(bcu, weight)

    backward = jax.jit(grads).lower(bcu, weight).compile().as_text()
    assert "short_conv_bwd" in backward and "tpu_custom_call" in backward
    # d(weight) leaves the kernel as one (8, d) partial a program
    assert "f32[4,32,8,2048]" in backward


def test_kimi_cell_kernels_compile_for_the_chip(one_chip, monkeypatch):
    """The Kimi-Linear cell's three new calls at its shapes: the chunked
    scan (2, 8192, 32 heads of 128), the SiLU conv over the fused q | k |
    v (2, 8192, 12288) under 4 taps, and latent attention at keys of 192
    over values of 128 (padded to 256 INSIDE the op: v keeps its width)."""
    from elasticdl_tpu.ops import kda, short_conv

    for module in (fa, kda, short_conv):
        monkeypatch.setattr(module, "use_interpret", lambda: False)

    def shaped(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def text_of(fn, *args):
        def grads(*args):
            return jax.grad(
                lambda *a: fn(*a).astype(jnp.float32).sum(),
                argnums=tuple(range(len(args))),
            )(*args)

        return jax.jit(grads).lower(*args).compile().as_text()

    head = (2, 8192, 32, 128)
    assert kda.kda_shapes_ok(head, head, head)
    scan = text_of(
        kda.kda, shaped(head), shaped(head), shaped(head),
        shaped(head, jnp.float32), shaped(head[:3], jnp.float32),
    )
    assert "kda_chunk_fwd" in scan and "kda_chunk_bwd" in scan
    # the states the chunks start from, float32, transposed (dv, dk)
    assert "f32[2,32,128,128,128]" in scan

    u, taps = (2, 8192, 12288), (4, 12288)
    assert short_conv.silu_conv_shapes_ok(u, taps)
    conv = text_of(
        short_conv.silu_short_conv, shaped(u), shaped(taps, jnp.float32)
    )
    assert "silu_short_conv_bwd" in conv and "tpu_custom_call" in conv
    forward = jax.jit(short_conv.silu_short_conv).lower(
        shaped(u), shaped(taps, jnp.float32)
    ).compile().as_text()
    assert "silu_short_conv_fwd" in forward

    q, v = (2, 8192, 32, 192), (2, 8192, 32, 128)
    assert fa.stream_shapes_ok(q, q, v)
    attention = text_of(
        fa.causal_attention, shaped(q), shaped(q), shaped(v)
    )
    _assert_two_kernels(attention, "causal")
    # keys at 256 columns a head, values and the output at their own 128
    assert "bf16[2,8192,8192]" in attention
    assert "bf16[2,8192,4096]" in attention


def test_granite_cell_kernels_compile_for_the_chip(one_chip, monkeypatch):
    """The granite cell's three calls at its shapes: the state-space scan
    (1, 8192, 64 heads of 64 over 128 state columns, ONE B and C a
    token), the biased SiLU conv over x | B | C (1, 8192, 4352) under 4
    taps, and attention at 32 / 8 heads of 64 (head-major)."""
    from elasticdl_tpu.ops import short_conv, ssd

    for module in (fa, ssd, short_conv):
        monkeypatch.setattr(module, "use_interpret", lambda: False)

    def shaped(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def text_of(fn, *args):
        def grads(*args):
            return jax.grad(
                lambda *a: fn(*a).astype(jnp.float32).sum(),
                argnums=tuple(range(len(args))),
            )(*args)

        return jax.jit(grads).lower(*args).compile().as_text()

    x, shared = (1, 8192, 64, 64), (1, 8192, 1, 128)
    assert ssd.ssd_shapes_ok(x, shared)
    scan = text_of(
        ssd.ssd, shaped(x), shaped(x[:3], jnp.float32),
        shaped((64,), jnp.float32), shaped(shared), shaped(shared),
        shaped((64,), jnp.float32),
    )
    assert "ssd_fwd" in scan and "ssd_bwd" in scan
    # the states the 32 chunks start from, float32, all heads' rows
    assert "f32[1,32,4096,128]" in scan

    u, taps = (1, 8192, 4352), (4, 4352)
    assert short_conv.silu_conv_shapes_ok(u, taps, True)
    conv = text_of(
        short_conv.silu_short_conv, shaped(u), shaped(taps, jnp.float32),
        shaped(taps[1:], jnp.float32),
    )
    assert "silu_short_conv_bwd" in conv and "tpu_custom_call" in conv
    # the taps' four rows and the bias's one of every program's partial
    assert "f32[1,32,8,4352]" in conv
    forward = jax.jit(short_conv.silu_short_conv).lower(
        shaped(u), shaped(taps, jnp.float32), shaped(taps[1:], jnp.float32)
    ).compile().as_text()
    assert "silu_short_conv_fwd" in forward

    q, kv = (1, 8192, 32, 64), (1, 8192, 8, 64)
    assert fa.stream_shapes_ok(q, kv, kv)
    attention = text_of(
        lambda q, k, v: fa.causal_attention(q, k, v, scale=0.015625),
        shaped(q), shaped(kv), shaped(kv),
    )
    _assert_two_kernels(attention, "causal")


# (apart from the three other whole steps, for the reason given above the
# Ouro cell's)
def test_olmo_hybrid_cell_step_compiles_for_the_chip(one_chip, monkeypatch):
    """The Olmo-Hybrid cell's whole train step (ONE sequence of 8,192, four
    layers at the published widths, heads 0-9 of 30 held, Adam, the lean
    remat policy: no room given) compiled for the described v5e, abstract:
    the scalar-decay scan's kernels at ten heads of 96 | 192 PADDED to 128
    | 256 (the state a head (256, 128)), the SiLU conv over 3,840 columns,
    attention at 10 / 10 heads of 128; and what the configuration's
    `fifteen_heads` states: the step's arguments and temporaries under
    ISSUE 64's line."""
    from elasticdl_tpu.ops import gdn, short_conv
    from model_zoo.olmo_hybrid import olmo_hybrid as zoo

    for module in (fa, gdn, short_conv):
        monkeypatch.setattr(module, "use_interpret", lambda: False)
    compiled = _cell_train_step(
        one_chip, zoo, _cell_config("olmo-hybrid-7b"), (1, 8192)
    )
    text = compiled.as_text()
    assert "gdn_chunk_fwd" in text and "gdn_chunk_bwd" in text
    assert not re.search(r"kda_\w*(fwd|bwd)", text)
    # q and k at ten heads of 128, v at ten of 256: 96 | 192 padded
    kernel = next(
        line for line in text.splitlines()
        if "gdn_chunk_bwd" in line and "custom-call(" in line
    )
    assert "bf16[1,8192,1280]" in kernel and "bf16[1,8192,2560]" in kernel
    assert "f32[1,10,128,2,64]" in kernel
    assert "f32[1,10,128,256,128]" in text
    assert "silu_short_conv_fwd" in text and "silu_short_conv_bwd" in text
    assert "bf16[1,8192,3840]" in text
    _assert_two_kernels(text, "causal")
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    # `fifteen_heads`: 8.546e9 of arguments (12 bytes a parameter) +
    # 2.369e9 of temporaries = 10.915e9 at ten heads, under the 14.5e9 line
    assert memory.argument_size_in_bytes == pytest.approx(8.546e9, rel=1e-3)
    assert 10.5e9 < held < 11.4e9, held


def test_nemotron_cell_kernels_compile_for_the_chip(one_chip, monkeypatch):
    """The Nemotron cell's four calls at its shapes: the state-space scan
    at EIGHT groups (2, 8192, 64 heads of 64 over 128 state columns, grid
    step t group t), the biased SiLU conv over x | B | C (2, 8192, 6144)
    under 4 taps, attention at 32 / 2 heads of 128 (16 query heads a K/V
    head), and one routed layer of eight held squared-ReLU experts 1,856
    wide through `routed_walk` in the `relu2` form."""
    from elasticdl_tpu.layers import moe
    from elasticdl_tpu.ops import short_conv, ssd

    for module in (fa, ssd, short_conv):
        monkeypatch.setattr(module, "use_interpret", lambda: False)

    def shaped(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def text_of(fn, *args, argnums=None):
        def grads(*args):
            return jax.grad(
                lambda *a: fn(*a).astype(jnp.float32).sum(),
                argnums=argnums or tuple(range(len(args))),
            )(*args)

        return jax.jit(grads).lower(*args).compile().as_text()

    x, shared = (2, 8192, 64, 64), (2, 8192, 8, 128)
    assert ssd.ssd_shapes_ok(x, shared)
    scan = text_of(
        ssd.ssd, shaped(x), shaped(x[:3], jnp.float32),
        shaped((64,), jnp.float32), shaped(shared), shaped(shared),
        shaped((64,), jnp.float32),
    )
    assert "ssd_fwd" in scan and "ssd_bwd" in scan
    # B, C and their gradients at every group's columns, nothing repeated
    # to the heads; the states the 32 chunks start from, all heads' rows
    assert "bf16[2,8192,1024]" in scan
    assert "f32[2,32,4096,128]" in scan

    u, taps = (2, 8192, 6144), (4, 6144)
    assert short_conv.silu_conv_shapes_ok(u, taps, True)
    conv = text_of(
        short_conv.silu_short_conv, shaped(u), shaped(taps, jnp.float32),
        shaped(taps[1:], jnp.float32),
    )
    assert "silu_short_conv_bwd" in conv and "tpu_custom_call" in conv

    q, kv = (2, 8192, 32, 128), (2, 8192, 2, 128)
    assert fa.stream_shapes_ok(q, kv, kv)
    attention = text_of(
        lambda q, k, v: fa.causal_attention(q, k, v, scale=128 ** -0.5),
        shaped(q), shaped(kv), shaped(kv),
    )
    _assert_two_kernels(attention, "causal")
    # K/V stay two heads wide: nothing repeated to the 32 query heads
    assert "bf16[2,8192,256]" in attention

    tokens, hidden, width, top_k, held = 16384, 2688, 1856, 6, 8
    walk = text_of(
        lambda t, up, down, order, weights, sizes: moe.routed_walk(
            t, up, down, order, weights, sizes, moe.RELU2
        ),
        shaped((tokens, hidden)), shaped((held, hidden, width)),
        shaped((held, width, hidden)), shaped((tokens * top_k,), jnp.int32),
        shaped((tokens * top_k,), jnp.float32), shaped((held,), jnp.int32),
        argnums=(0, 1, 2, 4),
    )
    # two products an expert forward (dead here: only gradients leave),
    # rebuilt in the backward, two to the rows and one to each stack: six
    # grouped kernels, the up stack's at the expert's own width (no gate
    # beside it) padded to whole tiles (`moe.TILE`), which the compiler
    # then tiles no narrower than that
    ffn = moe._whole(width)
    assert walk.count(f"= bf16[16384,{ffn}]") >= 2
    assert "= bf16[16384,1856]" not in walk
    assert f"bf16[16384,{2 * ffn}]" not in walk
    tilings = re.findall(r'ragged_dot_tiling="(\d+),(\d+),(\d+)"', walk)
    assert len(tilings) >= 6
    assert all(int(t) >= moe.TILE for tiling in tilings for t in tiling)


def _cell_train_step(one_chip, zoo, config, batch=(2, 8192)):
    """A cell's whole train step (`batch` sequences by positions, the
    configuration's model and Adam, the lean remat policy: no room given)
    compiled for the described v5e, abstract."""
    import optax

    from elasticdl_tpu.common.model_handler import _call_with_params
    from elasticdl_tpu.layers.moe import ROUTER_STATE
    from elasticdl_tpu.layers.step_metrics import AUX_LOSS, STEP_METRICS

    model = _call_with_params(
        zoo.custom_model, config["model_params"].format(**config)
    )
    optimizer = zoo.optimizer(config["learning_rate"])
    ids = jax.ShapeDtypeStruct(batch, jnp.int32)
    state = dict(jax.eval_shape(
        model.init, jax.random.PRNGKey(0), {"input_ids": ids}
    ))
    params = state.pop("params")

    def step(params, state, opt_state, ids):
        def loss_of(params):
            out, _ = model.apply(
                {"params": params, **state}, {"input_ids": ids},
                mutable=[AUX_LOSS, STEP_METRICS, ROUTER_STATE],
            )
            return zoo.loss(None, out.astype(jnp.float32))

        loss, grads = jax.value_and_grad(loss_of)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip
        ), tree)

    return jax.jit(step, donate_argnums=(0, 2)).lower(
        placed(params), placed(state),
        placed(jax.eval_shape(optimizer.init, params)), placed(ids),
    ).compile()


def _cell_config(name):
    import json
    import os

    with open(os.path.join(
        os.path.dirname(__file__), "..", "benchmarks", "configs",
        f"{name}.json",
    )) as f:
        return json.load(f)


def _assert_routed_once_a_layer(text, layers):
    """The chip's `top_k` is a SORT of every token's scores, whole rows of
    (tokens, experts), and the dispatch sorts the slots: the compiled step
    holds one of each a routed layer, the forward's, and none under
    `rematted_computation` (a remat keeps what the backward reads of the
    routing by name, `layers/moe.py: SAVED_NAMES`; the step held two of
    each a layer before PR 65), nor a second router product there."""
    for kind in ("router/top_k", "dispatch/jit(argsort)/sort"):
        sorts = [
            line for line in text.splitlines()
            if " sort(" in line and f'{kind}"' in line
        ]
        assert len(sorts) == layers, (kind, len(sorts))
        assert not [s for s in sorts if "rematted_computation" in s]
    assert not [
        line for line in text.splitlines()
        if "rematted_computation" in line
        and re.search(r'router/(dot_general|top_k|scatter-add)"', line)
    ]


def test_qwen3_next_cell_step_compiles_for_the_chip(one_chip, monkeypatch):
    """The Qwen3-Next cell's whole train step (2 sequences of 8,192, four
    layers at the published widths, 32 of 512 experts held, Adam, the lean
    remat policy: no room given) compiled for the described v5e, abstract:
    the scalar-decay scan's kernels at 32 value heads over 16 key heads
    (q and k enter at 2,048 columns: no repeated copy), the SiLU conv over
    8,192 columns, attention at 16 / 2 heads of 256, the routed walk at
    163,840 slots; and what the configuration's `fifth_layer` states: the
    step's arguments and temporaries under 0.9 of the chip's bytes."""
    from elasticdl_tpu.ops import gdn, short_conv
    from model_zoo.qwen3_next import qwen3_next as zoo

    for module in (fa, gdn, short_conv):
        monkeypatch.setattr(module, "use_interpret", lambda: False)
    compiled = _cell_train_step(
        one_chip, zoo, _cell_config("qwen3-next-80b-a3b")
    )
    text = compiled.as_text()
    assert "gdn_chunk_fwd" in text and "gdn_chunk_bwd" in text
    assert not re.search(r"kda_\w*(fwd|bwd)", text)
    # q and k at their 16 key heads, the states a value head, g over b as
    # rows of a chunk
    kernel = next(
        line for line in text.splitlines()
        if "gdn_chunk_bwd" in line and "custom-call(" in line
    )
    assert "bf16[2,8192,2048]" in kernel and "bf16[2,8192,4096]" in kernel
    assert "f32[2,32,128,2,64]" in kernel
    assert "f32[2,32,128,128,128]" in text
    assert "silu_short_conv_fwd" in text and "silu_short_conv_bwd" in text
    _assert_two_kernels(text, "causal")
    assert "ragged-dot" in text and "s32[163840]" in text
    _assert_routed_once_a_layer(text, layers=4)
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    # `fifth_layer`: 7.508e9 + 5.548e9 = 13.056e9 of at most 15.2e9
    assert 12.5e9 < held < 0.9 * 16_909_336_064, held


def test_smallthinker_cell_step_compiles_for_the_chip(one_chip, monkeypatch):
    """The SmallThinker cell's whole train step (ONE sequence of 16,384,
    four layers at the published widths, 8 of 64 experts held, Adam, the
    lean remat policy: no room given) compiled for the described v5e,
    abstract: both attention kinds' streaming kernels at a group of seven
    (one full layer, three band layers: a forward and ONE backward kernel
    each), the `reglu` walk at 98,304 slots with every product at whole
    tiles; and what the configuration's `eight_layers` states: the step's
    arguments and temporaries."""
    from elasticdl_tpu.layers import moe
    from model_zoo.smallthinker import smallthinker as zoo

    monkeypatch.setattr(fa, "use_interpret", lambda: False)
    compiled = _cell_train_step(
        one_chip, zoo, _cell_config("smallthinker-21b-a3b"), (1, 16384)
    )
    text = compiled.as_text()
    _assert_two_kernels(text, "causal")
    _assert_two_kernels(text, "window")
    # q at 28 heads of 128, K/V at their 4: nothing repeated to 28
    kernel = next(
        line for line in text.splitlines()
        if "window_attention_dkv" in line and "custom-call(" in line
    )
    assert "bf16[1,16384,3584]" in kernel and "bf16[1,16384,512]" in kernel
    assert "ragged-dot" in text and "s32[98304]" in text
    # the routing ahead of attention (`smallthinker/route`) is kept as well
    _assert_routed_once_a_layer(text, layers=4)
    tilings = re.findall(r'ragged_dot_tiling="(\d+),(\d+),(\d+)"', text)
    assert tilings
    assert all(int(t) >= moe.TILE for tiling in tilings for t in tiling)
    # 2,560 columns are a slow width to scatter-add to (8.0 ms a chunk on
    # the chip where 2,688 cost 2.4: `moe.SLOW_SCATTER_WIDTHS`): the walk's
    # float32 carries are a lane tile wider and nothing scatters into a
    # (tokens, 2,560) float32 array (PR 58; before it eight such scatters a
    # step, two a layer)
    assert moe._carry_width(2560) == 2688
    assert not re.search(r"= f32\[16384,2560\]\S* scatter\(", text)
    assert len(re.findall(r"= f32\[16384,2688\]\S* scatter\(", text)) == 8
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    # `eight_layers`: 4.447e9 + 5.265e9 = 9.712e9 at four layers before PR
    # 58, 4.447e9 + 5.244e9 = 9.691e9 with the wider carries, 4.447e9 +
    # 4.397e9 = 8.844e9 since PR 60 (four layers' log-sum-exp a lane-major
    # row of 14.7 MB as the chip tiles it where the column took 235)
    assert 8.4e9 < held < 9.3e9, held


def _whole_arrays_off_the_channels(text, size=2 * 8192 * 4096):
    """(the `copy` / `transpose` instructions whose result has `size`
    elements, the arrays of that many elements whose layout's minor axis
    is not the last) in a compiled text."""
    copies, off_lanes = [], set()
    for line in text.splitlines():
        found = re.match(
            r"\s*(?:ROOT )?%[\w.\-]+ = (\w+)\[([\d,]+)\]\{([\d,]+)[^ ]* "
            r"([\w\-]+)\(", line,
        )
        if not found:
            continue
        dtype, dims, layout, op = found.groups()
        dims = [int(d) for d in dims.split(",")]
        if math.prod(dims) != size:
            continue
        if op in ("copy", "transpose"):
            copies.append(f"{dtype}{dims}")
        if int(layout.split(",")[0]) != len(dims) - 1:
            off_lanes.add(f"{dtype}{dims}{{{layout}}}")
    return copies, sorted(off_lanes)


@pytest.mark.parametrize("form", ["grouped", "view"])
def test_the_grouped_gated_norm_keeps_the_channels_along_the_lanes(
    one_chip, monkeypatch, form
):
    """The Nemotron cell's gated norm, (2, 8192, 4096) in 8 groups of 512,
    followed by the (4,096 x 2,688) out projection, forward and gradient:
    no whole-array copy or transpose, and no whole array whose minor axis
    is not the channels.  The control is the view form, `rms_norm` over
    (..., 8, 512): XLA lays its arrays out tokens-minor and copies them
    (in the cell's whole step: three float32 copies a layer from the (8
    tokens x 128 channels) tiling to (8 groups x 128 channels))."""
    from model_zoo.common import decoder

    if form == "view":
        def view(x, scale, eps, groups):
            by_group = (*x.shape[:-1], groups, -1)
            return decoder.rms_norm(
                x.reshape(by_group), scale.reshape(groups, -1), eps
            ).reshape(x.shape)

        monkeypatch.setattr(decoder, "grouped_rms_norm", view)
    norm = decoder.GatedRMSNorm(1e-5, jnp.bfloat16, 8)

    def loss(y, z, scale, kernel):
        out = jnp.dot(norm.apply({"params": {"scale": scale}}, y, z), kernel)
        return jnp.square(out.astype(jnp.float32)).mean()

    def shaped(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3))).lower(
        shaped((2, 8192, 4096)), shaped((2, 8192, 4096)),
        shaped((4096,), jnp.float32), shaped((4096, 2688)),
    ).compile().as_text()
    copies, off_lanes = _whole_arrays_off_the_channels(text)
    if form == "grouped":
        assert not copies and not off_lanes, (copies, off_lanes)
    else:
        assert copies and off_lanes


@pytest.mark.parametrize("form", ["lanes", "view"])
def test_kimis_out_path_keeps_the_channels_along_the_lanes(one_chip, form):
    """The Kimi cell's KDA out path, the scan's (2, 8192, 4096) output in
    32 heads of 128: the norm a head rounded to bfloat16, times the
    sigmoid of the gate, through `Wo` (4,096 x 2,304), value and gradient:
    no whole-array copy or transpose and no whole array off the lanes.
    The control is the form the layer had: `RMSNorm` and the gate over the
    (2, 8192, 32, 128) view."""
    from model_zoo.kimi import kimi_linear as zoo

    by_head = (2, 8192, 32, 128)
    if form == "lanes":
        norm = zoo.HeadRMSNorm(1e-5, jnp.bfloat16, 32)

        def gated(out, gate, scale):
            return norm.apply({"params": {"scale": scale}}, out) * (
                jax.nn.sigmoid(gate)
            )
    else:
        norm = zoo.RMSNorm(1e-5, jnp.bfloat16)

        def gated(out, gate, scale):
            return (
                norm.apply({"params": {"scale": scale}}, out.reshape(by_head))
                * jax.nn.sigmoid(gate.reshape(by_head))
            ).reshape(out.shape)

    def loss(out, gate, scale, kernel):
        return jnp.square(
            jnp.dot(gated(out, gate, scale), kernel).astype(jnp.float32)
        ).mean()

    def shaped(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3))).lower(
        shaped((2, 8192, 4096)), shaped((2, 8192, 4096)),
        shaped((128,), jnp.float32), shaped((4096, 2304)),
    ).compile().as_text()
    copies, off_lanes = _whole_arrays_off_the_channels(text)
    if form == "lanes":
        assert not copies and not off_lanes, (copies, off_lanes)
    else:
        assert copies and off_lanes


def test_kimi_step_keeps_its_kda_layers_along_the_lanes(
    one_chip, monkeypatch
):
    """The Kimi cell's train step over TWO of its KDA layers (published
    layers 1 and 2, routed; one layer alone picks other layouts), Adam and
    the lean remat policy, compiled for the described v5e: no whole (2,
    8192, 4096) array is copied, transposed or laid off the lanes.  The
    view form held five such copies a KDA layer (four `f32[2048,8,32,128]`
    feeding `kimi/kda/out/o_norm/reduce_sum`, one under `kimi/kda/gate`);
    the decay alone over the view holds two."""
    from elasticdl_tpu.ops import kda, short_conv
    from model_zoo.kimi import kimi_linear as zoo

    for module in (fa, kda, short_conv):
        monkeypatch.setattr(module, "use_interpret", lambda: False)
    text = _cell_train_step(one_chip, zoo, dict(
        _cell_config("kimi-linear-48b-a3b"), layers_held=[1, 2]
    )).as_text()
    assert "kda_chunk_fwd" in text and "kda_chunk_bwd" in text
    assert "kimi/kda/out/o_norm" in text
    copies, off_lanes = _whole_arrays_off_the_channels(text)
    assert not copies and not off_lanes, (copies, off_lanes)


# (q heads, K/V heads, head width, batch, length, turned columns, the first
# of them): every turn a rotary cell makes
TURNS = [
    pytest.param(64, 8, 128, 2, 8192, 128, 0, id="laguna-window"),
    pytest.param(48, 8, 128, 2, 8192, 64, 0, id="laguna-full-yarn"),
    pytest.param(28, 4, 128, 1, 16384, 128, 0, id="smallthinker"),
    pytest.param(16, 16, 128, 1, 8192, 128, 0, id="ouro"),
    pytest.param(32, 8, 64, 4, 8192, 64, 0, id="lfm2"),
    pytest.param(16, 2, 256, 2, 8192, 64, 0, id="qwen3-next"),
    # MLA: 64 columns after 192 that carry no position, and ONE shared
    # 64-wide key part, read two tokens a row
    pytest.param(20, 1, 256, 4, 4096, 64, 192, id="glm-mla"),
]


@pytest.mark.parametrize(
    "heads, kv_heads, dim, batch, length, columns, first", TURNS
)
def test_the_rotary_kernel_compiles_for_the_chip(
    one_chip, monkeypatch, heads, kv_heads, dim, batch, length, columns,
    first
):
    """The one-pass turn of q and of k, forward and VJP, at a cell's
    shape: Mosaic takes the lane rotations, the select and the blocks."""
    import numpy as np

    from elasticdl_tpu.ops import rotary

    monkeypatch.setattr(rotary, "use_interpret", lambda: False)
    inv_freq = jnp.asarray(
        1e4 ** (-np.arange(0, columns, 2) / columns), jnp.float32
    )
    for count in (heads, kv_heads):
        width, start = (dim, first) if count > 1 else (columns, 0)
        shape = (batch, length, count, width)
        assert rotary.one_pass_ok(shape, columns, start)

        def both(x, g, start=start):
            out, vjp = jax.vjp(
                lambda x: rotary.rotary_turn(x, inv_freq, 1.0, start), x
            )
            return out, vjp(g)[0]

        x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
        text = jax.jit(both).lower(x, x).compile().as_text()
        assert text.count("tpu_custom_call") == 2
        assert "rotary_turn_bwd" in text


def _float32_or_half_filled(text, q_size, half):
    """(the float32 arrays of `q_size` elements, the arrays whose two last
    axes are (heads, `half`)) that the entry computation of a compiled
    text MATERIALISES outside its kernels (what a fusion holds inside
    itself never reaches memory)."""
    whole, halves = set(), set()
    for line in text[text.index("\nENTRY "):].splitlines():
        found = re.match(
            r"\s*(?:ROOT )?%[\w.\-]+ = \(?(\w+)\[([\d,]+)\]", line
        )
        if not found or "custom-call(" in line:
            continue
        dtype, dims = found.groups()
        dims = [int(d) for d in dims.split(",")]
        if dtype == "f32" and math.prod(dims) == q_size:
            whole.add(f"{dtype}{dims}")
        if len(dims) == 4 and dims[2:] == [64, half]:
            halves.add(f"{dtype}{dims}")
    return sorted(whole), sorted(halves)


@pytest.mark.parametrize("form", ["kernel", "halves"])
def test_a_laguna_window_layer_turns_q_where_its_columns_lie(
    one_chip, monkeypatch, form
):
    """One Laguna window layer (64 heads of 128 over 8 K/V heads, (2,
    8192, 2048) bfloat16 in) under the zoo's remat, value and gradient,
    compiled for the described v5e (`scripts/probe_rotary.py`'s program):
    NO float32 array of q's size and no `[.., 64, 64]` half outside the
    kernels.  The control is the form the layer had, `halves_turn`: the q
    product writes float32, the cotangent is converted whole, and four
    half-filled copies a pass feed the turn."""
    from elasticdl_tpu.ops import rotary
    from model_zoo.common import decoder
    from scripts import probe_rotary

    for module in (fa, rotary):
        monkeypatch.setattr(module, "use_interpret", lambda: False)
    monkeypatch.setattr(decoder, "rotary_turn", probe_rotary.turn_of(form))
    program, operands = probe_rotary.layer_program("window", 8192, one_chip)
    text = program.lower(*operands).compile().as_text()
    whole, halves = _float32_or_half_filled(text, 2 * 8192 * 64 * 128, 64)
    if form == "kernel":
        assert "rotary_turn" in text and "rotary_turn_bwd" in text
        assert not whole and not halves, (whole, halves)
    else:
        assert "rotary_turn" not in text
        assert whole and halves


def test_the_scope_table_reads_a_text_compiled_for_the_chip(one_chip):
    """The chip's compiled text differs from the CPU's where the parser
    looks: tiled layouts with brackets of their own
    (`{1,0:T(8,128)(2,1)S(1)}`) before the opcode, tuple-shaped loops,
    and `lax.ragged_dot` turned into a kernel the compiler names itself
    (`%ragged-dot-none.N`, its path dropped), which
    `profiler.COMPILER_NAMED_SCOPES` puts back under `experts`, where
    `layers/moe.py` calls it."""
    from elasticdl_tpu.common import profiler, programs
    from elasticdl_tpu.layers.moe import grouped_matmul

    def step(x, w, sizes):
        def loss(x, w):
            with jax.named_scope("dispatch"):
                rows = jnp.tanh(x)
            with jax.named_scope("experts"):
                return grouped_matmul(rows, w, sizes).astype(
                    jnp.float32
                ).sum()

        with jax.named_scope("glm/moe"):
            d_x, d_w = jax.grad(loss, argnums=(0, 1))(x, w)
            # a trip count the device reads, as `routed_walk`'s
            with jax.named_scope("combine"):
                return d_w, jax.lax.fori_loop(
                    0, sizes.sum() // 256,
                    lambda i, acc: acc + d_x.astype(jnp.float32) * i,
                    jnp.zeros(d_x.shape, jnp.float32),
                )

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(step).lower(
        shaped((1024, 256), jnp.bfloat16),
        shaped((4, 256, 512), jnp.bfloat16), shaped((4,), jnp.int32),
    ).compile().as_text()
    assert ":T(8,128)" in text
    table = programs.parse_scope_table(text)
    assert table and all(row.opcode for row in table.values())
    loops = [r for r in table.values() if r.opcode == "while"]
    assert loops and all(r.container for r in loops)
    assert {r.entry for r in loops} == {"combine"}
    kernels = {
        name: row for name, row in table.items()
        if name.startswith(tuple(profiler.COMPILER_NAMED_SCOPES))
    }
    assert kernels, sorted(
        n for n, r in table.items() if r.opcode == "custom-call"
    )
    assert {row.entry for row in kernels.values()} == {"experts"}
    assert all(row.opcode == "custom-call" for row in kernels.values())
    assert {"forward", "backward"} <= {row.phase for row in table.values()}
