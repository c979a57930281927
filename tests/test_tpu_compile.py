"""The streaming attention kernels compiled for the chip WITHOUT the chip:
the TPU's compiler is installed here and compiles for a described v5e, so
what Mosaic refuses (a slice off the tiling, too much fast memory) fails
here and not in a chip call.  Interpret-mode tests cannot show that.
Nothing runs, so nothing here is a result or a time.

The topology is described inside a fixture, never at import: only one
process may hold the TPU's library, and every xdist worker imports every
test file.  Keep such tests in THIS file."""

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
]


@pytest.mark.parametrize("heads, kv_heads, dim, batch, length, window", SHAPES)
def test_streaming_kernels_compile_for_the_chip(
    one_chip, monkeypatch, heads, kv_heads, dim, batch, length, window
):
    # the kernels ask the default backend (the CPU here) whether to run
    # interpreted: steer them to Mosaic for the described chip
    monkeypatch.setattr(fa, "use_interpret", lambda: False)

    def shaped(h):
        return jax.ShapeDtypeStruct(
            (batch, length, h, dim), jnp.bfloat16, sharding=one_chip
        )

    def grads(q, k, v):
        return jax.grad(
            lambda q, k, v: fa.causal_attention(
                q, k, v, window=window
            ).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    compiled = jax.jit(grads).lower(
        shaped(heads), shaped(kv_heads), shaped(kv_heads)
    ).compile()
    text = compiled.as_text()
    kind = "causal" if window is None else "window"
    for kernel in ("fwd", "dkv", "dq"):
        assert f"{kind}_attention_{kernel}" in text
    assert text.count("tpu_custom_call") >= 3
    # K/V stay at their own head count: nothing repeated to the query's
    assert f"bf16[{batch},{length},{kv_heads * dim}]" in text


def test_half_lane_head_kernels_compile_for_the_chip(one_chip, monkeypatch):
    """The LFM2 cell's attention: 32 query heads of 64 over 8 K/V heads at
    (4, 8192), the head-major layout of a head of half a lane tile."""
    monkeypatch.setattr(fa, "use_interpret", lambda: False)

    def shaped(h):
        return jax.ShapeDtypeStruct(
            (4, 8192, h, 64), jnp.bfloat16, sharding=one_chip
        )

    def grads(q, k, v):
        return jax.grad(
            lambda q, k, v: fa.causal_attention(
                q, k, v
            ).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    text = jax.jit(grads).lower(
        shaped(32), shaped(8), shaped(8)
    ).compile().as_text()
    for kernel in ("fwd", "dkv", "dq"):
        assert f"causal_attention_{kernel}" in text
    assert text.count("tpu_custom_call") >= 3
    # K/V go head-major at their own head count: nothing repeated to 32
    assert "bf16[4,8,8192,64]" in text and "bf16[4,32,8192,64]" in text


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
