"""`layers/moe.py: routed_walk` with the expert's FORM an argument: the
gated `swiglu` and `reglu` over a fused gate-and-up stack and the non-gated
`relu2` over an up stack alone, each against a per-expert dense sum (forward and
every gradient) at loads that leave a dead tail, an empty expert, no row
and every row, at a token whose slots lie in one chunk and in two, and with
the float32 carries at the tokens' own width and a lane tile wider (where
the chip scatters to the tokens' width slowly); what the backward holds, by
form; the leaves a layer of
each form builds; the `swiglu` walk at a sibling cell's shape traced
to what it was traced to before the form entered; and the grouped
products' operands padded to whole `TILE`s (every case above at a tile
that pads both axes, one, and neither)."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers import moe

TOKENS, TOP_K, HIDDEN, FFN, HELD = 64, 2, 32, 24, 4
CHUNK = 48               # 128 slots: 3 chunks, 16 rows padded
# rows each held expert gets: a dead tail in the last live chunk with an
# EMPTY expert between two others; no row here; a chunk and one row;
# every slot here
LOADS = {
    "dead_tail_and_empty_expert": (20, 0, 9, 0),
    "none": (0, 0, 0, 0),
    "one_chunk_and_a_row": (20, 0, 28, 1),
    "every_slot": (32, 32, 32, 32),
}
ACTIVATIONS = {
    moe.SWIGLU: lambda h: jax.nn.silu(h[..., :FFN]) * h[..., FFN:],
    moe.RELU2: lambda h: jnp.square(jax.nn.relu(h)),
    moe.REGLU: lambda h: jax.nn.relu(h[..., :FFN]) * h[..., FFN:],
}


def dealt(loads, rng):
    """A key (the held expert of every slot, `HELD` for an absent one) that
    sends exactly `loads[e]` slots, chosen at random, to held expert e."""
    key = np.full((TOKENS * TOP_K,), HELD, np.int32)
    here = rng.permutation(key.size)[:sum(loads)]
    key[here] = np.repeat(np.arange(HELD), loads)
    return key


def in_one_chunk_and_in_two():
    """Token 0 sends BOTH its slots to expert 0 (adjacent rows); tokens 1-10
    a slot to expert 0 and one to expert 1, both in chunk 0 (rows 2-11 and
    32-41); tokens 11-30 a slot to expert 0 and one to expert 3, whose 20
    rows (42-61) cross the chunks' edge at 48: tokens 17-30 are summed from
    two chunks.  Expert 2 has a load of 0; the last live chunk a dead tail;
    tokens 31-63 only absent experts."""
    key = np.full((TOKENS, TOP_K), HELD, np.int32)
    key[:31, 0] = 0
    key[0, 1], key[1:11, 1], key[11:31, 1] = 0, 1, 3
    return key.reshape(-1)


def given(form, loads, seed=0):
    """Tokens, the form's two stacks, and a routing that sends exactly
    `loads[e]` slots to held expert e and the rest to absent experts
    (key `HELD`; an array for `loads` is the key itself), in the layer's
    own terms: `order` (the slots sorted by key), one weight a slot, the
    group sizes."""
    rng = np.random.RandomState(seed)
    slots = TOKENS * TOP_K
    key = loads if isinstance(loads, np.ndarray) else dealt(loads, rng)
    loads = np.bincount(key, minlength=HELD + 1)[:HELD]
    width = moe.FORMS[form][1] * FFN
    return dict(
        tokens=jnp.asarray(rng.randn(TOKENS, HIDDEN), jnp.float32),
        w_first=jnp.asarray(0.2 * rng.randn(HELD, HIDDEN, width), jnp.float32),
        w_down=jnp.asarray(0.2 * rng.randn(HELD, FFN, HIDDEN), jnp.float32),
        weights=jnp.asarray(rng.rand(slots) + 0.5, jnp.float32),
        order=jnp.argsort(jnp.asarray(key), stable=True),
        group_sizes=jnp.asarray(loads, jnp.int32), key=key,
    )


def dense_sum(form, tokens, w_first, w_down, weights, key):
    """Every held expert over ALL tokens, times the weight of the slots
    that chose it (zero where none did): no sort, no walk."""
    act = ACTIVATIONS[form]
    by_token = weights.reshape(TOKENS, TOP_K)
    out = jnp.zeros_like(tokens)
    for e in range(HELD):
        weight = jnp.where(key.reshape(TOKENS, TOP_K) == e, by_token, 0.0)
        out = out + weight.sum(axis=1)[:, None] * (
            act(tokens @ w_first[e]) @ w_down[e]
        )
    return out


# and a routing that is set, not dealt
LOADS["in_one_chunk_and_in_two"] = in_one_chunk_and_in_two()

# HIDDEN 32 and FFN 24 against the tile: both axes padded far (the module's
# own tile), both padded to 40 (neither a multiple of 20), the expert's
# width alone (to 32), neither
TILES = {"module": None, "both": 20, "ffn_only": 16, "neither": 8}


# the float32 carries a trip scatter-adds into: at the tokens' own width,
# or a lane tile wider where that width is one the chip scatters to slowly
# (`moe._carry_width`, `moe.SLOW_SCATTER_WIDTHS`)
CARRIES = {"own": set(), "a_tile_wider": {HIDDEN}}


@pytest.mark.parametrize("carry", sorted(CARRIES))
@pytest.mark.parametrize("tile", sorted(TILES))
@pytest.mark.parametrize("form", sorted(moe.FORMS))
@pytest.mark.parametrize("load", sorted(LOADS))
def test_a_walk_of_either_form_is_the_per_expert_dense_sum(
        load, form, tile, carry, monkeypatch):
    monkeypatch.setattr(moe, "CHUNK", CHUNK)
    monkeypatch.setattr(moe, "SLOW_SCATTER_WIDTHS", CARRIES[carry])
    assert moe._carry_width(HIDDEN) == HIDDEN + 128 * (carry != "own")
    if TILES[tile]:
        monkeypatch.setattr(moe, "TILE", TILES[tile])
    assert (moe.padded_work(HIDDEN, FFN) > 0) == (tile != "neither")
    g = given(form, LOADS[load])
    loads = np.asarray(g["group_sizes"])
    cotangent = jnp.asarray(
        np.random.RandomState(5).randn(TOKENS, HIDDEN), jnp.float32
    )
    leaves = ("tokens", "w_first", "w_down", "weights")

    def through(function):
        return jax.value_and_grad(
            lambda *a: (function(*a) * cotangent).sum(),
            argnums=(0, 1, 2, 3),
        )(*(g[name] for name in leaves))

    with jax.default_matmul_precision("highest"):
        out = moe.routed_walk(
            g["tokens"], g["w_first"], g["w_down"], g["order"],
            g["weights"], g["group_sizes"], form,
        )
        want = dense_sum(form, *(g[name] for name in leaves), g["key"])
        _, got_grads = through(lambda t, a, b, w: moe.routed_walk(
            t, a, b, g["order"], w, g["group_sizes"], form
        ))
        _, want_grads = through(
            lambda t, a, b, w: dense_sum(form, t, a, b, w, g["key"])
        )
    assert out.dtype == jnp.float32
    assert bool(np.abs(np.asarray(out)).sum() > 0) == bool(loads.sum())
    close = dict(rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(out, want, **close)
    for name, got, ref in zip(leaves, got_grads, want_grads):
        # the padding is cut off before a cotangent leaves the walk
        assert (got.shape, got.dtype) == (g[name].shape, g[name].dtype)
        np.testing.assert_allclose(got, ref, err_msg=name, **close)
    # an expert no slot chose gets no gradient, a slot of no held expert
    # no weight's
    for e, rows in enumerate(loads):
        got = np.abs(np.asarray(got_grads[1][e])).sum()
        assert bool(got > 0) == bool(rows)
    assert not np.asarray(got_grads[3])[g["key"] == HELD].any()


def test_the_forms_differ_and_the_default_is_swiglu():
    g = given(moe.RELU2, LOADS["one_chunk_and_a_row"])
    args = (g["tokens"], g["w_first"][..., :FFN], g["w_down"], g["order"],
            g["weights"], g["group_sizes"])
    fused = jnp.concatenate([args[1], args[1]], axis=-1)
    squared = moe.routed_walk(*args, moe.RELU2)
    gated = moe.routed_walk(args[0], fused, *args[2:], moe.SWIGLU)
    assert np.abs(np.asarray(squared - gated)).max() > 0.01
    np.testing.assert_array_equal(
        moe.routed_walk(args[0], fused, *args[2:]), gated
    )
    # rectified where `swiglu` is smooth; gated where `relu2` squares: with
    # the gate's stack for the up stack too relu(h) h is relu(h)^2, and
    # with its negative the negative
    rectified = moe.routed_walk(args[0], fused, *args[2:], moe.REGLU)
    assert np.abs(np.asarray(rectified - gated)).max() > 0.01
    np.testing.assert_allclose(rectified, squared, rtol=1e-6, atol=1e-6)
    mirrored = jnp.concatenate([args[1], -args[1]], axis=-1)
    np.testing.assert_allclose(
        moe.routed_walk(args[0], mirrored, *args[2:], moe.REGLU), -squared,
        rtol=1e-6, atol=1e-6,
    )
    with pytest.raises(KeyError):
        moe.routed_walk(*args, "gelu")


def test_walk_bytes_by_form():
    """The backward's four buffers take the form's widths: (hidden, 2
    ffn, ffn, hidden) gated, (hidden, ffn, ffn, hidden) squared; the three
    float32 sums the carry's."""
    tokens, hidden, top_k, ffn = 16384, 2048, 6, 1536   # whole tiles
    chunk, total = moe._chunks(tokens * top_k)
    assert (chunk, total) == (16384, 6)
    sums = 3 * tokens * hidden * 4
    assert moe.walk_bytes(tokens, hidden, top_k, ffn, 2) == (
        (total + 2) * chunk * (2 * hidden + 3 * ffn) * 2 + sums
    )
    assert moe.walk_bytes(tokens, hidden, top_k, ffn, 2, moe.RELU2) == (
        (total + 2) * chunk * (2 * hidden + 2 * ffn) * 2 + sums
    )
    # `reglu` is gated: `swiglu`'s widths
    assert moe.walk_bytes(tokens, hidden, top_k, ffn, 2, moe.REGLU) == (
        moe.walk_bytes(tokens, hidden, top_k, ffn, 2)
    )
    # 16,384 tokens of 2,560, top-6, experts 768 wide (whole tiles): the
    # walk of one sequence at a 16k context
    # ... whose three sums are a lane tile wider than the tokens: the chip
    # scatters to 2,560 columns at a quarter of its neighbours' pace
    assert moe._carry_width(2560) == 2688 and moe._carry_width(2048) == 2048
    assert moe.walk_bytes(16384, 2560, 6, 768, 2, moe.REGLU) == (
        8 * 16384 * 7424 * 2 + 3 * 16384 * 2688 * 4
    )
    # the GLM cell's layer, as before the form entered
    assert moe.walk_bytes(16384, 2048, 4, 1536, 2) == (
        6 * 16384 * (2 * 2048 + 3 * 1536) * 2 + 3 * 16384 * 2048 * 4
    )


@pytest.mark.parametrize("tile, hidden, ffn", [
    (128, 2688, 1920), (256, 2816, 2048), (512, 3072, 2048),
])
def test_walk_bytes_at_the_padded_widths(tile, hidden, ffn, monkeypatch):
    """The four buffers are allocated at whole tiles (the sums stay the
    tokens' own width); a layer whose widths are whole reads as before."""
    monkeypatch.setattr(moe, "TILE", tile)
    tokens, top_k = 16384, 6
    chunk, total = moe._chunks(tokens * top_k)
    assert moe.walk_bytes(tokens, 2688, top_k, 1856, 2, moe.RELU2) == (
        (total + 2) * chunk * (2 * hidden + 2 * ffn) * 2
        + 3 * tokens * 2688 * 4
    )
    assert moe.walk_bytes(16384, 2048, 4, 1536, 2) == (
        6 * 16384 * (2 * 2048 + 3 * 1536) * 2 + 3 * 16384 * 2048 * 4
    )


@pytest.mark.parametrize("form, first", [
    (moe.SWIGLU, ("expert_w_gate_up", (4, HIDDEN, 2 * FFN))),
    (moe.RELU2, ("expert_w_up", (4, HIDDEN, FFN))),
    (moe.REGLU, ("expert_w_gate_up", (4, HIDDEN, 2 * FFN))),
])
def test_a_layer_builds_its_forms_stacks(form, first):
    layer = moe.RoutedExperts(
        num_experts=16, top_k=TOP_K, ffn_dim=FFN, held_experts=(0, 4),
        form=form,
    )
    x = jnp.zeros((2, 8, HIDDEN))
    shapes = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)["params"]
    assert {name: leaf.shape for name, leaf in shapes.items()} == {
        "router_kernel": (HIDDEN, 16), first[0]: first[1],
        "expert_w_down": (4, FFN, HIDDEN),
    }


# sha256 of str(make_jaxpr(grad(routed_walk ...))) at the GLM cell's
# bfloat16 shape (16,384 tokens of 2,048, 8 held experts 1,536 wide, top-4:
# 65,536 slots), recorded at the commit before the form entered
# (1daa31a): the four routed cells' walk traces to what it traced to.
# ISSUE 58 expected to re-record it; PR 58's probe found the scatter-add slow
# at ONE width, 2,560 columns (`moe.SLOW_SCATTER_WIDTHS`), so it STANDS: the
# walk at 2,048 columns is still that commit's.
SWIGLU_JAXPR = (
    "b6b6b0df946fc301f1f36d1bf05b107c4b73728266de328ee7a7e96c9ace8af4"
)


def test_the_swiglu_walk_is_the_parents():
    shaped = jax.ShapeDtypeStruct
    text = str(jax.make_jaxpr(jax.grad(
        lambda t, a, b, o, w, g: moe.routed_walk(t, a, b, o, w, g).sum(),
        argnums=(0, 1, 2, 4),
    ))(
        shaped((16384, 2048), jnp.bfloat16),
        shaped((8, 2048, 3072), jnp.bfloat16),
        shaped((8, 1536, 2048), jnp.bfloat16),
        shaped((65536,), jnp.int32), shaped((65536,), jnp.float32),
        shaped((8,), jnp.int32),
    ))
    assert "0x" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == SWIGLU_JAXPR


def walk_jaxpr(form, tokens, hidden, ffn, top_k, held=8, backward=True):
    """The jaxpr of the walk's forward and backward (of its forward alone
    without `backward`) at a cell's bfloat16 shape (abstract: nothing is
    computed)."""
    shaped = jax.ShapeDtypeStruct
    slots = tokens * top_k

    def walk(t, a, b, o, w, g):
        return moe.routed_walk(t, a, b, o, w, g, form)

    return jax.make_jaxpr(jax.grad(
        lambda *args: walk(*args).sum(), argnums=(0, 1, 2, 4),
    ) if backward else walk)(
        shaped((tokens, hidden), jnp.bfloat16),
        shaped((held, hidden, moe.FORMS[form][1] * ffn), jnp.bfloat16),
        shaped((held, ffn, hidden), jnp.bfloat16),
        shaped((slots,), jnp.int32), shaped((slots,), jnp.float32),
        shaped((held,), jnp.int32),
    )


def equations(jaxpr, name):
    """Every equation called `name`, those of inner jaxprs (the loops',
    a `pjit`'s, a `custom_vjp`'s) included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append(eqn)
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    found.extend(equations(inner, name))
    return found


def test_nemotrons_grouped_products_run_at_whole_tiles():
    """16,384 tokens of 2,688, eight `relu2` experts 1,856 wide, top-6:
    every `ragged_dot` of the walk, forward, backward and the two stack
    gradients, has operands whose every width is whole tiles, and the
    four cotangents the arguments' shapes."""
    closed = walk_jaxpr(moe.RELU2, 16384, 2688, 1856, 6)
    products = equations(closed.jaxpr, "ragged_dot_general")
    # eight on the device; `jax.vjp` of a stack's gradient traces its
    # forward too, dead code
    assert len(products) >= 8
    widths = {
        dim for eqn in products for operand in eqn.invars[:2]
        for dim in operand.aval.shape[-2:] if dim != 16384 * 6
    }
    assert widths and all(dim % moe.TILE == 0 for dim in widths), widths
    assert moe._whole(1856) in widths and 1856 not in widths
    assert [(v.aval.shape, v.aval.dtype) for v in closed.jaxpr.outvars] == [
        ((16384, 2688), jnp.bfloat16), ((8, 2688, 1856), jnp.bfloat16),
        ((8, 1856, 2688), jnp.bfloat16), ((98304,), jnp.float32),
    ]


@pytest.mark.parametrize("cell, shape", [
    ("glm_lfm2", (moe.SWIGLU, 16384, 2048, 1536, 4)),
    ("laguna", (moe.SWIGLU, 16384, 2048, 512, 8)),
    ("kimi", (moe.SWIGLU, 16384, 2304, 1024, 8)),
])
def test_a_walk_at_whole_tiles_pads_no_stack(cell, shape):
    """The siblings' widths are whole tiles: the only `pad` of the walk is
    the sorted buffer's (one axis), none of a stack or of the tokens."""
    closed = walk_jaxpr(*shape)
    pads = equations(closed.jaxpr, "pad")
    assert all(eqn.invars[0].aval.ndim == 1 for eqn in pads), pads
    assert moe.padded_work(shape[2], shape[3]) == 0.0


@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("cell, shape", [
    ("smallthinker", (moe.REGLU, 16384, 2560, 768, 6)),
    ("top_8_of_the_same_width", (moe.SWIGLU, 16384, 2560, 512, 8)),
])
def test_a_slow_width_is_scattered_to_a_tile_wider(cell, shape, backward):
    """16,384 tokens of 2,560: no trip scatter-adds into a (tokens, 2,560)
    float32 carry, forward or backward (8.0 ms a chunk on the chip): the
    carries are 2,688 wide (2.4 ms), and the walk's results keep the
    tokens' shape.  (At the siblings' widths the walk is the parent's:
    `test_the_swiglu_walk_is_the_parents`.)"""
    _, tokens, hidden, _, _ = shape
    closed = walk_jaxpr(*shape, backward=backward)
    carries = [
        eqn.invars[0].aval.shape
        for eqn in equations(closed.jaxpr, "scatter-add")
        if eqn.invars[0].aval.ndim == 2
    ]
    # a gradient's jaxpr traces the forward too
    assert carries == [(tokens, 2688)] * (1 + backward)
    assert closed.jaxpr.outvars[0].aval.shape == (tokens, hidden)
