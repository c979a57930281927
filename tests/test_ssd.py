"""The scalar-decay state-space scan (elasticdl_tpu/ops/ssd.py): the
chunked `jnp` form against the token-by-token recurrence in float32, y and
every gradient (x, dt, A_log, B, C, D, and dt_bias through the softplus);
two chunk sizes give the same numbers; a state that crosses chunk
boundaries, with a control that zeroes what is carried; dB and dC as sums
over the heads of a group, and of ITS group alone where there are several;
the kernels in interpret mode against the chunked form, at one group and
at 2, 4 and 8; shapes that do not tile (groups that do not fill whole grid
steps among them) fall to the `jnp` form; and the one-group call at the
granite cell's shape traced to what it was traced to before groups
entered the kernels."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import ssd as ops

NAMES = ("x", "dt_raw", "dt_bias", "A_log", "B", "C", "D")


def recurrence(x, dt, A, B, C, D):
    """S_t = exp(A dt_t) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t + D x_t,
    a `lax.scan` over t: nothing of the chunked algebra."""
    batch, _, heads, dim = x.shape
    each = heads // B.shape[2]
    B, C = (jnp.repeat(t, each, axis=2) for t in (B, C))

    def step(state, token):
        x_t, dt_t, b_t, c_t = token
        state = (
            jnp.exp(A * dt_t)[..., None, None] * state
            + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        )
        return state, (state * c_t[..., None, :]).sum(-1) + D[:, None] * x_t

    _, y = jax.lax.scan(
        step, jnp.zeros((batch, heads, dim, B.shape[-1])),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, B, C)),
    )
    return jnp.moveaxis(y, 0, 1)


def inputs(batch=2, length=96, heads=4, dim=8, groups=1, columns=16,
           seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    return dict(
        x=jax.random.normal(keys[0], (batch, length, heads, dim)),
        dt_raw=jax.random.normal(keys[1], (batch, length, heads)),
        dt_bias=jax.random.normal(keys[2], (heads,)) - 2.0,
        A_log=jnp.log(jax.random.uniform(keys[3], (heads,), minval=1.0,
                                         maxval=16.0)),
        B=0.4 * jax.random.normal(keys[4], (batch, length, groups, columns)),
        C=0.4 * jax.random.normal(keys[5], (batch, length, groups, columns)),
        D=jax.random.normal(keys[6], (heads,)),
        weight=jax.random.normal(keys[7], (batch, length, heads, dim)),
    )


def through(core, given):
    """(y, gradients by name) of `core(x, dt, A, B, C, D)` under the
    model's softplus and -exp."""
    def y_of(x, dt_raw, dt_bias, A_log, B, C, D):
        return core(
            x, jax.nn.softplus(dt_raw + dt_bias), -jnp.exp(A_log), B, C, D
        )

    def weighted(*args):
        y = y_of(*args)
        return (y * given["weight"]).sum(), y

    # ONE compiled program for y and the gradients: walked a primitive at
    # a time, an interpreted kernel's forward ran twice and its backward
    # once, each an equation at a time
    with jax.default_matmul_precision("highest"):
        (_, y), grads = jax.jit(jax.value_and_grad(
            weighted, argnums=tuple(range(len(NAMES))), has_aux=True
        ))(*[given[name] for name in NAMES])
    return np.asarray(y), dict(zip(NAMES, map(np.asarray, grads)))


def assert_close(got, want, limit=1e-4):
    y, grads = got
    want_y, want_grads = want
    assert np.abs(y - want_y).max() < limit * np.abs(want_y).max()
    for name, ref in want_grads.items():
        error = np.linalg.norm(grads[name] - ref) / np.linalg.norm(ref)
        assert error < limit, (name, error)


@pytest.mark.parametrize("groups", [1, 2, 4], ids=[
    "one-group", "two-groups", "four-groups",
])
@pytest.mark.parametrize("chunk", [16, 32, 40])
def test_chunked_form_matches_the_recurrence(chunk, groups):
    """y and every gradient, at chunks that divide the 96 tokens (six and
    three chunks: the state crosses boundaries) and one that pads them."""
    given = inputs(groups=groups)
    assert_close(
        through(lambda *a: ops.chunked_ssd(*a, chunk=chunk), given),
        through(recurrence, given),
    )


def test_the_carried_state_does_work():
    """With what a chunk hands the next zeroed (every chunk a sequence of
    its own) the numbers differ: the comparison above sees the carry."""
    given = inputs()
    batch, length, heads, dim = given["x"].shape

    def cut(x, dt, A, B, C, D, chunk=32):
        def folded(t):
            return t.reshape(batch * length // chunk, chunk, *t.shape[2:])

        return ops.chunked_ssd(
            folded(x), folded(dt), A, folded(B), folded(C), D, chunk=chunk
        ).reshape(x.shape)

    y, _ = through(cut, given)
    want, _ = through(recurrence, given)
    np.testing.assert_allclose(y[:, :32], want[:, :32], rtol=1e-4, atol=1e-5)
    assert np.abs(y[:, 32:] - want[:, 32:]).max() > 0.05 * np.abs(want).max()


@pytest.mark.parametrize("form", ["plain", "kernels"])
def test_shared_b_and_c_sum_their_gradient_over_the_heads(form):
    """One B and one C a token for all heads: with every head given the
    same x, dt, A and D, the gradient of B and of C is the one-head call's
    times the head count, and y is the one-head call's in every head."""
    heads = 4
    if form == "plain":
        one = inputs(batch=1, length=64, heads=1)
        core = lambda *a: ops.chunked_ssd(*a, chunk=16)
    else:
        one = inputs(batch=1, length=64, heads=1, dim=128, columns=128)
        core = lambda *a: ops._ssd(*a, 16)
    many = dict(
        one, x=jnp.repeat(one["x"], heads, axis=2),
        dt_raw=jnp.repeat(one["dt_raw"], heads, axis=2),
        dt_bias=jnp.repeat(one["dt_bias"], heads),
        A_log=jnp.repeat(one["A_log"], heads),
        D=jnp.repeat(one["D"], heads),
        weight=jnp.repeat(one["weight"], heads, axis=2),
    )
    y1, g1 = through(core, one)
    y4, g4 = through(core, many)
    np.testing.assert_allclose(
        y4, np.repeat(y1, heads, axis=2), rtol=1e-5, atol=1e-6
    )
    for name in ("B", "C"):
        assert np.abs(g1[name]).max() > 0.0
        np.testing.assert_allclose(
            g4[name], heads * g1[name], rtol=1e-4,
            atol=1e-5 * np.abs(g1[name]).max(),
        )


@pytest.mark.parametrize("heads,dim", [(4, 64), (2, 128), (8, 64)],
                         ids=["half-lane-heads", "whole-lane-heads",
                              "four-tiles-a-step"])
def test_kernels_match_the_chunked_form(heads, dim):
    """The Pallas kernels, interpreted: two heads a lane tile (the cell's
    heads of 64) and one, a tile a grid step and four (`_TILES`, as the
    cell's 32 tiles go), three chunks of 32."""
    given = inputs(length=96, heads=heads, dim=dim, columns=128, seed=1)
    assert ops.ssd_shapes_ok(
        (2, ops.CHUNK, heads, dim), (2, ops.CHUNK, 1, 128)
    )
    assert_close(
        through(lambda *a: ops._ssd(*a, 32), given),
        through(lambda *a: ops.chunked_ssd(*a, chunk=32), given),
    )
    assert_close(
        through(lambda *a: ops._ssd(*a, 32), given),
        through(recurrence, given), limit=2e-4,
    )


def test_the_entry_takes_the_kernels_where_the_shapes_tile(monkeypatch):
    calls = []
    monkeypatch.setattr(
        ops, "_ssd", lambda *a: calls.append("kernel") or ops.chunked_ssd(*a)
    )
    tiled = inputs(batch=1, length=ops.CHUNK, heads=2, dim=64, columns=128)
    core = [tiled["x"], jax.nn.softplus(tiled["dt_raw"]),
            -jnp.exp(tiled["A_log"]), tiled["B"], tiled["C"], tiled["D"]]
    ops.ssd(*core)
    assert calls == ["kernel"]
    small = inputs()
    ops.ssd(small["x"], jax.nn.softplus(small["dt_raw"]),
            -jnp.exp(small["A_log"]), small["B"], small["C"], small["D"])
    assert calls == ["kernel"]


@pytest.mark.parametrize("x_shape,b_shape,ok", [
    ((1, 8192, 64, 64), (1, 8192, 1, 128), True),      # the cell's call
    ((2, 512, 2, 128), (2, 512, 1, 128), True),
    ((1, 8192, 64, 64), (1, 8192, 8, 128), True),      # a grid step a group
    ((2, 512, 32, 64), (2, 512, 2, 128), True),        # two steps a group
    ((2, 512, 16, 64), (2, 512, 8, 128), False),       # a group half a step
    ((2, 512, 6, 64), (2, 512, 3, 128), True),         # a tile a step, a group
    ((2, 512, 8, 64), (2, 512, 3, 128), False),        # heads no whole groups
    ((1, 8192, 3, 64), (1, 8192, 1, 128), False),      # half a lane tile left
    ((1, 8192, 64, 64), (1, 8192, 1, 64), False),      # state columns
    ((1, 8000, 64, 64), (1, 8000, 1, 128), False),     # no whole chunks
    ((1, 8192, 64, 48), (1, 8192, 1, 128), False),     # heads off the lanes
    ((8192, 64, 64), (8192, 1, 128), False),
])
def test_which_shapes_the_kernels_take(x_shape, b_shape, ok):
    assert ops.ssd_shapes_ok(x_shape, b_shape) is ok


def test_shapes_that_do_not_tile_fall_to_the_plain_form():
    given = inputs(length=70, heads=3, dim=8)
    assert not ops.ssd_shapes_ok(given["x"].shape, given["B"].shape)
    assert_close(through(ops.ssd, given), through(recurrence, given))


def test_bfloat16_operands_stay_close():
    """The stated type through kernels and plain form alike: float32
    statistics (dt, the running sums, the state), bfloat16 products."""
    given = inputs(length=64, heads=2, dim=64, columns=128, seed=2)
    low = {k: (v.astype(jnp.bfloat16) if k in ("x", "B", "C") else v)
           for k, v in given.items()}
    args = lambda g: (g["x"], jax.nn.softplus(g["dt_raw"] + g["dt_bias"]),
                      -jnp.exp(g["A_log"]), g["B"], g["C"], g["D"])
    want = np.asarray(recurrence(*args(given)))
    for core in (lambda *a: ops._ssd(*a, 32),
                 lambda *a: ops.chunked_ssd(*a, chunk=32)):
        got = core(*args(low))
        assert got.dtype == jnp.bfloat16
        error = np.abs(np.asarray(got, np.float32) - want).max()
        assert error < 0.05 * np.abs(want).max()


# (heads, head width, groups): two groups of a grid step each (four lane
# tiles), two grid steps a group, four groups, and the Nemotron cell's 64
# heads in 8 groups, grid step t group t; whole-lane heads in two groups
GROUPED = [(16, 64, 2), (32, 64, 2), (32, 64, 4), (64, 64, 8), (8, 128, 2)]


@pytest.mark.parametrize("heads, dim, groups", GROUPED, ids=[
    "a-step-a-group", "two-steps-a-group", "four-groups", "eight-groups",
    "whole-lane-heads",
])
def test_grouped_kernels_match_the_chunked_form_and_the_recurrence(
        heads, dim, groups):
    """The Pallas kernels, interpreted, with B and C BY GROUP: y and every
    gradient against the chunked form and against the token-by-token
    recurrence, three chunks of 32."""
    given = inputs(batch=1, length=96, heads=heads, dim=dim, groups=groups,
                   columns=128, seed=1)
    assert ops.ssd_shapes_ok(
        (1, ops.CHUNK, heads, dim), (1, ops.CHUNK, groups, 128)
    )
    kernels = through(lambda *a: ops._ssd(*a, 32), given)
    assert_close(
        kernels, through(lambda *a: ops.chunked_ssd(*a, chunk=32), given)
    )
    assert_close(kernels, through(recurrence, given), limit=2e-4)


@pytest.mark.parametrize("form", ["plain", "kernels"])
def test_a_groups_b_and_c_sum_their_gradient_over_its_heads_alone(form):
    """dB and dC of a group are the sum over ITS heads: the two-group
    call's gradient of group g is the one-group call's over group g's
    heads alone, and a control that sums over ALL heads (one group given
    every head) differs."""
    if form == "plain":
        sizes = dict(batch=1, length=64, heads=4, dim=8, columns=16)
        core = lambda *a: ops.chunked_ssd(*a, chunk=16)
    else:
        sizes = dict(batch=1, length=64, heads=16, dim=64, columns=128)
        core = lambda *a: ops._ssd(*a, 16)
    both = inputs(groups=2, seed=3, **sizes)
    each = sizes["heads"] // 2
    _, grads = through(core, both)

    def alone(g, heads):
        """The call over `heads` with group g's B and C as the one
        group."""
        part = {
            k: both[k][:, :, heads] for k in ("x", "dt_raw", "weight")
        }
        part.update({k: both[k][heads] for k in ("dt_bias", "A_log", "D")})
        part.update({k: both[k][:, :, g:g + 1] for k in ("B", "C")})
        return through(core, part)[1]

    for g in range(2):
        own = alone(g, slice(g * each, (g + 1) * each))
        every = alone(g, slice(None))
        for name in ("B", "C"):
            got = grads[name][:, :, g]
            scale = np.abs(got).max()
            np.testing.assert_allclose(
                got, own[name][:, :, 0], rtol=1e-4, atol=1e-5 * scale,
                err_msg=f"{name} of group {g}",
            )
            assert np.abs(got - every[name][:, :, 0]).max() > 0.05 * scale


def test_groups_that_fill_no_whole_grid_step_fall_to_the_plain_form(
        monkeypatch):
    """16 heads of 64 are 8 lane tiles, four a grid step; 8 groups of two
    heads are a tile each: the kernels are not called, and the numbers
    are the recurrence's."""
    calls = []
    monkeypatch.setattr(
        ops, "_ssd", lambda *a: calls.append("kernel") or ops.chunked_ssd(*a)
    )
    given = inputs(batch=1, length=ops.CHUNK, heads=16, dim=64, groups=8,
                   columns=128, seed=4)
    assert not ops.ssd_shapes_ok(given["x"].shape, given["B"].shape)
    assert_close(through(ops.ssd, given), through(recurrence, given))
    assert not calls
    # four groups of four heads are two tiles each: still half a step;
    # two groups of eight are a step each
    x_shape = (1, ops.CHUNK, 16, 64)
    assert not ops.ssd_shapes_ok(x_shape, (1, ops.CHUNK, 4, 128))
    assert ops.ssd_shapes_ok(x_shape, (1, ops.CHUNK, 2, 128))


# sha256 of str(make_jaxpr(grad(ssd ...))) at the granite cell's bfloat16
# shape (1, 8192, 64 heads of 64) under ONE B and C a token (1, 8192, 1,
# 128), recorded at the commit before groups entered the kernels (1daa31a):
# the one-group callable is the parent's, grid, specs, scratch and body.
ONE_GROUP_JAXPR = (
    "48af8fe473f6ed06d61a3b39b53f250d9693068c31e8fa5db2836bd9bdee8e06"
)


def _grad_text(groups):
    shaped = jax.ShapeDtypeStruct
    shared = shaped((1, 8192, groups, 128), jnp.bfloat16)
    return str(jax.make_jaxpr(jax.grad(
        lambda x, dt, A, B, C, D: ops.ssd(x, dt, A, B, C, D).astype(
            jnp.float32
        ).sum(), argnums=(0, 1, 2, 3, 4, 5),
    ))(
        shaped((1, 8192, 64, 64), jnp.bfloat16),
        shaped((1, 8192, 64), jnp.float32), shaped((64,), jnp.float32),
        shared, shared, shaped((64,), jnp.float32),
    ))


def test_the_one_group_call_is_the_parents():
    text = _grad_text(1)
    assert "0x" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == ONE_GROUP_JAXPR
    # and eight groups are another program of the same two kernels
    grouped = _grad_text(8)
    assert "ssd_fwd" in grouped and "ssd_bwd" in grouped
    assert "bf16[1,8192,1024]" in grouped
    assert grouped != text
