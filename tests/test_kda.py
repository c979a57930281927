"""The gated delta rule with a per-channel decay (elasticdl_tpu/ops/kda.py):
the chunked `jnp` form and the Pallas kernels (interpreted here) against
the token-by-token recurrence of the plain reference (`benchmarks/
reference/kimi_linear.py: delta_recurrence`, which shares none of the
chunked algebra), forward and all five gradients, at no decay, at a mild
one and at a decay so strong that `1 / exp(G)` would overflow; a length
that is no whole number of chunks; the admission rule, the names and the
types."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference.kimi_linear import delta_recurrence
from elasticdl_tpu.ops import kda as kda_ops


def recurrence(q, k, v, g, beta):
    """(B, L, H, D) operands through the one-head recurrence."""
    one = jax.vmap(jax.vmap(delta_recurrence, in_axes=1, out_axes=1))
    with jax.default_matmul_precision("highest"):
        return one(q, k, v, g, beta)


def inputs(batch, length, heads, dim, g_min, seed=0, dtype=jnp.float32):
    """q and k L2-normed a head, as a model hands them over; g uniform in
    [g_min, 0] a token and channel."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = (batch, length, heads, dim)

    def normed(key, scale):
        x = jax.random.normal(key, shape)
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True) * scale

    return (
        normed(keys[0], dim ** -0.5).astype(dtype),
        normed(keys[1], 1.0).astype(dtype),
        jax.random.normal(keys[2], shape).astype(dtype),
        g_min * jax.random.uniform(keys[3], shape),
        jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3])),
        jax.random.normal(keys[5], shape),
    )


def out_and_grads(fn, q, k, v, g, beta, weight):
    """(fn's output, the gradients of a weighted sum of it by the five
    operands) from ONE compiled program: walked a primitive at a time, an
    interpreted kernel's forward ran twice a test and its backward once,
    each an equation at a time."""
    def weighted(*operands):
        out = fn(*operands)
        return (out * weight).sum(), out

    with jax.default_matmul_precision("highest"):
        (_, out), grads = jax.jit(jax.value_and_grad(
            weighted, argnums=(0, 1, 2, 3, 4), has_aux=True
        ))(q, k, v, g, beta)
    return out, grads


def assert_close(got, want, limit, what):
    error = float(
        jnp.linalg.norm(got - want) / (jnp.linalg.norm(want) + 1e-30)
    )
    assert error < limit, (what, error)


# g down to -20 a token: over a chunk of 64 the running sum reaches -1280
# and exp(+1280) is far past float32 (and float64)
DECAYS = [
    pytest.param(0.0, id="no-decay"),
    pytest.param(-1.0, id="mild"),
    pytest.param(-20.0, id="strong"),
]
FORMS = [
    pytest.param(kda_ops.chunked_kda, id="jnp"),
    pytest.param(kda_ops._kda, id="kernels"),
]
NAMES = ("dq", "dk", "dv", "dg", "dbeta")


@pytest.mark.parametrize("g_min", DECAYS)
@pytest.mark.parametrize("form", FORMS)
def test_chunked_forms_match_the_recurrence(form, g_min):
    """Two chunks of two heads of 128: the output, and the gradient of a
    weighted sum of it by q, k, v, g and beta."""
    args = inputs(1, 128, 2, 128, g_min)
    assert kda_ops.kda_shapes_ok(*(a.shape for a in args[:3]))
    want_out, want = out_and_grads(recurrence, *args)
    out, got = out_and_grads(form, *args)
    assert_close(out, want_out, 5e-5, "o")
    for name, a, b in zip(NAMES, got, want):
        assert a.shape == b.shape and np.isfinite(np.asarray(a)).all()
        assert_close(a, b, 2e-4, name)


def test_the_entry_pads_a_length_that_is_no_whole_chunk():
    """80 positions go the `jnp` form, padded to 128 with tokens that
    leave the state alone; the outputs and gradients are the first 80's."""
    args = inputs(2, 80, 2, 16, -2.0, seed=1)
    assert not kda_ops.kda_shapes_ok(*(a.shape for a in args[:3]))
    want_out, want = out_and_grads(recurrence, *args)
    out, got = out_and_grads(kda_ops.kda, *args)
    assert out.shape == (2, 80, 2, 16)
    assert_close(out, want_out, 5e-5, "o")
    for name, a, b in zip(NAMES, got, want):
        assert_close(a, b, 2e-4, name)
    # a chunk of another size is the same number
    assert_close(
        kda_ops.chunked_kda(*args[:5], chunk=16), out, 5e-5, "chunk 16"
    )


@pytest.mark.parametrize("form", FORMS)
def test_the_l2_norms_inside_the_op(form):
    """`qk_norm` = (eps, q's scale): raw q and k go in, the op norms them
    a head; the recurrence on operands normed outside is the same number,
    and so are the gradients by the RAW q and k."""
    q, k, v, g, beta, weight = inputs(1, 128, 2, 128, -1.0, seed=4)
    q, k = 3.0 * q + 0.1, 0.5 * k - 0.05           # no unit rows
    norm = (1e-6, 128 ** -0.5)

    def plain(q, k, v, g, beta):
        return recurrence(
            kda_ops.l2_normed(q, *norm), kda_ops.l2_normed(k, norm[0]), v, g,
            beta,
        )

    def fused(q, k, v, g, beta):
        return form(q, k, v, g, beta, norm)

    want_out, want = out_and_grads(plain, q, k, v, g, beta, weight)
    out, got = out_and_grads(fused, q, k, v, g, beta, weight)
    assert_close(out, want_out, 5e-5, "o")
    for name, a, b in zip(NAMES, got, want):
        assert_close(a, b, 2e-4, name)


def test_the_state_crosses_chunks():
    """With no decay and no new writes after the first chunk, the second
    chunk reads what the first wrote: zeroing the first chunk's values
    zeroes the second chunk's output."""
    q, k, v, g, beta, _ = inputs(1, 128, 1, 128, 0.0, seed=2)
    beta = beta.at[:, 64:].set(0.0)
    out = kda_ops._kda(q, k, v, g, beta)
    assert float(jnp.abs(out[:, 64:]).max()) > 1e-3
    silent = kda_ops._kda(q, k, v.at[:, :64].set(0.0), g, beta)
    assert float(jnp.abs(silent[:, 64:]).max()) == 0.0


@pytest.mark.parametrize("form", FORMS)
def test_aligned_keys_do_not_cancel(form):
    """Every key of a chunk the same unit vector, beta near 1, no decay:
    I + A is then as far from the identity as it gets, and a Neumann
    series over the whole chunk (terms up to C(63, 32) ~ 1e18) would
    cancel to nothing in float32.  Substitution over sub-blocks holds."""
    q, k, v, g, beta, weight = inputs(1, 128, 1, 128, 0.0, seed=3)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    beta = jnp.full_like(beta, 0.95)
    want, want_grads = out_and_grads(recurrence, q, k, v, g, beta, weight)
    out, got = out_and_grads(form, q, k, v, g, beta, weight)
    assert_close(out, want, 2e-3, "o")
    for name, a, b in zip(NAMES, got, want_grads):
        assert np.isfinite(np.asarray(a)).all()
        assert_close(a, b, 5e-3, name)


def chunk_system(kind, g_min, seed):
    """(A strictly lower (64, 64), R (64, 128)) of one chunk, float32: A =
    Diag(beta) (K K^T o decay) below the diagonal, as `_rebuild` hands it
    to `_solve`; `kind` as in `test_aligned_keys_do_not_cancel`."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    k = jax.random.normal(keys[0], (64, 128))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = jax.nn.sigmoid(jax.random.normal(keys[1], (64, 1)))
    if kind != "random":
        k = jnp.broadcast_to(k[:1], k.shape)
        beta = jnp.full_like(beta, 0.95)
    if kind == "opposite":
        k = k * jnp.where(jnp.arange(64) % 2 == 0, 1.0, -1.0)[:, None]
    G = jnp.cumsum(g_min * jax.random.uniform(keys[2], (64, 1)), axis=0)
    with jax.default_matmul_precision("highest"):
        A = jnp.tril(k @ k.T * jnp.exp(jnp.minimum(G - G.T, 0.0)), -1) * beta
    return A, jax.random.normal(keys[3], (64, 128))


SYSTEMS = [
    pytest.param("random", 0.0, id="random-keys-no-decay"),
    pytest.param("random", -1.0, id="random-keys-mild"),
    pytest.param("random", -20.0, id="random-keys-strong"),
    pytest.param("aligned", 0.0, id="aligned-keys"),
    pytest.param("aligned", -20.0, id="aligned-keys-strong"),
    pytest.param("opposite", 0.0, id="opposite-keys"),
]


@pytest.mark.parametrize("kind, g_min", SYSTEMS)
def test_the_substitution_solves_both_systems_side_by_side(kind, g_min):
    """`_solve` against `jax.scipy.linalg.solve_triangular`: (I + A) X = R
    and, handed A^T as a strictly upper system, (I + A)^T X = R, two
    chunks' four systems advancing together; and each alone is the same
    number to the bit (side by side is an ORDER of statements, not
    another arithmetic)."""
    from jax.scipy.linalg import solve_triangular

    chunks = [chunk_system(kind, g_min, seed) for seed in (5, 6)]
    systems = [(A, R, False) for A, R in chunks] + [
        (A.T, R, True) for A, R in chunks
    ]
    with jax.default_matmul_precision("highest"):
        together = kda_ops._solve(systems, jnp.float32)
        alone = [kda_ops._solve([one], jnp.float32)[0] for one in systems]
        for (A, R, upper), got, single in zip(systems, together, alone):
            want = solve_triangular(
                A + jnp.eye(64), R, lower=not upper, unit_diagonal=True
            )
            assert np.isfinite(np.asarray(got)).all()
            assert_close(got, want, 1e-5, (kind, g_min, upper))
            assert (np.asarray(got) == np.asarray(single)).all()


def test_heads_a_step_divide_the_heads_and_fit_a_wider_head():
    """Four heads of 128 a grid step, fewer of a wider head, and never a
    count that does not divide the heads."""
    assert kda_ops._heads_a_step(32) == 4               # the cell's
    assert kda_ops._heads_a_step(2) == 2
    assert kda_ops._heads_a_step(6) == 2
    assert kda_ops._heads_a_step(3) == 1
    assert kda_ops._heads_a_step(32, 256) == 2
    assert kda_ops._heads_a_step(32, 1024) == 1


def test_admission_names_and_types():
    ok = kda_ops.kda_shapes_ok
    cell = (2, 8192, 32, 128)
    assert ok(cell, cell, cell)                              # the cell's
    assert not ok((2, 8200, 32, 128), (2, 8200, 32, 128), (2, 8200, 32, 128))
    assert not ok((2, 8192, 32, 64), (2, 8192, 32, 64), (2, 8192, 32, 64))
    assert not ok(cell, (2, 8192, 16, 128), cell)            # k is not q's
    q, k, v, g, beta, w = inputs(1, 64, 2, 128, -1.0, dtype=jnp.bfloat16)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda *a: kda_ops.kda(*a).astype(jnp.float32).sum(),
        argnums=(0, 1, 2, 3, 4),
    ))(q, k, v, g, beta))
    names = sorted(set(re.findall(r"\bkda_\w+(?:fwd|bwd)\b", jaxpr)))
    assert names == ["kda_chunk_bwd", "kda_chunk_fwd"]
    # what the benchmark's rules find them by, and apart from
    for name in names:
        assert re.match(r"^kda_\w*(fwd|bwd)$", name)
        assert "attention" not in name and "short_conv" not in name
    def weighted(*operands):
        out = kda_ops.kda(*operands)
        return (out * w).astype(jnp.float32).sum(), out

    # the output and the gradients from one compiled program
    (_, out), grads = jax.jit(jax.value_and_grad(
        weighted, argnums=(0, 1, 2, 3, 4), has_aux=True
    ))(q, k, v, g, beta)
    assert out.dtype == jnp.bfloat16
    assert_close(
        out.astype(jnp.float32),
        recurrence(*(t.astype(jnp.float32) for t in (q, k, v, g, beta))),
        2e-2, "bfloat16 o",
    )
    assert [t.dtype for t in grads] == [
        jnp.bfloat16, jnp.bfloat16, jnp.bfloat16, jnp.float32, jnp.float32
    ]


def test_the_forward_results_carry_the_names_a_remat_may_save():
    """`decoder.remat_block` keeps ONE policy: what the scan names joins
    the attention core's there, and `SAVED_NAMES` says which are kept."""
    from model_zoo.common import decoder

    q, k, v, g, beta, _ = inputs(1, 64, 1, 128, -1.0)
    text = str(jax.make_jaxpr(
        lambda *a: kda_ops._kda_fwd(*a, None)
    )(q, k, v, g, beta))
    for name in kda_ops.RESULT_NAMES:
        assert f"name={name}" in text
    assert set(kda_ops.SAVED_NAMES) <= set(kda_ops.RESULT_NAMES)
    assert set(kda_ops.SAVED_NAMES) <= set(decoder.SAVED_NAMES)
    assert "attention_core_out" in decoder.SAVED_NAMES


def test_a_kernels_body_is_traced_once_a_shape(monkeypatch):
    """Every layer of a model calls the kernels at one shape: the callable
    is built once (`_forward_call`, `_backward_call`), so jax traces the
    unrolled body once a process and not once a layer and pass (the
    cell's five-layer step traced it twelve times: a third of a minute
    of every run's set-up on the chip's host)."""
    bodies = {"forward": 0, "backward": 0}
    forward, backward = kda_ops._chunk_forward, kda_ops._chunk_backward

    def counted(name, fn):
        def call(*args, **kwargs):
            bodies[name] += 1
            return fn(*args, **kwargs)
        return call

    monkeypatch.setattr(kda_ops, "_chunk_forward", counted("forward", forward))
    monkeypatch.setattr(
        kda_ops, "_chunk_backward", counted("backward", backward)
    )
    kda_ops._forward_call.cache_clear()
    kda_ops._backward_call.cache_clear()
    q, k, v, g, beta, weight = inputs(1, 128, 2, 128, -1.0)

    def two_layers(q, k, v, g, beta):
        first = kda_ops.kda(q, k, v, g, beta)
        return (kda_ops.kda(q, k, first, g, beta) * weight).sum()

    text = str(jax.make_jaxpr(jax.grad(two_layers, argnums=(0, 2)))(
        q, k, v, g, beta
    ))
    # each call site keeps its own kernel call (its own scope in a trace)
    assert text.count("kda_chunk_fwd") >= 2
    assert text.count("kda_chunk_bwd") >= 2
    heads_a_step = kda_ops._heads_a_step(2)
    assert bodies == {"forward": heads_a_step, "backward": heads_a_step}
    kda_ops._forward_call.cache_clear()
    kda_ops._backward_call.cache_clear()
