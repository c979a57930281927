"""DistributedEmbedding: hashing, combiners, pad masking, and — the key
property — numerical equivalence between the row-sharded table on a
data×model mesh and a replicated table (the sharding must be a pure layout
choice, like the reference's id-hash partition across PS shards)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from elasticdl_tpu.layers.embedding import (
    DistributedEmbedding,
    embedding_param_sharding,
    hash_ids,
)
from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.worker.trainer import Trainer


def test_hash_ids_in_range_and_deterministic():
    ids = jnp.array([0, 1, 2, 12345678, 2**31 - 1])
    rows = hash_ids(ids, 1024)
    assert rows.shape == ids.shape
    assert bool(jnp.all((rows >= 0) & (rows < 1024)))
    np.testing.assert_array_equal(rows, hash_ids(ids, 1024))


def test_lookup_shapes_and_pad_masking():
    layer = DistributedEmbedding(64, 8)
    ids = jnp.array([[1, 2, -1], [3, -1, -1]])
    params = layer.init(jax.random.PRNGKey(0), ids)
    out = layer.apply(params, ids)
    assert out.shape == (2, 3, 8)
    np.testing.assert_array_equal(np.asarray(out[0, 2]), np.zeros(8))
    np.testing.assert_array_equal(np.asarray(out[1, 1]), np.zeros(8))


@pytest.mark.parametrize("combiner", ["sum", "mean", "sqrtn"])
def test_combiners(combiner):
    layer = DistributedEmbedding(64, 4, combiner=combiner, hash_input=False)
    ids = jnp.array([[1, 2, -1]])
    params = layer.init(jax.random.PRNGKey(0), ids)
    out = layer.apply(params, ids)
    assert out.shape == (1, 4)
    table = params["params"]["embedding"]
    v = np.asarray(table[1]) + np.asarray(table[2])
    if combiner == "mean":
        v = v / 2
    elif combiner == "sqrtn":
        v = v / np.sqrt(2)
    np.testing.assert_allclose(np.asarray(out[0]), v, rtol=1e-6)


class TinyEmbedModel:
    """Zoo-style module: embedding bag + dense head."""

    @staticmethod
    def build():
        import flax.linen as nn

        class Model(nn.Module):
            @nn.compact
            def __call__(self, ids):
                emb = DistributedEmbedding(
                    256, 16, combiner="mean", name="embedding_bag"
                )(ids)
                return nn.Dense(2)(emb)

        return Model()


def _loss(labels, preds):
    return optax.softmax_cross_entropy_with_integer_labels(
        preds, labels
    ).mean()


def _batch(seed=0, n=32):
    rng = np.random.RandomState(seed)
    return {
        "features": rng.randint(0, 10_000, size=(n, 5)).astype(np.int32),
        "labels": rng.randint(0, 2, size=n).astype(np.int32),
    }


def _train(mesh, param_sharding, steps=3):
    trainer = Trainer(
        model=TinyEmbedModel.build(),
        optimizer=optax.adam(1e-2),
        loss_fn=_loss,
        mesh=mesh,
        param_sharding_fn=param_sharding,
    )
    state = trainer.init_state(jax.random.PRNGKey(0), _batch()["features"])
    losses = []
    for i in range(steps):
        state, loss = trainer.train_on_batch(state, _batch(i))
        losses.append(float(loss))
    return losses, state


def test_sharded_table_matches_replicated():
    """data=4 x model=2 mesh with the table sharded over `model` must give
    the same losses/params as a fully replicated 1-device run."""
    devices = jax.devices()
    mesh_sharded = mesh_lib.create_mesh(devices, data=4, model=2)
    mesh_single = mesh_lib.create_mesh(devices[:1], data=1)
    losses_sh, state_sh = _train(mesh_sharded, embedding_param_sharding)
    losses_rep, state_rep = _train(mesh_single, None)
    np.testing.assert_allclose(losses_sh, losses_rep, rtol=2e-4)
    for a, b in zip(
        jax.tree.leaves(state_sh.params), jax.tree.leaves(state_rep.params)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5
        )


def test_table_actually_sharded_on_model_axis():
    devices = jax.devices()
    mesh = mesh_lib.create_mesh(devices, data=4, model=2)
    trainer = Trainer(
        model=TinyEmbedModel.build(),
        optimizer=optax.adam(1e-2),
        loss_fn=_loss,
        mesh=mesh,
        param_sharding_fn=embedding_param_sharding,
    )
    state = trainer.init_state(jax.random.PRNGKey(0), _batch()["features"])
    table = state.params["params"]["embedding_bag"]["embedding"]
    # each model-shard holds half the rows
    shard_shape = table.addressable_shards[0].data.shape
    assert shard_shape[0] == table.shape[0] // 2
    assert shard_shape[1] == table.shape[1]


def test_gradients_flow_only_through_looked_up_rows():
    layer = DistributedEmbedding(128, 4, hash_input=False)
    ids = jnp.array([3, 7])
    params = layer.init(jax.random.PRNGKey(0), ids)

    def loss_fn(p):
        return layer.apply(p, ids).sum()

    grads = jax.grad(loss_fn)(params)
    g = np.asarray(grads["params"]["embedding"])
    nonzero_rows = set(np.nonzero(np.abs(g).sum(axis=1))[0].tolist())
    assert nonzero_rows == {3, 7}


# ---- the distinct-row backward (`scatter_add_rows`) -----------------------


def _zipf(exponent, rows):
    return lambda rng, n: (rng.zipf(exponent, n) % rows).astype(np.int32)


# (ids of n updates, table rows, row width, CHUNK patched in)
_SCATTER_CASES = {
    "all_distinct": (
        lambda rng, n: rng.permutation(8192)[:n].astype(np.int32),
        8192, 16, 65536),
    "zipf_1.5": (_zipf(1.5, 4096), 4096, 16, 65536),
    "zipf_1.05": (_zipf(1.05, 4096), 4096, 16, 65536),
    "one_id_for_every_update": (
        lambda rng, n: np.full(n, 17, np.int32), 64, 16, 65536),
    "distinct_an_exact_multiple_of_chunk": (
        lambda rng, n: np.repeat(rng.permutation(4096)[:512], n // 512)
        .astype(np.int32)[rng.permutation(n)], 4096, 16, 128),
    "distinct_over_several_chunks": (_zipf(1.05, 4096), 4096, 16, 100),
    "width_1_plain_path": (_zipf(1.5, 4096), 4096, 1, 256),
    "width_2": (_zipf(1.5, 4096), 4096, 2, 256),
    "ids_at_the_last_row": (
        lambda rng, n: np.where(
            rng.random(n) < 0.5, 4095, rng.integers(0, 4096, n)
        ).astype(np.int32), 4096, 16, 64),
}


@pytest.mark.parametrize("case", sorted(_SCATTER_CASES))
def test_scatter_add_rows_matches_a_float64_sum(case, monkeypatch):
    from elasticdl_tpu.layers import embedding

    make_ids, rows, width, chunk = _SCATTER_CASES[case]
    monkeypatch.setattr(embedding, "CHUNK", chunk)
    rng = np.random.default_rng(3)
    n = 2048
    ids = make_ids(rng, n)
    assert ids.dtype == np.int32 and ids.shape == (n,)
    distinct = len(np.unique(ids))
    if case == "distinct_an_exact_multiple_of_chunk":
        assert distinct == 4 * chunk
    if case == "distinct_over_several_chunks":
        assert distinct > 3 * chunk and distinct % chunk
    g = rng.standard_normal((n, width), dtype=np.float32)
    want = np.zeros((rows, width), np.float64)
    np.add.at(want, ids, g.astype(np.float64))
    got = np.asarray(jax.jit(
        lambda i, u: embedding.scatter_add_rows((rows, width), i, u)
    )(ids, g))
    plain = np.asarray(jnp.zeros((rows, width)).at[ids].add(g))
    # a tree sum of each run: at least as near the float64 sum as the
    # plain scatter's serial one, up to a rounding of the largest total
    ulp = np.abs(want).max() * 2.0 ** -23
    assert np.abs(got - want).max() <= np.abs(plain - want).max() + 2 * ulp
    np.testing.assert_allclose(got, want, atol=16 * ulp, rtol=0)
    # rows no id named stay exactly zero
    untouched = np.setdiff1d(np.arange(rows), ids)
    assert not got[untouched].any()


def _primitives(jaxpr, inside_loop=False, out=None):
    """{(primitive name, inside a while loop)} over a jaxpr and every
    jaxpr nested in it."""
    out = set() if out is None else out
    for eqn in jaxpr.eqns:
        out.add((eqn.primitive.name, inside_loop))
        for value in eqn.params.values():
            nested = inside_loop or eqn.primitive.name == "while"
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, nested, out)
    return out


def test_wide_rows_keep_the_plain_scatter_and_narrow_rows_sort_and_loop():
    ids = jnp.arange(64, dtype=jnp.int32) % 7

    def backward_of(width):
        layer = DistributedEmbedding(32, width, hash_input=False)
        params = layer.init(jax.random.PRNGKey(0), ids)
        found = _primitives(jax.make_jaxpr(jax.grad(
            lambda p: jnp.sum(layer.apply(p, ids) ** 2)
        ))(params).jaxpr)
        return found, params

    wide, params = backward_of(2048)
    assert ("scatter-add", False) in wide
    assert not {name for name, _ in wide} & {"sort", "while", "cond"}
    assert "step_metrics" not in params            # a wide lookup sows none
    narrow, params = backward_of(16)
    assert ("sort", False) in narrow
    assert ("scatter-add", True) in narrow         # inside the chunk loop
    assert ("scatter-add", False) not in narrow
    assert "step_metrics" in params


def test_lookup_sows_the_share_of_distinct_rows():
    layer = DistributedEmbedding(512, 8)
    rng = np.random.default_rng(5)
    ids = (rng.zipf(1.3, (64, 5)) % 1000).astype(np.int32)
    variables = layer.init(jax.random.PRNGKey(0), ids)
    _, sown = layer.apply(variables, ids, mutable=["step_metrics"])
    rows = np.asarray(hash_ids(jnp.asarray(ids), 512))
    assert float(sown["step_metrics"]["distinct_rows_ratio"]) == (
        pytest.approx(len(np.unique(rows)) / rows.size)
    )
    # outside a step that takes the collection nothing is sown
    assert layer.apply(variables, ids).shape == (64, 5, 8)


def test_trainer_fetches_the_distinct_share_with_the_loss():
    from elasticdl_tpu.worker.sync import ModelOwner

    trainer = Trainer(
        model=TinyEmbedModel.build(), optimizer=optax.adam(1e-2),
        loss_fn=_loss,
    )
    owner = ModelOwner(trainer)
    batch = _batch(1)
    loss = owner.train_batch(batch)
    value, sown = owner.fetch_loss(loss)
    rows = np.asarray(hash_ids(jnp.asarray(batch["features"]), 256))
    assert value == pytest.approx(float(loss))
    assert sown["embedding_bag/distinct_rows_ratio"] == pytest.approx(
        len(np.unique(rows)) / rows.size
    )


# ---- the distinct-row forward (`_gather_rows`, PR 48) ----------------------

# constants patched in: trips of 64 rows, the route from 256 ids on, a
# compact buffer of 512 rows
_ROUTE = {"CHUNK": 64, "_COMPACT_MIN_CHUNKS": 4, "_COMPACT_CHUNKS": 8}


def _take_the_route(monkeypatch, **changed):
    from elasticdl_tpu.layers import embedding

    for name, value in {**_ROUTE, **changed}.items():
        monkeypatch.setattr(embedding, name, value)
    return embedding


def _exactly(distinct):
    return lambda rng, n: np.resize(
        rng.permutation(4096)[:distinct], n
    ).astype(np.int32)[rng.permutation(n)]


# (ids of n lookups into 4,096 rows, n, which side of the `cond`)
_LOOKUP_CASES = {
    "zipf_1.5": (_zipf(1.5, 4096), 2048, 1.0),
    "flat": (lambda rng, n: rng.integers(0, 4096, n).astype(np.int32),
             2048, 0.0),
    "all_distinct": (
        lambda rng, n: rng.permutation(4096)[:n].astype(np.int32), 2048, 0.0),
    "all_one_row": (lambda rng, n: np.full(n, 4095, np.int32), 2048, 1.0),
    "with_pads": (
        lambda rng, n: np.where(
            rng.random(n) < 0.2, -1, rng.zipf(1.5, n) % 4096
        ).astype(np.int32), 2048, 1.0),
    "n_no_multiple_of_chunk": (_zipf(1.5, 4096), 2000, 1.0),
    "distinct_fill_the_buffer": (_exactly(512), 2048, 1.0),
    "distinct_one_over_the_buffer": (_exactly(513), 2048, 0.0),
    "one_trip": (_exactly(5), 2048, 1.0),
}


@pytest.mark.parametrize("width", [1, 2, 16, 64])
@pytest.mark.parametrize("case", sorted(_LOOKUP_CASES))
def test_lookup_is_the_plain_gather_to_the_bit(case, width, monkeypatch):
    """Forward and gradient through `lookup_rows` against `table[ids]`
    and the backward with no order handed to it (a one-element row's:
    the plain scatter-add), on both sides of the `cond`, said by the sown
    `lookup_compact`."""
    embedding = _take_the_route(monkeypatch)
    make_ids, n, compact = _LOOKUP_CASES[case]
    rng = np.random.default_rng(11)
    ids = make_ids(rng, n)
    layer = DistributedEmbedding(4096, width, hash_input=False)
    table = jnp.asarray(
        rng.standard_normal((4096, width), dtype=np.float32)
    ).at[7].set(-0.0)
    weight = jnp.asarray(rng.standard_normal((n, width), dtype=np.float32))

    def loss(params):
        out, sown = layer.apply(params, ids, mutable=["step_metrics"])
        return jnp.sum(out * weight), (out, sown["step_metrics"])

    (_, (out, sown)), grads = jax.jit(
        jax.value_and_grad(loss, has_aux=True)
    )({"params": {"embedding": table}})
    rows = np.maximum(ids, 0)
    masked = jnp.where((ids != -1)[:, None], weight, 0.0)
    want_grad = jax.jit(
        lambda g: embedding.scatter_add_rows(table.shape, rows, g)
    )(masked)

    def bits(x):
        return np.asarray(x).view(np.int32)

    np.testing.assert_array_equal(
        bits(out), bits(jnp.where((ids != -1)[:, None], table[rows], 0.0))
    )
    np.testing.assert_array_equal(
        bits(grads["params"]["embedding"]), bits(want_grad)
    )
    assert float(sown["lookup_compact"]) == compact
    assert float(sown["distinct_rows_ratio"]) == pytest.approx(
        len(np.unique(rows)) / n
    )


@pytest.mark.parametrize("width", [1, 16])
def test_few_ids_lower_to_the_plain_gather(width, monkeypatch):
    _take_the_route(monkeypatch)
    ids = jnp.arange(255, dtype=jnp.int32) % 7      # one under 4 chunks
    layer = DistributedEmbedding(32, width, hash_input=False)
    params = layer.init(jax.random.PRNGKey(0), ids)
    found = {name for name, _ in _primitives(jax.make_jaxpr(
        lambda p: layer.apply(p, ids, mutable=["step_metrics"])
    )(params).jaxpr)}
    assert "gather" in found and not found & {"while", "cond"}
    # the static rule's plain gather says nothing of a route; a
    # one-element row has no order to sort for either
    sown = params.get("step_metrics", {})
    assert set(sown) == ({"distinct_rows_ratio"} if width > 1 else set())
    assert ("sort" in found) == (width > 1)
    many = jnp.arange(256, dtype=jnp.int32) % 7
    found = {name for name, _ in _primitives(jax.make_jaxpr(
        lambda p: layer.apply(p, many, mutable=["step_metrics"])
    )(params).jaxpr)}
    assert {"sort", "while", "cond"} <= found
    assert set(layer.init(jax.random.PRNGKey(0), many)["step_metrics"]) == {
        "distinct_rows_ratio", "lookup_compact"
    }


# sha256 of the program a WIDE row's lookup and its gradient lower to
# over 1,024 ids (64 CHUNKs as patched: no count of ids brings a wide
# row onto the route), recorded at the commit before the forward had a
# route (61a50ed)
_WIDE_ROW_PROGRAMS = {
    (128, "float32"):
        "601b9f0dbf378fe5edd28da2792507d975be9245b4dd7bb098c596186dd81867",
    (2048, "float32"):
        "93e5277a903e98386c938462b4a3a1dc6f6a10faa5c875c1aef743e1d2d426fc",
    (256, "bfloat16"):
        "3d88ab6d898aeb78a4abe72ec0f211863c523ce9e8eb836ee432bc7474f2086f",
}


@pytest.mark.parametrize("width,dtype", sorted(_WIDE_ROW_PROGRAMS))
def test_a_wide_rows_lookup_lowers_to_the_program_it_had(
    width, dtype, monkeypatch
):
    import hashlib
    import re

    _take_the_route(monkeypatch, CHUNK=16)
    layer = DistributedEmbedding(
        64, width, hash_input=False, param_dtype=jnp.dtype(dtype)
    )
    ids = jnp.arange(1024, dtype=jnp.int32) % 7
    shapes = jax.eval_shape(layer.init, jax.random.PRNGKey(0), ids)
    assert set(shapes) == {"params"}

    def loss(params):
        out, _ = layer.apply({"params": params}, ids, mutable=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = re.sub(
        r"(@[A-Za-z_]\w*?)_\d+\b", r"\1",
        jax.jit(jax.value_and_grad(loss)).lower(shapes["params"]).as_text(),
    )
    assert hashlib.sha256(text.encode()).hexdigest() == (
        _WIDE_ROW_PROGRAMS[width, dtype]
    )
    found = {name for name, _ in _primitives(
        jax.make_jaxpr(jax.value_and_grad(loss))(shapes["params"]).jaxpr
    )}
    assert not found & {"sort", "while", "cond"}


def test_sharded_table_matches_replicated_on_the_route(monkeypatch):
    """The forward's loop gathers from a table row-sharded over `model`
    as the backward's loop scatters into one."""
    _take_the_route(monkeypatch, CHUNK=16)         # 64 ids on, 128 rows
    devices = jax.devices()
    mesh_sharded = mesh_lib.create_mesh(devices, data=4, model=2)
    mesh_single = mesh_lib.create_mesh(devices[:1], data=1)

    def train(mesh, sharding):
        trainer = Trainer(
            model=TinyEmbedModel.build(), optimizer=optax.adam(1e-2),
            loss_fn=_loss, mesh=mesh, param_sharding_fn=sharding,
        )
        # 5 ids an example over 64 values: every batch stays in the buffer
        batches = [
            {**_batch(i), "features": _batch(i)["features"] % 64}
            for i in range(3)
        ]
        state = trainer.init_state(
            jax.random.PRNGKey(0), batches[0]["features"]
        )
        losses = []
        for batch in batches:
            state, loss = trainer.train_on_batch(state, batch)
            losses.append(float(loss))
        took = state.model_state["step_metrics"]["embedding_bag"][
            "lookup_compact"]
        return losses, state, float(took)

    losses_sh, state_sh, took_sh = train(
        mesh_sharded, embedding_param_sharding
    )
    losses_rep, state_rep, took_rep = train(mesh_single, None)
    assert took_sh == took_rep == 1.0
    np.testing.assert_allclose(losses_sh, losses_rep, rtol=2e-4)
    for a, b in zip(
        jax.tree.leaves(state_sh.params), jax.tree.leaves(state_rep.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_trainer_fetches_the_lookups_route_with_the_loss(monkeypatch):
    """`lookup_compact` rides to the task's one fetch beside the distinct
    share (32 x 5 ids over ~120 rows: on the route by the static rule,
    more rows than the 32 of the buffer as patched: the plain side)."""
    from elasticdl_tpu.worker.sync import ModelOwner

    _take_the_route(monkeypatch, CHUNK=4)
    owner = ModelOwner(Trainer(
        model=TinyEmbedModel.build(), optimizer=optax.adam(1e-2),
        loss_fn=_loss,
    ))
    _, sown = owner.fetch_loss(owner.train_batch(_batch(1)))
    assert sown["embedding_bag/lookup_compact"] == 0.0


def test_a_symbolic_count_of_ids_keeps_the_plain_gather():
    """A model exported for any batch size has no count to hold against
    the static rule (`tests/test_saved_model_export.py` exports DeepFM
    that way)."""
    from elasticdl_tpu.layers import embedding

    (batch,) = jax.export.symbolic_shape("b")
    assert not embedding.compact_lookup_path(
        (4096, 16), jnp.float32, 26 * batch
    )
    assert embedding.compact_lookup_path(
        (4096, 16), jnp.float32, embedding._COMPACT_MIN_CHUNKS * embedding.CHUNK
    )
    assert not embedding.compact_lookup_path(
        (4096, 128), jnp.float32, 1 << 30
    )
