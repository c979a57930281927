"""Mixture-of-Experts layer: routing numerics, capacity semantics,
expert-parallel sharding over the mesh `expert` axis, gradient flow."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from elasticdl_tpu.layers import moe
from elasticdl_tpu.layers.moe import MoEMLP, moe_param_sharding
from elasticdl_tpu.layers.step_metrics import STEP_METRICS
from elasticdl_tpu.parallel import mesh as mesh_lib


def _layer(num_experts=4, hidden=16, ffn=32, capacity_factor=4.0):
    layer = MoEMLP(
        num_experts=num_experts, ffn_dim=ffn,
        capacity_factor=capacity_factor,
    )
    x = jnp.asarray(
        np.random.RandomState(0).randn(2, 8, hidden).astype(np.float32)
    )
    params = layer.init(jax.random.PRNGKey(0), x)
    return layer, params, x


def _dense_reference(layer, params, x):
    """Apply each token's top-1 expert directly (no dispatch tensors)."""
    p = params["params"]
    hidden = x.shape[-1]
    tokens = np.asarray(x).reshape(-1, hidden)
    logits = tokens @ np.asarray(p["router"]["kernel"]) + np.asarray(
        p["router"]["bias"]
    )
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    idx = probs.argmax(-1)
    out = np.zeros_like(tokens)
    for i, e in enumerate(idx):
        h = np.maximum(
            tokens[i] @ np.asarray(p["expert_w_in"][e])
            + np.asarray(p["expert_b_in"][e]),
            0.0,
        )
        out[i] = (
            h @ np.asarray(p["expert_w_out"][e])
            + np.asarray(p["expert_b_out"][e])
        ) * probs[i, e]
    return out.reshape(x.shape)


def test_matches_dense_reference_with_ample_capacity():
    layer, params, x = _layer()
    out = layer.apply(params, x)
    ref = _dense_reference(layer, params, x)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-4)


def test_capacity_overflow_drops_tokens_to_zero():
    """With capacity 1 per expert, overflowing tokens contribute zeros
    (Switch semantics: they ride the residual connection)."""
    layer = MoEMLP(num_experts=2, ffn_dim=8, capacity_factor=0.125)
    x = jnp.ones((1, 16, 4), jnp.float32)  # identical tokens, same expert
    params = layer.init(jax.random.PRNGKey(0), x)
    out = np.asarray(layer.apply(params, x))
    flat = out.reshape(16, 4)
    nonzero = (np.abs(flat).sum(-1) > 0).sum()
    assert nonzero <= 2  # at most one slot per expert
    assert (np.abs(flat).sum(-1) == 0).sum() >= 14


def test_expert_parallel_matches_unsharded():
    """Params sharded P('expert', ...) over an expert=2 mesh produce the
    same output as the unsharded layer; the partitioner owns the routing
    all-to-all."""
    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    mesh = mesh_lib.create_mesh(devices, data=4, expert=2)
    layer, params, _ = _layer()
    x = jnp.asarray(
        np.random.RandomState(1).randn(8, 8, 16).astype(np.float32)
    )
    unsharded = layer.apply(params, x)

    def spec_for(path, leaf):
        spec = moe_param_sharding(path, leaf)
        return NamedSharding(mesh, spec if spec is not None else P())

    sharded_params = jax.tree_util.tree_map_with_path(spec_for, params)
    params_on_mesh = jax.device_put(
        params,
        jax.tree_util.tree_map_with_path(spec_for, params),
    )
    x_sharded = jax.device_put(
        x, NamedSharding(mesh, P("data", None, None))
    )
    out = jax.jit(layer.apply)(params_on_mesh, x_sharded)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(unsharded), rtol=1e-4, atol=1e-4
    )
    # expert stacks really live sharded over the expert axis
    w_in = params_on_mesh["params"]["expert_w_in"]
    assert w_in.sharding.spec == P("expert", None, None)


def test_gradients_flow_to_all_param_groups():
    layer, params, x = _layer()

    def loss(p):
        return (layer.apply(p, x) ** 2).sum()

    grads = jax.grad(loss)(params)["params"]
    for name in ("router", "expert_w_in", "expert_w_out"):
        leaves = jax.tree.leaves(grads[name])
        assert any(float(jnp.abs(leaf).sum()) > 0 for leaf in leaves), name


def test_load_balancing_loss_sown_and_trained():
    layer, params, x = _layer()
    # `init` sows too (only flax's own "intermediates" is exempt): apply
    # on the parameters alone, as the Trainer does (`split_variables`)
    _, state = layer.apply(
        {"params": params["params"]}, x, mutable=["aux_loss"]
    )
    (lb_loss,) = state["aux_loss"]["moe_aux_loss"]
    # coef * E * sum(density*proxy) >= coef (Cauchy-Schwarz; = at uniform)
    assert float(lb_loss) >= layer.aux_loss_coef * 0.99

    # ...and the Trainer really adds it to the objective: identical
    # params, aux coefficient on vs off, the reported losses differ by it
    from elasticdl_tpu.worker.trainer import Trainer

    def make_trainer(coef):
        model = MoEMLP(
            num_experts=4, ffn_dim=32, capacity_factor=4.0,
            aux_loss_coef=coef,
        )
        return Trainer(
            model=model,
            optimizer=__import__("optax").sgd(0.0),
            loss_fn=lambda labels, preds: (preds ** 2).mean(),
        )

    x8 = jnp.asarray(
        np.random.RandomState(2).randn(8, 8, 16).astype(np.float32)
    )  # batch divisible by the data axis
    batch = {"features": x8, "labels": jnp.zeros((x8.shape[0],))}
    losses = {}
    for coef in (0.0, 0.5):
        trainer = make_trainer(coef)
        state0 = trainer.init_state(jax.random.PRNGKey(0), x8)
        _, loss = trainer.train_on_batch(state0, batch)
        losses[coef] = float(loss)
    assert losses[0.5] > losses[0.0] + 0.4  # aux term >= coef when sown


def test_moe_bert_trains_end_to_end():
    """The zoo BERT with moe_experts>0 trains under jit on a dp x ep mesh
    and the loss falls — expert parallelism through the full Trainer path."""
    from elasticdl_tpu.common.model_handler import get_model_spec
    from elasticdl_tpu.worker.trainer import Trainer

    spec = get_model_spec(
        "model_zoo", "bert.bert_finetune.custom_model",
        model_params=(
            "hidden=32;num_layers=1;heads=2;mlp_dim=64;max_len=16;"
            "vocab_size=64;moe_experts=2"
        ),
    )
    mesh = mesh_lib.create_mesh(jax.devices(), data=4, expert=2)
    trainer = Trainer(
        model=spec.model, optimizer=spec.optimizer, loss_fn=spec.loss,
        mesh=mesh, param_sharding_fn=spec.param_sharding,
    )
    rng = np.random.RandomState(0)
    batch = {
        "features": {
            "input_ids": rng.randint(0, 64, size=(16, 16)).astype(np.int32)
        },
        "labels": rng.randint(0, 2, 16).astype(np.int32),
    }
    state = trainer.init_state(jax.random.PRNGKey(0), batch["features"])
    first = None
    for _ in range(12):
        state, loss = trainer.train_on_batch(state, batch)
        if first is None:
            first = float(loss)
    assert float(loss) < first


@pytest.mark.parametrize("hidden, ffn, form, padded", [
    # Nemotron's layer: 2,688 = 10.5 x 256, 1,856 = 7.25 x 256
    (2688, 1856, moe.RELU2, 2816 * 2048 / (2688 * 1856) - 1),
    (84, 58, moe.SWIGLU, 256 * 256 / (84 * 58) - 1),
    # GLM's and LFM2's layer, Kimi's: whole tiles, nothing padded
    (2048, 1536, moe.SWIGLU, 0.0),
    (2304, 1024, moe.SWIGLU, 0.0),
])
def test_a_routed_layer_sets_what_its_padding_costs(
        hidden, ffn, form, padded, monkeypatch):
    """`worker_moe_padded_work_ratio`: the grouped products' multiply-adds
    at whole tiles over those at the layer's own widths, minus 1; 0.0
    where the widths are whole already.  A constant of the shapes: set on
    the host under the layer's path as a step that keeps the sown four is
    traced, and no leaf of what the step carries."""
    monkeypatch.setattr(moe, "TILE", 256)

    class Block(nn.Module):
        @nn.compact
        def __call__(self, x):
            return moe.RoutedExperts(
                num_experts=4, top_k=2, ffn_dim=ffn, held_experts=(1, 1),
                form=form, name="routed",
            )(x)

    x = jax.ShapeDtypeStruct((1, 8, hidden), jnp.float32)
    moe.padded_work_ratio.reset()
    variables = jax.eval_shape(Block().init, jax.random.PRNGKey(0), x)
    assert moe.padded_work_ratio.child_values() == {}        # not by `init`
    for mutable, children in (
        (nn.DenyList(STEP_METRICS), {}),       # `decoder.block_shapes`'
        ([STEP_METRICS], {("routed",): pytest.approx(padded, rel=1e-6)}),
    ):
        out, kept = jax.eval_shape(
            lambda v, x: Block().apply(v, x, mutable=mutable), variables, x
        )
        assert out.shape == x.shape
        assert moe.padded_work_ratio.child_values() == children
    assert "padded_work_ratio" not in kept[STEP_METRICS]["routed"]
    got = moe.padded_work_ratio.value(layer="routed")
    assert (got > 0) == (padded > 0)


# sha256 of str(make_jaxpr(grad(sum of the layer's output))) of one routed
# layer at the GLM cell's bfloat16 shape (4 x 4,096 tokens of 2,048, top-4
# of 64 with experts 8-15 held, 1,536 wide), sigmoid scores with the
# balancing buffer and softmax scores, recorded at the commit before the
# layer learnt the routing's source (87e4d23).  BOTH RE-RECORDED ON PURPOSE
# in PR 65: the layer names what a block's backward reads of its routing
# (`moe.SAVED_NAMES`), so the text gains five `name` equations and the six
# `reshape`s of the three flat views (435 -> 446 equations under the sigmoid,
# every other primitive as often as before); the softmax is written out in
# `moe._softmax` so that its exponentials can carry the name (jax's jitted
# `softmax` held its row maximum's dead derivative: 454 -> 455 equations, a
# `max` against -inf, a `div`, two `eq`s' `select_n`s and four broadcasts
# fewer).  The gradients are the parent's bit for bit in both
# (`tests/test_remat_plan.py::test_a_rematerialised_routed_block_routes_once`);
# the commit before gave a7e95908... and 165492cb...
PARENTS_LAYER = {
    moe.SIGMOID:
        "6d78ba5d953ebad5a980527e651d2af3e1f4abd275d75042f82b6a50bdf12922",
    moe.SOFTMAX:
        "23470e290f1e0e037dd09f505fbdbc19bc485428f146c17f2437aed815efb45e",
}


@pytest.mark.parametrize("scores", sorted(PARENTS_LAYER))
def test_a_layer_given_no_routing_source_is_the_parents(scores):
    """`RoutedExperts.__call__(x)` with no `route_from` traces to what it
    traced to before the argument was there."""
    import hashlib

    layer = moe.RoutedExperts(
        num_experts=64, top_k=4, ffn_dim=1536, held_experts=(8, 8),
        routed_scaling=1.8, bias_update_rate=1e-3, dtype=jnp.bfloat16,
        scores=scores,
    )
    x = jax.ShapeDtypeStruct((4, 4096, 2048), jnp.bfloat16)
    variables = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)

    def loss(v, x):
        out, _ = layer.apply(v, x, mutable=[STEP_METRICS, moe.ROUTER_STATE])
        return out.sum()

    text = str(jax.make_jaxpr(
        jax.grad(loss, argnums=(0, 1), allow_int=True)
    )(variables, x))
    assert "0x" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == PARENTS_LAYER[scores]


@pytest.mark.parametrize("form", sorted(moe.FORMS))
def test_the_routing_source_absent_is_the_rows_themselves(form):
    """With no `route_from` the router reads the experts' rows: the
    layer's output and every gradient are bit-equal to the same layer
    handed its rows as the source, and another source routes otherwise
    while the experts' weights see the same rows."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 24, 32), jnp.float32)
    other = jnp.asarray(rng.randn(2, 24, 32), jnp.float32)
    layer = moe.RoutedExperts(
        num_experts=16, top_k=3, ffn_dim=24, held_experts=(4, 8), form=form,
        scores=moe.SOFTMAX,
    )
    variables = layer.init(jax.random.PRNGKey(1), x)

    def through(source):
        def loss(params, x):
            out, sown = layer.apply(
                {"params": params}, x, source(x), mutable=[STEP_METRICS]
            )
            return (out ** 2).sum(), (out, sown[STEP_METRICS])

        (_, (out, sown)), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True
        )(variables["params"], x)
        return out, sown, grads

    out, sown, grads = through(lambda x: None)
    same, same_sown, same_grads = through(lambda x: x)
    np.testing.assert_array_equal(out, same)
    for a, b in zip(jax.tree.leaves(grads[0]), jax.tree.leaves(same_grads[0])):
        np.testing.assert_array_equal(a, b)
    # (handed x twice, x's gradient is the sum of the two uses: the same
    # number, summed in another order)
    np.testing.assert_allclose(grads[1], same_grads[1], rtol=1e-5, atol=1e-6)
    assert jax.tree.map(float, sown) == jax.tree.map(float, same_sown)
    routed, routed_sown, routed_grads = through(lambda x: other)
    assert np.abs(np.asarray(routed - out)).max() > 1e-3
    # the rows' gradient no longer carries the router's
    assert np.abs(np.asarray(routed_grads[1] - grads[1])).max() > 1e-4
    assert float(routed_sown["routed_here_ratio"]) != float(
        sown["routed_here_ratio"]
    ) or float(routed_sown["expert_load_imbalance_ratio"]) != float(
        sown["expert_load_imbalance_ratio"]
    )
