"""model_zoo/ouro against benchmarks/reference/ouro.py on the CPU: float32
leaf by leaf, the kernels' path, bfloat16 inside the twin's rule, ONE set
of leaves whose gradient is the sum of the trips' parts, one trip as a
plain decoder, each norm and the carried state where the equations put
them, the exit distribution and its entropy term, the remat policy bit for
bit, the trips as one loop in the lowered step, the published sizes, the
per-trip gauges through the Trainer, a two-task CLI job and the scopes.

The tiny models (hidden 64, 2 layers, 2 heads of 32, vocabulary 128, 128
positions, four trips and one) are built ONCE a module and the cases share
what they compute: the tier-1 run's clock is nearly spent (ISSUE 59)."""

import functools
import json
import math
import os
import threading
import types

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import datagen, trees
from benchmarks.reference import ouro as reference
from elasticdl_tpu.layers.step_metrics import AUX_LOSS, STEP_METRICS
from model_zoo.common import decoder
from model_zoo.ouro import ouro as zoo
from tests import remat_cases

ROOT = os.path.join(os.path.dirname(__file__), "..")
CONFIG = dict(
    hidden_size=64, num_hidden_layers=2, num_hidden_layers_published=4,
    layers_held=[0, 1], num_attention_heads=2, num_key_value_heads=2,
    head_dim=32, intermediate_size=96, total_ut_steps=4, exit_beta=0.05,
    rope_theta=1e6, vocab_size=128, rms_norm_eps=1e-6, use_bf16=True,
)
MUTABLE = [AUX_LOSS, STEP_METRICS]
# 4 attention kernels, 4 norms, gate | up and down
BLOCK_LEAVES = 10


def model_of(config, **overrides):
    sizes = dict(
        hidden=config["hidden_size"],
        num_layers=config["num_hidden_layers_published"],
        layers=config["layers_held"], heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        dense_width=config["intermediate_size"],
        trips=config["total_ut_steps"], exit_beta=config["exit_beta"],
        rope_theta=config["rope_theta"], vocab_size=config["vocab_size"],
        eps=config["rms_norm_eps"], remat=True,
    )
    sizes.update(overrides)
    return zoo.custom_model(**sizes)


def ids_of(rows, length=128, seed=0):
    return np.random.RandomState(seed).randint(
        0, CONFIG["vocab_size"], (rows, length)
    ).astype(np.int32)


def objective(model, params, state, ids, room=None):
    """The objective the Trainer builds: the mean of the model's
    per-position losses plus everything sown into AUX_LOSS."""
    out, sown = model.apply(
        {"params": params, **state}, {"input_ids": ids}, mutable=MUTABLE,
        **({} if room is None else {"room": room}),
    )
    return zoo.loss(None, out.astype(jnp.float32)) + sum(
        jax.tree.leaves(sown.get(AUX_LOSS, {}))
    )


def loss_and_grads(model, variables, ids, room=None):
    state = {k: v for k, v in variables.items() if k != "params"}
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda params: objective(model, params, state, ids, room)
        ))(variables["params"])
    return float(loss), {
        k: np.asarray(v, np.float32) for k, v in trees.flat(grads).items()
    }


def loss_of(model, variables, ids):
    state = {k: v for k, v in variables.items() if k != "params"}
    with jax.default_matmul_precision("highest"):
        return float(jax.jit(
            lambda params: objective(model, params, state, ids)
        )(variables["params"]))


def seeded_of(config, ids):
    """Seeded weights with every norm's scale and the gate's bias moved
    off their seeds, so that each one's place shows in the numbers."""
    model = model_of(config)
    variables = {
        k: v for k, v in model.init(
            jax.random.PRNGKey(0), {"input_ids": ids}
        ).items() if k != AUX_LOSS       # as the Trainer drops it
    }
    leaves, tree = jax.tree.flatten(variables["params"])
    keys = jax.random.split(jax.random.PRNGKey(5), len(leaves))
    variables["params"] = jax.tree.unflatten(tree, [
        leaf + 0.2 * jax.random.normal(key, leaf.shape)
        if leaf.ndim == 1 else leaf for leaf, key in zip(leaves, keys)
    ])
    flat = {
        k: np.asarray(v) for k, v in trees.flat(variables["params"]).items()
    }
    want_loss, want = reference.loss_and_grads(
        flat, {"input_ids": ids}, None, config
    )
    return types.SimpleNamespace(
        ids=ids, variables=variables, flat=flat, want_loss=want_loss,
        want={k: np.asarray(v) for k, v in want.items()},
    )


@pytest.fixture(scope="module")
def seeded():
    return seeded_of(CONFIG, ids_of(8, seed=5))


@pytest.fixture(scope="module")
def computed(seeded):
    """(loss, gradients) of the float32 model as the cells run it."""
    return loss_and_grads(model_of(CONFIG), seeded.variables, seeded.ids)


def worst_leaf(got, want):
    assert set(got) == set(want)
    errors = {
        name: np.linalg.norm(got[name] - ref) / np.linalg.norm(ref)
        for name, ref in want.items()
    }
    name = max(errors, key=errors.get)
    return name, errors[name]


def test_float32_matches_reference_leaf_by_leaf(seeded, computed):
    loss, got = computed
    assert set(seeded.variables) == {"params", STEP_METRICS}   # no buffer
    assert abs(loss - seeded.want_loss) < 1e-5 * abs(seeded.want_loss)
    assert got["layer_0/attn/q/kernel"].shape == (64, 2 * 32)
    assert got["layer_0/mlp/gate_up/kernel"].shape == (64, 2 * 96)
    assert got["exit_gate/kernel"].shape == (64, 1)
    name, error = worst_leaf(got, seeded.want)
    assert error < 1e-4, (name, error)


def test_the_weights_are_one_set_of_leaves_with_no_trip_in_a_path(computed):
    _, got = computed
    # two blocks, the embedding, the untied head, the final norm, the
    # gate's kernel and bias: what ONE trip would hold, plus the gate
    assert len(got) == 2 * BLOCK_LEAVES + 5
    assert {name.split("/")[0] for name in got} == {
        "layer_0", "layer_1", "token_embedding", "lm_head_kernel",
        "final_norm", "exit_gate",
    }
    assert not [name for name in got if "trip" in name or "scan" in name]
    assert {
        name.split("/")[1] for name in got if name.startswith("layer_0/")
    } == {
        "attn", "mlp", "input_layernorm", "input_layernorm_2",
        "post_attention_layernorm", "post_attention_layernorm_2",
    }


_SCAN = nn.scan


def _scan_cut_at_a_trips_edge(fn, **kwargs):
    return _SCAN(
        lambda model, h, x: fn(model, jax.lax.stop_gradient(h), x), **kwargs
    )


def test_a_leafs_gradient_is_the_sum_of_the_four_trips_parts(
    seeded, computed, monkeypatch
):
    parts = reference.trip_grads(
        seeded.flat, {"input_ids": seeded.ids}, None, CONFIG
    )
    _, got = computed
    shared = [n for n in got if n.startswith(("layer_", "final_norm"))]
    assert len(shared) == 2 * BLOCK_LEAVES + 1
    for name in shared:
        assert parts[name].shape == (4,) + got[name].shape
        # every trip adds its part: none is rounding beside the sum
        norms = np.linalg.norm(parts[name].reshape(4, -1), axis=1)
        assert norms.min() > 1e-3 * norms.max(), name
        error = np.linalg.norm(got[name] - parts[name].sum(axis=0))
        assert error < 1e-4 * np.linalg.norm(got[name]), name
    for name in set(got) - set(shared):
        np.testing.assert_array_equal(parts[name], seeded.want[name])
    # a model that stops the gradient at a trip's edge gives each leaf its
    # trips' parts WITHOUT what flows back through the later trips: it
    # fails the comparison
    monkeypatch.setattr(zoo.nn, "scan", _scan_cut_at_a_trips_edge)
    _, cut = loss_and_grads(model_of(CONFIG), seeded.variables, seeded.ids)
    name, error = worst_leaf(cut, seeded.want)
    assert error > 0.05, (name, error)


def test_one_trip_with_no_entropy_is_a_plain_decoder(seeded):
    config = dict(CONFIG, total_ut_steps=1, exit_beta=0.0)
    model = model_of(config)
    params = {
        k: v for k, v in seeded.variables["params"].items()
        if k != "exit_gate"
    }
    state = {STEP_METRICS: {
        "token_embedding": seeded.variables[STEP_METRICS]["token_embedding"]
    }}
    variables = {"params": params, **state}
    # no gate, no trip's gauge: the leaves of a plain decoder
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), {"input_ids": seeded.ids}
    )
    assert jax.tree.structure(shapes["params"]) == jax.tree.structure(params)
    assert set(shapes[STEP_METRICS]) == {"token_embedding"}
    flat = {k: v for k, v in seeded.flat.items() if "exit_gate" not in k}
    want_loss, want = reference.loss_and_grads(
        flat, {"input_ids": seeded.ids}, None, config
    )
    loss, got = loss_and_grads(model, variables, seeded.ids)
    assert abs(loss - want_loss) < 1e-5 * abs(want_loss)
    name, error = worst_leaf(got, want)
    assert error < 1e-4, (name, error)
    # and it is the plain next-token loss of the one state: no weighing
    out, sown = model.apply(
        variables, {"input_ids": seeded.ids}, mutable=MUTABLE
    )
    assert AUX_LOSS not in sown and out.shape == (8, 127)
    assert float(out.mean()) == pytest.approx(want_loss, rel=1e-5)


# ---- each piece where the equations put it ---------------------------------


def _unnormed_state_carried(config, classes, norm_cls, h):
    for i, (kind, block_cls) in enumerate(zip(config.layers, classes)):
        h = block_cls(config, kind, name=f"layer_{i}")(h)
    return h, norm_cls(config.eps, config.dtype, name="final_norm")(h)


class _NormOutsideTheBranch(zoo.Block):
    """`moved` normed AFTER the residual sum, not inside the branch."""

    moved: str = ""

    @nn.compact
    def __call__(self, x):
        c = self.config

        def norm(name, inside, after=None):
            layer = decoder.RMSNorm(c.eps, c.dtype, name=name)
            if name != self.moved:
                return layer(inside) if after is None else after + layer(
                    inside
                )
            return layer(after + inside)

        y = decoder.GroupedAttention(
            c.hidden, c.heads, c.kv_heads, c.head_dim, c.head_dim ** -0.5,
            c.dtype, rope=c.rope, name="attn",
        )(norm("input_layernorm", x))
        h = norm("input_layernorm_2", y, x)
        y = decoder.SwiGLU(c.hidden, c.dense_width, c.dtype, name="mlp")(
            norm("post_attention_layernorm", h)
        )
        return norm("post_attention_layernorm_2", y, h)


def _moved(name):
    def patch(monkeypatch):
        monkeypatch.setattr(zoo, "Block", functools.partial(
            _NormOutsideTheBranch, moved=name
        ))
    return patch


CONTROLS = {
    "the-unnormed-state-carried": lambda monkeypatch: monkeypatch.setattr(
        zoo, "trip_body", _unnormed_state_carried
    ),
    "norm2-outside-the-branch": _moved("input_layernorm_2"),
    "norm4-outside-the-branch": _moved("post_attention_layernorm_2"),
    # the control's control: the variant block with nothing moved passes
    "nothing-moved": _moved(""),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_departure_from_the_equations_fails_the_comparison(
    seeded, monkeypatch, control
):
    CONTROLS[control](monkeypatch)
    loss = loss_of(model_of(CONFIG, remat=False), seeded.variables,
                   seeded.ids)
    error = abs(loss - seeded.want_loss) / abs(seeded.want_loss)
    # (the carried state's norm shows only through the residual sums: a
    # block's first norm rescales what it reads)
    if control == "nothing-moved":
        assert error < 1e-5
    else:
        assert error > 1e-4, error


def test_the_exit_distribution_is_the_survival_products():
    logits = jnp.asarray(
        np.random.RandomState(3).randn(3, 5, 7) * 4.0, jnp.float32
    )
    log_p = zoo.exit_distribution(logits)
    p = np.asarray(jnp.exp(log_p), np.float64)
    assert p.shape == (4, 5, 7)
    np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-6)
    leave = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    stay = np.cumprod(1.0 - leave, axis=0)
    np.testing.assert_allclose(p[0], leave[0], rtol=1e-5)
    np.testing.assert_allclose(p[2], leave[2] * stay[1], rtol=1e-5)
    # the last trip has no gate: it takes what survived the three
    np.testing.assert_allclose(p[3], stay[2], rtol=1e-5)
    np.testing.assert_allclose(
        p, np.asarray(reference.exit_probabilities(logits)), rtol=1e-4,
        atol=1e-9,
    )
    entropy = -(p * np.asarray(log_p)).sum(axis=0)
    assert entropy.max() <= math.log(4) + 1e-6 and entropy.min() >= 0.0
    even = zoo.exit_distribution(jnp.log(jnp.asarray(
        [1 / 3, 1 / 2, 1.0]
    ))[:, None])                       # lambda = 1/4, 1/3, 1/2: uniform
    np.testing.assert_allclose(np.exp(even), 0.25, rtol=1e-5)
    # a gate that has closed on a trip costs no NaN, in the value or in
    # the gradient, here or in the reference: 0 ln 0 = 0 (the cell's runs
    # get there within fifty steps; the reference's first form gave a
    # gradient that was not a number in three runs of six)
    shut = jnp.asarray([[200.0, -200.0, 30.0], [0.0, 200.0, -30.0],
                        [-200.0, 0.0, 200.0]], jnp.float32)

    def ours(logits):
        log_p = zoo.exit_distribution(logits)
        return -jnp.sum(jnp.exp(log_p) * log_p)

    def theirs(logits):
        return jnp.sum(reference.exit_entropy(
            reference.exit_probabilities(logits)
        ))

    for entropy_of in (ours, theirs):
        value, slope = jax.value_and_grad(entropy_of)(shut)
        assert np.isfinite(float(value)) and np.isfinite(slope).all()
    np.testing.assert_allclose(
        ours(shut), theirs(shut), rtol=1e-5, atol=1e-9
    )
    np.testing.assert_allclose(
        jax.grad(ours)(shut), jax.grad(theirs)(shut), rtol=1e-4, atol=1e-7
    )
    np.testing.assert_allclose(
        jax.grad(ours)(logits), jax.grad(theirs)(logits), rtol=1e-3,
        atol=1e-6,
    )


def test_the_entropy_reaches_the_objective_at_minus_beta(seeded, computed):
    model = model_of(CONFIG)
    out, sown = model.apply(
        seeded.variables, {"input_ids": seeded.ids}, mutable=MUTABLE
    )
    metrics = sown[STEP_METRICS]
    entropy = float(metrics["trip_exit_entropy_nats"])
    assert 0.0 < entropy <= math.log(4)
    (term,) = sown[AUX_LOSS]["exit_entropy"]
    assert float(term) == pytest.approx(-CONFIG["exit_beta"] * entropy)
    loss, got = computed
    assert loss == pytest.approx(float(out.mean()) + float(term), rel=1e-5)
    # the per-position predictions are the exit-weighted sum of the four
    # trips' losses: between the best and the worst trip's
    trips = [float(metrics[f"trip_{t}"]["trip_loss"]) for t in (1, 2, 3, 4)]
    assert min(trips) - 1e-3 < float(out.mean()) < max(trips) + 1e-3
    mass = [float(metrics[f"trip_{t}"]["trip_exit_mass"])
            for t in (1, 2, 3, 4)]
    assert sum(mass) == pytest.approx(1.0, abs=1e-5) and min(mass) > 0.0
    # the exit weights carry gradient to the gate, both of its leaves
    assert np.linalg.norm(got["exit_gate/kernel"]) > 0.0
    assert abs(float(got["exit_gate/bias"][0])) > 0.0
    # another beta moves the objective by the entropy's share alone
    other = loss_of(
        model_of(CONFIG, exit_beta=0.25), seeded.variables, seeded.ids
    )
    assert other - loss == pytest.approx(-0.2 * entropy, rel=1e-3)


# ---- remat, kernels, types --------------------------------------------------


@pytest.mark.parametrize("other", remat_cases.OTHERS)
def test_the_remat_policy_changes_no_bit(seeded, computed, monkeypatch,
                                         other):
    """`remat=True` against the plain `nn.remat`, against no remat at all
    and against every named product kept (four of each, `ffn_out` among
    them), bit for bit."""
    remat_cases.assert_saving_changes_nothing(
        zoo, monkeypatch, other,
        lambda remat, room=None: loss_and_grads(
            model_of(CONFIG, remat=remat), seeded.variables, seeded.ids,
            room,
        ),
        computed,
    )


def test_kernels_match_reference_leaf_by_leaf():
    """A group of ONE at heads of 128 with rotary over the whole head, two
    tiles of 128 positions, two trips: the streaming kernels (interpreted
    here) inside the trips' loop."""
    from elasticdl_tpu.ops.flash_attention import stream_shapes_ok

    config = dict(
        CONFIG, num_attention_heads=2, num_key_value_heads=2, head_dim=128,
        layers_held=[0], num_hidden_layers=1, total_ut_steps=2,
    )
    assert stream_shapes_ok((1, 256, 2, 128), (1, 256, 2, 128),
                            (1, 256, 2, 128))
    seeded = seeded_of(config, ids_of(1, length=256, seed=2))
    loss, got = loss_and_grads(model_of(config), seeded.variables, seeded.ids)
    assert abs(loss - seeded.want_loss) < 1e-4 * abs(seeded.want_loss)
    name, error = worst_leaf(got, seeded.want)
    assert error < 2e-4, (name, error)


def test_bfloat16_inside_the_twins_rule(seeded):
    """The model computing in bfloat16 is held as the benchmark holds a
    cell that states it: to the reference's own bfloat16 twin, leaf by
    leaf and on the angle (`check_gradient`), where the float8 control
    in the step's place fails."""
    from benchmarks.drivers import train

    held = types.SimpleNamespace(
        **{k: getattr(reference, k) for k in dir(reference)
           if not k.startswith("__")},
        STATED_RATIO=reference.TWIN_RATIO,
    )
    # the gate's bias is ONE number, a sum over 1,016 positions' roundings
    # here where the cell's sums 8,191 and the twin's own error in it may
    # come out near nothing: at a test's size it is held as a norm's scale
    held.LEAF_REL_L2 = (("exit_gate/bias$", 4.5e-2),) + reference.LEAF_REL_L2
    features = {"input_ids": seeded.ids}
    labels = np.zeros(len(seeded.ids), np.int32)
    _, got = loss_and_grads(
        model_of(CONFIG, bf16=True), seeded.variables, seeded.ids
    )
    check = train.check_gradient(
        held, seeded.flat, features, labels, CONFIG, seeded.want, got
    )
    assert check["ok"], sorted(
        check["shares"].items(), key=lambda kv: -kv[1]
    )[:4]
    _, control = reference.loss_and_grads(
        seeded.flat, features, labels, CONFIG, tower="float8_e4m3fn"
    )
    control = {k: np.asarray(v, np.float32) for k, v in control.items()}
    assert not train.check_gradient(
        held, seeded.flat, features, labels, CONFIG, seeded.want, control
    )["ok"]


# ---- the program ------------------------------------------------------------


def _step_text(trips):
    model = model_of(dict(CONFIG, total_ut_steps=trips), bf16=True)
    features = {"input_ids": jnp.zeros((1, 128), jnp.int32)}
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), features)
    state = {STEP_METRICS: shapes[STEP_METRICS]}
    return jax.jit(jax.grad(
        lambda params: objective(model, params, state, features["input_ids"])
    )).lower(shapes["params"]).as_text()


def test_the_trips_are_one_loop_in_the_lowered_step():
    """Four trips lower to at most 1.3 times the text of one: the stack is
    traced once and the trips are a `while` of the program, forward and
    backward, beside the cross-entropy's."""
    one, four = _step_text(1), _step_text(4)
    assert len(four) <= 1.3 * len(one), (len(four), len(one))
    assert four.count("stablehlo.while") >= one.count("stablehlo.while")


def test_published_sizes_hold_what_the_configuration_states():
    """The parameters of the cut model at the published widths, counted
    from the built model's shapes: the numbers in the configuration's
    `deployment` and its `parameters_held`, part by part, and the uncut
    model's 2,667,974,657."""
    with open(os.path.join(
        ROOT, "benchmarks", "configs", "ouro-2.6b.json"
    )) as f:
        config = json.load(f)
    from elasticdl_tpu.common.model_handler import _call_with_params

    model = _call_with_params(
        zoo.custom_model, config["model_params"].format(**config)
    )
    held = config["layers_held"]
    assert held == [0, 1, 2, 3, 4, 5]
    assert len(held) == config["num_hidden_layers"]
    c = model.config
    assert c.layers == (zoo.FULL_ATTENTION,) * 6
    assert (c.heads, c.kv_heads, c.head_dim) == (16, 16, 128)
    assert (c.trips, c.exit_beta, c.dense_width) == (4, 0.05, 5632)
    assert c.rope.columns == 128
    assert c.rope.inv_freq[-1] == pytest.approx(1e6 ** (-126 / 128))
    assert c.dtype == jnp.bfloat16 and c.remat and c.eps == 1e-6
    assert set(config["layer_types"]) == {zoo.FULL_ATTENTION}
    assert len(config["layer_types"]) == 48
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), {"input_ids": jnp.zeros((1, 512), jnp.int32)}
    ))
    flat = {
        name: int(np.prod(leaf.shape))
        for name, leaf in trees.flat(shapes["params"]).items()
    }
    by_top = {}
    for name, size in flat.items():
        top = name.split("/")[0]
        by_top[top] = by_top.get(top, 0) + size
    assert by_top == {
        **{f"layer_{i}": 51_388_416 for i in range(6)},
        "token_embedding": 100_663_296, "lm_head_kernel": 100_663_296,
        "final_norm": 2_048, "exit_gate": 2_049,
    }
    assert {
        k[len("layer_1/"):]: v for k, v in flat.items()
        if k.startswith("layer_1/")
    } == {
        "attn/q/kernel": 4_194_304, "attn/k/kernel": 4_194_304,
        "attn/v/kernel": 4_194_304, "attn/o/kernel": 4_194_304,
        "mlp/gate_up/kernel": 23_068_672, "mlp/down/kernel": 11_534_336,
        "input_layernorm/scale": 2_048, "input_layernorm_2/scale": 2_048,
        "post_attention_layernorm/scale": 2_048,
        "post_attention_layernorm_2/scale": 2_048,
    }
    total = sum(by_top.values())
    assert total == config["parameters_held"] == 509_661_185
    assert f"{total:,}" in config["deployment"]
    uncut = 48 * by_top["layer_0"] + total - 6 * by_top["layer_0"]
    assert uncut == 2_667_974_657
    assert f"{uncut:,}" in config["deployment"]
    assert 12 * total > 0.25 * 16.9e9          # over the floor, held alone


# ---- through the system ---------------------------------------------------


def test_the_trips_gauges_publish_four_values_a_step(seeded):
    from elasticdl_tpu.common import metrics as metrics_lib
    from elasticdl_tpu.layers import step_metrics
    from elasticdl_tpu.worker.sync import ModelOwner
    from elasticdl_tpu.worker.trainer import Trainer

    trainer = Trainer(
        model=model_of(CONFIG), optimizer=zoo.optimizer(1e-3),
        loss_fn=zoo.loss,
    )
    batch = {"features": {"input_ids": seeded.ids},
             "labels": np.zeros(len(seeded.ids), np.int32)}
    state = trainer.init_state(jax.random.PRNGKey(0), batch["features"])
    state, loss = trainer.train_on_batch(state, batch)
    owner = ModelOwner.__new__(ModelOwner)
    owner.state, owner.lock = state, threading.Lock()
    value, sown = owner.fetch_loss(loss)
    assert value == pytest.approx(float(loss))
    trips = [f"trip_{t}" for t in (1, 2, 3, 4)]
    assert {path for path in sown if path.startswith("trip_")} == {
        f"{trip}/{leaf}" for trip in trips
        for leaf in ("trip_loss", "trip_exit_mass")
    } | {"trip_exit_entropy_nats"}
    # nothing is left for the summary: every one is declared
    assert not [
        path for path in step_metrics.publish(sown)
        if path.startswith("trip_")
    ]
    registry = metrics_lib.default_registry()
    mass = [
        registry.value("worker_trip_exit_mass_ratio", trip=trip)
        for trip in trips
    ]
    assert sum(mass) == pytest.approx(1.0, abs=1e-5)
    for trip in trips:
        assert registry.value(
            "worker_trip_loss_nats", trip=trip
        ) == pytest.approx(sown[f"{trip}/trip_loss"])
        assert 3.0 < sown[f"{trip}/trip_loss"] < 7.0
    assert len(set(sown[f"{trip}/trip_loss"] for trip in trips)) == 4
    assert registry.value("worker_trip_exit_entropy_nats") == pytest.approx(
        sown["trip_exit_entropy_nats"]
    )
    # the objective the Trainer minimised holds the entropy term
    assert float(loss) == pytest.approx(
        sum(m * sown[f"{trip}/trip_loss"] for m, trip in zip(mass, trips))
        - CONFIG["exit_beta"] * sown["trip_exit_entropy_nats"], abs=0.05
    )


def test_cli_job_of_two_tasks_with_a_falling_loss(tmp_path, monkeypatch):
    from elasticdl_tpu.client.main import main as cli_main
    from elasticdl_tpu.common import metrics as metrics_lib
    from elasticdl_tpu.worker import trainer as trainer_lib
    from elasticdl_tpu.worker.worker import Worker

    # a device with room for every named product: the gauge reads 1
    monkeypatch.setattr(
        trainer_lib, "device_room", lambda mesh: remat_cases.ALL_THE_ROOM
    )
    path = str(tmp_path / "train.tfrecord")
    datagen.write_task_file(
        path, 7, {"format": "tokens", "seq_len": 32, "vocab_size": 50},
        64, 2,
    )
    workers = []
    init = Worker.__init__

    def recording_init(self, *args, **kwargs):
        init(self, *args, **kwargs)
        workers.append(self)

    Worker.__init__ = recording_init
    try:
        rc = cli_main([
            "train", "--model_zoo", os.path.join(ROOT, "model_zoo"),
            "--model_def", "ouro.ouro.custom_model",
            "--model_params",
            "hidden=32;num_layers=4;layers=[0,1];heads=2;kv_heads=2;"
            "head_dim=16;dense_width=48;trips=4;vocab_size=50;remat=True;"
            "lr=0.03",
            "--distribution_strategy", "Local", "--training_data", path,
            "--minibatch_size", "8", "--records_per_task", "64",
            "--num_epochs", "1",
        ])
    finally:
        Worker.__init__ = init
    assert rc == 0
    losses = [float(x) for x in workers[0].losses]
    assert len(losses) == 16                      # two tasks of 8 steps
    assert np.mean(losses[-4:]) < np.mean(losses[:4]) - 0.05
    registry = metrics_lib.default_registry()
    assert registry.value("worker_remat_kept_ratio") == 1.0
    assert 0.0 < registry.value(
        "worker_trip_exit_entropy_nats"
    ) <= math.log(4)
    assert sum(
        registry.value("worker_trip_exit_mass_ratio", trip=f"trip_{t}")
        for t in (1, 2, 3, 4)
    ) == pytest.approx(1.0, abs=1e-4)


def test_the_scopes_reach_the_lowered_operations():
    """The block's scopes carry the model's prefix into the operations'
    names INSIDE the trips' loop, the exit and the head outside it, and
    the profiler's table reads through the loop's structure."""
    from elasticdl_tpu.common import profiler, programs

    model = model_of(CONFIG)
    ids = ids_of(1, length=16)
    variables = model.init(jax.random.PRNGKey(0), {"input_ids": ids})
    text = jax.jit(
        lambda v, ids: model.apply(v, {"input_ids": ids}, mutable=MUTABLE)[0]
    ).lower(variables, ids).as_text(debug_info=True)
    for scope in ("embed", "trips", "norm", "attn", "dense_ffn", "exit",
                  "head_ce"):
        assert f"ouro/{scope}" in profiler.DEVICE_SCOPES
        assert f"ouro/{scope}/" in text, scope
    assert "Scope object" not in text
    inside = ("jit(step)/jvp(Ouro)/ouro/trips/while/body/checkpoint/"
              "layer_1/ouro/norm/add")
    assert programs.split_op_name(inside) == (
        "Ouro/ouro/trips/layer_1/ouro/norm", "forward"
    )
    # a block's scope is the innermost entry inside the loop's; what the
    # loop does beside its blocks is the loop's own
    assert "ouro/trips/while/body" in text
    assert profiler.catalogue_scope(
        "Ouro/ouro/trips/layer_1/attn/ouro/attn/q"
    ) == "ouro/attn"
    assert profiler.catalogue_scope(
        "Ouro/ouro/trips/layer_0/ouro/dense_ffn/mlp/down"
    ) == "ouro/dense_ffn"
    assert profiler.catalogue_scope("Ouro/ouro/trips") == "ouro/trips"
    assert profiler.catalogue_scope("ouro/exit/exit_gate") == "ouro/exit"
