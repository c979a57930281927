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
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import ouro as reference
from elasticdl_tpu.layers.step_metrics import AUX_LOSS, STEP_METRICS
from elasticdl_tpu.ops.flash_attention import stream_shapes_ok
from model_zoo.common import decoder
from model_zoo.ouro import ouro as zoo
from tests import decoder_cases
from tests.decoder_cases import MUTABLE, computed, seeded  # noqa: F401
from tests.decoder_cases import worst_leaf

CONFIG = dict(
    hidden_size=64, num_hidden_layers=2, num_hidden_layers_published=4,
    layers_held=[0, 1], num_attention_heads=2, num_key_value_heads=2,
    head_dim=32, intermediate_size=96, total_ut_steps=4, exit_beta=0.05,
    rope_theta=1e6, vocab_size=128, rms_norm_eps=1e-6, learning_rate=1e-3,
    use_bf16=True,
)
# 4 attention kernels, 4 norms, gate | up and down
BLOCK_LEAVES = 10


def moved_off_their_seeds(params):
    """Every norm's scale and the gate's bias moved off their seeds, so
    that each one's place shows in the numbers."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(5), len(leaves))
    return jax.tree.unflatten(tree, [
        leaf + 0.2 * jax.random.normal(key, leaf.shape)
        if leaf.ndim == 1 else leaf for leaf, key in zip(leaves, keys)
    ])


def test_the_weights_are_one_set_of_leaves_with_no_trip_in_a_path(computed):
    _, got = computed()
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
    _, got = computed()
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
    # (and the share of the blocks' rotary turn the kernel took, which the
    # model sows for its blocks: `decoder.sow_rope_one_pass`)
    assert set(shapes[STEP_METRICS]) == {
        "token_embedding", "rope_one_pass_ratio"
    }
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


def a_departure_fails_the_comparison(self, seeded, monkeypatch, control):
    """This model's own rule in the shared case's place: its controls
    are held on the LOSS (the carried state's norm shows only through the
    residual sums: a block's first norm rescales what it reads), to 1e-4
    of it, and the control's control passes to 1e-5."""
    CONTROLS[control](monkeypatch)
    loss = loss_of(model_of(CONFIG, remat=False), seeded.variables,
                   seeded.ids)
    error = abs(loss - seeded.want_loss) / abs(seeded.want_loss)
    if control == "nothing-moved":
        assert error < 1e-5
    else:
        assert error > 1e-4, error


def float32_also(model, seeded, got):
    assert set(seeded.variables) == {"params", STEP_METRICS}   # no buffer
    assert got["layer_0/attn/q/kernel"].shape == (64, 2 * 32)
    assert got["layer_0/mlp/gate_up/kernel"].shape == (64, 2 * 96)
    assert got["exit_gate/kernel"].shape == (64, 1)


def published_also(model, config, shapes, flat, by_top):
    """Part by part, and the uncut model's 2,667,974,657."""
    held = config["layers_held"]
    assert held == [0, 1, 2, 3, 4, 5]
    assert len(held) == config["num_hidden_layers"]
    c = model.config
    assert c.layers == (zoo.FULL_ATTENTION,) * 6
    assert (c.heads, c.kv_heads, c.head_dim) == (16, 16, 128)
    assert (c.trips, c.exit_beta, c.dense_width) == (4, 0.05, 5632)
    assert c.rope.columns == 128
    assert c.rope.inv_freq[-1] == pytest.approx(1e6 ** (-126 / 128))
    assert c.eps == 1e-6
    assert set(config["layer_types"]) == {zoo.FULL_ATTENTION}
    assert len(config["layer_types"]) == 48
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
    uncut = 48 * by_top["layer_0"] + total - 6 * by_top["layer_0"]
    assert uncut == 2_667_974_657
    assert f"{uncut:,}" in config["deployment"]


def the_trips_gauges_publish_four_values_a_step(sown, state, loss, seeded):
    from elasticdl_tpu.common import metrics as metrics_lib
    from elasticdl_tpu.layers import step_metrics

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


def job_gauges(registry):
    assert 0.0 < registry.value(
        "worker_trip_exit_entropy_nats"
    ) <= math.log(4)
    assert sum(
        registry.value("worker_trip_exit_mass_ratio", trip=f"trip_{t}")
        for t in (1, 2, 3, 4)
    ) == pytest.approx(1.0, abs=1e-4)


def scopes_also(text):
    """The block's scopes reach the operations' names INSIDE the trips'
    loop, the exit and the head outside it, and the profiler's table
    reads through the loop's structure."""
    from elasticdl_tpu.common import profiler, programs

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


DECODER = decoder_cases.Decoder(
    zoo=zoo, reference=reference, cell="ouro-2.6b", config=CONFIG,
    length=128, seed=5, reseed=moved_off_their_seeds,
    # two blocks, the embedding, the untied head, the final norm, the
    # gate's kernel and bias
    leaves=2 * BLOCK_LEAVES + 5, float32_also=float32_also, loss_limit=1e-5,
    # a group of ONE at heads of 128 with rotary over the whole head, two
    # tiles of 128 positions, two trips: the streaming kernels
    # (interpreted here) inside the trips' loop
    kernels=decoder_cases.Kernels(
        config=dict(
            num_attention_heads=2, num_key_value_heads=2, head_dim=128,
            layers_held=[0], num_hidden_layers=1, total_ut_steps=2,
        ),
        length=256,
        admitted=((stream_shapes_ok, (1, 256, 2, 128), (1, 256, 2, 128),
                   (1, 256, 2, 128)),),
    ),
    # (four named products of each, `ffn_out` among them)
    remat_types=(False,),
    # the gate's bias is ONE number, a sum over 1,016 positions' roundings
    # here where the cell's sums 8,191 and the twin's own error in it may
    # come out near nothing: at a test's size it is held as a norm's scale
    twin_held=dict(
        LEAF_REL_L2=(("exit_gate/bias$", 4.5e-2),) + reference.LEAF_REL_L2,
    ),
    controls=CONTROLS,
    published=decoder_cases.Published(
        by_top={
            **{f"layer_{i}": 51_388_416 for i in range(6)},
            "token_embedding": 100_663_296, "lm_head_kernel": 100_663_296,
            "final_norm": 2_048, "exit_gate": 2_049,
        },
        total=509_661_185, also=published_also,
    ),
    trainer_gauges=the_trips_gauges_publish_four_values_a_step,
    job=decoder_cases.Job(
        params=(
            "hidden=32;num_layers=4;layers=[0,1];heads=2;kv_heads=2;"
            "head_dim=16;dense_width=48;trips=4;vocab_size=50;remat=True;"
            "lr=0.03"
        ),
        gauges=job_gauges,
    ),
    scopes=decoder_cases.Scopes(
        prefix="ouro",
        names=("embed", "trips", "norm", "attn", "dense_ffn", "exit",
               "head_ce"),
        remat=True, also=scopes_also,
    ),
)
model_of, loss_and_grads = DECODER.model_of, DECODER.loss_and_grads
loss_of, objective = DECODER.loss_of, DECODER.objective
TestConformance = decoder_cases.conformance(
    DECODER,
    test_a_departure_from_the_mathematics_fails_the_comparison=(
        a_departure_fails_the_comparison
    ),
)


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
    loss, got = computed()
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


# ---- through the system ---------------------------------------------------


