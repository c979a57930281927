"""The GLM-4.7-Flash decoder (model_zoo/glm/glm_moe_lite.py) and its
routed expert layer (layers/moe.py: RoutedExperts) at tiny widths on the
CPU, seeded weights: against the plain float32 reference leaf by leaf,
the selection bias and its update, the shares of an expert-parallel
deployment adding up to the uncut layer, no dropped token at any load,
one compile across loads, the chunked walk of the sorted buffer against
the whole-buffer form at every kind of load, and a two-task job through
the CLI."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import trees
from benchmarks.reference import glm_moe_lite as reference
from elasticdl_tpu.layers import moe
from elasticdl_tpu.layers.moe import ROUTER_STATE, RoutedExperts
from elasticdl_tpu.layers.step_metrics import AUX_LOSS, STEP_METRICS
from model_zoo.glm import glm_moe_lite as zoo
from tests import decoder_cases
from tests.decoder_cases import computed, seeded  # noqa: F401

CONFIG = dict(
    hidden_size=32, num_hidden_layers=3, first_k_dense_replace=1,
    num_attention_heads=2, q_lora_rank=12, kv_lora_rank=8,
    qk_nope_head_dim=6, qk_rope_head_dim=4, v_head_dim=10,
    intermediate_size=48, moe_intermediate_size=16,
    n_routed_experts_published=8, num_experts_per_tok=2,
    n_shared_experts=1, held_experts=[2, 3], routed_scaling_factor=1.8,
    bias_update_rate=0.0, vocab_size=50, num_nextn_predict_layers=1,
    mtp_loss_weight=0.3, rope_theta=1e6, rms_norm_eps=1e-5,
    learning_rate=1e-3, use_bf16=True,
)


def the_mtp_loss_is_added(metrics, state, loss, seeded):
    assert AUX_LOSS not in state.model_state
    sown = state.model_state[STEP_METRICS]
    assert float(loss) == pytest.approx(
        float(sown["main_loss"]) + 0.3 * float(sown["mtp_loss"]), rel=1e-5
    )
    assert metrics["layer_1/moe/routed/dropped_tokens"] == 0.0
    assert 0.0 < metrics["layer_1/moe/routed/routed_here_ratio"] < 1.0
    # a buffer no longer than one chunk: one trip, the whole of it
    assert metrics["layer_1/moe/routed/live_chunks_ratio"] == 1.0
    assert metrics["mtp_block/moe/routed/expert_load_imbalance_ratio"] >= 1.0


def job_gauges(registry):
    assert registry.value("worker_moe_dropped_tokens_total") == 0.0
    assert 0.0 < registry.value(
        "worker_moe_routed_here_ratio", layer="layer_1/moe/routed"
    ) < 1.0
    assert registry.value(
        "worker_moe_live_chunks_ratio", layer="layer_1/moe/routed"
    ) == 1.0


DECODER = decoder_cases.Decoder(
    zoo=zoo, reference=reference, cell="glm-4.7-flash", config=CONFIG,
    length=16, seed=0, leaves=60,
    # no remat to 1e-5 of a leaf: XLA on the CPU fuses an MLA block inside
    # a remat's computation otherwise than outside one, the plain remat's
    # gradients differ by the same 1e-6
    no_remat_limit=1e-5,
    trainer_gauges=the_mtp_loss_is_added,
    job=decoder_cases.Job(
        params=(
            "hidden=32;num_layers=3;heads=2;q_lora_rank=12;kv_lora_rank=8;"
            "qk_nope_head_dim=6;qk_rope_head_dim=4;v_head_dim=10;"
            "dense_width=48;expert_width=16;num_experts=8;top_k=2;"
            "held_experts=(0,4);vocab_size=50;remat=True;lr=0.01"
        ),
        gauges=job_gauges, falls_by=0.1, all_the_room=False, seq_len=16,
    ),
)
model_of = DECODER.model_of
TestConformance = decoder_cases.conformance(DECODER)


# ---- the routed layer -----------------------------------------------------


def routed_layer(held=None, experts=8, top_k=2, rate=0.0):
    return RoutedExperts(
        num_experts=experts, top_k=top_k, ffn_dim=16, held_experts=held,
        routed_scaling=1.8, bias_update_rate=rate,
    )


def tokens_of(rows=64, hidden=32, seed=1):
    return jnp.asarray(
        np.random.RandomState(seed).randn(rows, hidden).astype(np.float32)
    )


def routing_of(variables, x, top_k=2):
    """(chosen experts, their weights) as the layer's equations give
    them, in numpy."""
    p = variables["params"]
    scores = 1.0 / (1.0 + np.exp(-np.asarray(x) @ np.asarray(
        p["router_kernel"]
    )))
    bias = np.asarray(
        variables[ROUTER_STATE]["e_score_correction_bias"]
    )
    chosen = np.argsort(-(scores + bias), axis=1)[:, :top_k]
    picked = np.take_along_axis(scores, chosen, axis=1)
    return chosen, 1.8 * picked / picked.sum(axis=1, keepdims=True)


def dense_routed(variables, x, chosen, weights, first=0):
    """Every held expert over the tokens that chose it, no dispatch."""
    p = variables["params"]
    x = np.asarray(x)
    out = np.zeros_like(x)
    for e in range(p["expert_w_down"].shape[0]):
        gate, up = np.split(x @ np.asarray(p["expert_w_gate_up"][e]), 2, -1)
        y = (gate / (1 + np.exp(-gate)) * up) @ np.asarray(
            p["expert_w_down"][e]
        )
        weight = np.where(chosen == first + e, weights, 0.0).sum(axis=1)
        out += weight[:, None] * y
    return out


def test_selection_bias_picks_and_does_not_weigh():
    layer, x = routed_layer(), tokens_of()
    variables = layer.init(jax.random.PRNGKey(0), x)
    bias = np.zeros(8, np.float32)
    bias[5] = 10.0                      # expert 5 wins a slot everywhere
    biased = {
        **variables,
        ROUTER_STATE: {"e_score_correction_bias": jnp.asarray(bias)},
    }
    plain_chosen, _ = routing_of(variables, x)
    chosen, weights = routing_of(biased, x)
    assert (chosen == 5).any(axis=1).all()
    assert not (plain_chosen == 5).any(axis=1).all()
    # the weights are the UNBIASED scores of the chosen, renormalised:
    # the layer's output equals the dense form built from them
    with jax.default_matmul_precision("highest"):
        out = layer.apply(biased, x)
    np.testing.assert_allclose(
        out, dense_routed(biased, x, chosen, weights), rtol=2e-4, atol=2e-5
    )


def test_bias_update_moves_toward_the_mean_load():
    layer, x = routed_layer(rate=0.01), tokens_of()
    variables = layer.init(jax.random.PRNGKey(0), x)
    chosen, _ = routing_of(variables, x)
    loads = np.bincount(chosen.reshape(-1), minlength=8)
    _, updated = layer.apply(variables, x, mutable=[ROUTER_STATE,
                                                    STEP_METRICS])
    bias = np.asarray(updated[ROUTER_STATE]["e_score_correction_bias"])
    np.testing.assert_allclose(
        bias, 0.01 * np.sign(loads.mean() - loads), atol=1e-7
    )
    assert (bias[loads > loads.mean()] < 0).all()
    assert (bias[loads < loads.mean()] > 0).all()
    # rate 0 (the benchmark's configuration) leaves the buffer alone
    _, kept = routed_layer().apply(
        variables, x, mutable=[ROUTER_STATE, STEP_METRICS]
    )
    assert not np.asarray(
        kept[ROUTER_STATE]["e_score_correction_bias"]
    ).any()


def test_the_shares_add_up_to_the_uncut_layer():
    """8 experts over 4 shares of 2: the routed parts of all shares plus
    the shared expert counted ONCE equal the uncut reference's layer."""
    config = dict(CONFIG, held_experts=[0, 8])
    sizes = reference.sizes_of(config, None)
    x = tokens_of(rows=48)
    whole = zoo.MoEFFN(
        32, 8, 2, 16, 1, None, 1.8, 0.0, name=None
    )
    variables = whole.init(jax.random.PRNGKey(3), x)
    p = variables["params"]
    with jax.default_matmul_precision("highest"):
        want = reference.routed(x, p["routed"], sizes, lambda t: t) + (
            reference.swiglu(x, p["shared"], lambda t: t)
        )
        total = np.zeros_like(np.asarray(want))
        for share in range(4):
            first = 2 * share
            held = {
                "router_kernel": p["routed"]["router_kernel"],
                "expert_w_gate_up":
                    p["routed"]["expert_w_gate_up"][first:first + 2],
                "expert_w_down":
                    p["routed"]["expert_w_down"][first:first + 2],
            }
            part = routed_layer(held=(first, 2)).apply(
                {"params": held, ROUTER_STATE: variables[ROUTER_STATE][
                    "routed"]}, x
            )
            total += np.asarray(part)
        total += np.asarray(zoo.SwiGLU(32, 16).apply(
            {"params": p["shared"]}, x
        ))
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("held, here", [((0, 8), 1.0), ((8, 8), 0.0)])
def test_no_token_dropped_at_either_extreme(held, here):
    """A router of 16 outputs whose top 2 always fall in experts 0-7 (a
    large selection bias): a holder of 0-7 takes EVERY slot (the
    worst-case buffer is full), a holder of 8-15 takes none."""
    layer = routed_layer(held=held, experts=16)
    x = tokens_of()
    variables = layer.init(jax.random.PRNGKey(0), x)
    bias = np.where(np.arange(16) < 8, 10.0, 0.0).astype(np.float32)
    variables = {
        **variables,
        ROUTER_STATE: {"e_score_correction_bias": jnp.asarray(bias)},
    }
    with jax.default_matmul_precision("highest"):
        out, sown = layer.apply(variables, x, mutable=[STEP_METRICS])
    metrics = sown[STEP_METRICS]
    assert float(metrics["dropped_tokens"]) == 0.0
    assert float(metrics["routed_here_ratio"]) == here
    chosen, weights = routing_of(variables, x)
    np.testing.assert_allclose(
        out, dense_routed(variables, x, chosen, weights, first=held[0]),
        rtol=2e-4, atol=2e-5,
    )
    assert bool(np.abs(np.asarray(out)).sum() > 0) == bool(here)


def test_one_compile_across_loads():
    layer = routed_layer(held=(0, 4))
    x = tokens_of()
    variables = layer.init(jax.random.PRNGKey(0), x)
    traces = []

    @jax.jit
    def run(variables, x):
        traces.append(1)
        return layer.apply(variables, x, mutable=[STEP_METRICS])

    shares = set()
    for seed in range(4):
        _, sown = run(variables, tokens_of(seed=seed) * (1 + seed))
        shares.add(float(sown[STEP_METRICS]["routed_here_ratio"]))
    assert len(traces) == 1 and len(shares) > 1


# ---- the walk of the sorted buffer ----------------------------------------

WALK_CHUNK = 48          # 64 tokens x top-2 = 128 slots: 3 chunks, 16 padded
# rows each held expert (0-3 of 16) gets: none here; exactly one chunk; one
# chunk + 1 row; a ragged last chunk, group 1 (rows 30-59) across the
# boundary at 48; every slot here
WALK_LOADS = {
    "none": (0, 0, 0, 0),
    "one_chunk": (20, 0, 28, 0),
    "one_chunk_and_a_row": (20, 0, 28, 1),
    "ragged_straddling": (30, 30, 10, 7),
    "every_slot": (32, 32, 32, 32),
}


def steered(loads, tokens=64, hidden=32, experts=16):
    """(tokens, a router kernel) that send exactly `loads[e]` slots to
    expert e < 4 and every other slot to experts 4-15: token t's top 2
    are slots t and t + tokens of one list of experts, its first 16
    features are the router's logits."""
    slots = [e for e, rows in enumerate(loads) for _ in range(rows)]
    slots += [4 + i % 12 for i in range(2 * tokens - len(slots))]
    x = 0.3 * np.random.RandomState(2).randn(tokens, hidden)
    for t in range(tokens):
        first, second = slots[t], slots[tokens + t]
        assert first != second
        x[t, [first, second]] += 3.0
    kernel = np.zeros((hidden, experts), np.float32)
    kernel[:experts] = 2.0 * np.eye(experts)
    return jnp.asarray(x.astype(np.float32)), jnp.asarray(kernel)


def whole_buffer(params, x, held=(0, 4), top_k=2):
    """The plain statement: the layer over its WHOLE worst-case buffer,
    every row gathered, multiplied, activated, weighted and scattered."""
    first, count = held
    scores = jax.nn.sigmoid(jnp.dot(
        x, params["router_kernel"], precision=jax.lax.Precision.HIGHEST
    ))
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(scores), top_k)
    picked = jnp.take_along_axis(scores, idx, axis=1)
    weights = 1.8 * picked / picked.sum(axis=1, keepdims=True)
    local = idx - first
    key = jnp.where((local >= 0) & (local < count), local, count).reshape(-1)
    order = jnp.argsort(key, stable=True)
    token_of = order // top_k
    group_sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
    gate, up = jnp.split(moe.grouped_matmul(
        x[token_of], params["expert_w_gate_up"], group_sizes
    ), 2, axis=-1)
    expert_out = moe.grouped_matmul(
        jax.nn.silu(gate) * up, params["expert_w_down"], group_sizes
    )
    return jnp.zeros_like(x).at[token_of].add(
        expert_out * weights.reshape(-1)[order][:, None]
    )


def walked(loads):
    """(the layer's apply over (params, tokens), params, tokens, rows
    routed here) of one steered load."""
    layer = routed_layer(held=(0, 4), experts=16)
    x, kernel = steered(loads)
    variables = layer.init(jax.random.PRNGKey(0), x)
    params = dict(variables["params"], router_kernel=kernel)

    def apply(params, x):
        out, sown = layer.apply(
            {**variables, "params": params}, x, mutable=[STEP_METRICS]
        )
        return out, sown[STEP_METRICS]

    return apply, params, x, sum(loads)


@pytest.mark.parametrize("load", sorted(WALK_LOADS))
def test_the_walk_is_the_whole_buffer_at_every_load(load, monkeypatch):
    monkeypatch.setattr(moe, "CHUNK", WALK_CHUNK)
    apply, params, x, rows = walked(WALK_LOADS[load])
    cotangent = tokens_of(seed=5)

    def through(function):
        def loss(params, x):
            out, sown = function(params, x)
            return (out * cotangent).sum(), (out, sown)
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)

    with jax.default_matmul_precision("highest"):
        (_, (out, metrics)), (d_params, d_x) = through(apply)(params, x)
        (_, (want, _)), (want_params, want_x) = through(
            lambda params, x: (whole_buffer(params, x), None)
        )(params, x)
    assert float(metrics["routed_here_ratio"]) == rows / 128
    assert float(metrics["dropped_tokens"]) == 0.0
    assert float(metrics["live_chunks_ratio"]) == pytest.approx(
        -(-rows // WALK_CHUNK) / 3
    )
    assert bool(np.abs(np.asarray(out)).sum() > 0) == bool(rows)
    close = dict(rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(out, want, **close)
    np.testing.assert_allclose(d_x, want_x, **close)
    for leaf in ("router_kernel", "expert_w_gate_up", "expert_w_down"):
        np.testing.assert_allclose(
            d_params[leaf], want_params[leaf], err_msg=leaf, **close
        )


def test_the_walk_is_one_program_across_loads(monkeypatch):
    monkeypatch.setattr(moe, "CHUNK", WALK_CHUNK)
    apply = walked(WALK_LOADS["none"])[0]
    traces = []

    def scalar(params, x):
        out, metrics = apply(params, x)
        return out.sum(), metrics["live_chunks_ratio"]

    @jax.jit
    def run(params, x):
        traces.append(1)
        return jax.grad(scalar, has_aux=True)(params, x)

    chunks = []
    for load in sorted(WALK_LOADS):
        _, params, x, _ = walked(WALK_LOADS[load])
        _, ratio = run(params, x)
        chunks.append(round(float(ratio) * 3))
    assert len(traces) == 1
    assert sorted(chunks) == [0, 1, 2, 2, 3]


# ---- through the system ---------------------------------------------------


# ---- MLA lives in model_zoo/common/mla.py ---------------------------------

# sha256 of the sorted "leaf path + shape" lines of a tiny GLM, recorded at
# the commit before `MLA` moved out of `model_zoo/glm/glm_moe_lite.py`, and
# of the program its gradient lowers to (`decoder_cases.grad_program_digest`),
# recorded at the commit before the blocks' products had names (198d98a;
# until then the test held the jaxpr's text, which a name no policy lists
# adds an equation to and the program nothing).
GLM_TREE_DIGEST = (
    "9b2a900c0e4119196b3602646c0cc728d1d053fb0a84cb68ad7b2930d9cee186"
)
# re-recorded on purpose in PR 62 (the cross-entropy makes the head's
# gradient in the pass that makes the logits, `decoder.blocked_nll`, twice
# here: the main head and the MTP module's; the commit before gave
# 6fe1e1dc...); the tree's digest stands.  And in PR 65: a rematerialised
# routed block keeps what its backward reads of the routing by name
# (`layers/moe.py: SAVED_NAMES` in `decoder.SAVED_NAMES`: five `name`s a
# routed layer, three of them on a flat view, and the sigmoid's derivative
# read off the named scores), so its rebuilt forward holds no router
# product, `top_k` or sort; the gradients are the parent's bit for bit
# (`tests/test_remat_plan.py::test_a_rematerialised_routed_block_routes_once`)
# and nothing else of the program moved (the commit before gave 38c04ffc...).
# And in PR 66, for the ORDER of two operations alone: the query's turn goes
# through `decoder.rotary(q, theta, first=nope)`, which makes its
# frequencies BEFORE `ops/rotary.py: halves_turn` splits nope | rope (this
# model's heads of 16 are no shape the one-pass kernel takes); the same
# operations, and the gradients of a seeded step are the parent's bit for
# bit (compared against a `git archive` of the parent; the commit before
# gave 9dd023e3...)
GLM_PROGRAM_DIGEST = (
    "e1226af4c1f26987e4c115f2ec29f1e83c944449c6e62bdd0a8d7270b1af60cc"
)


def test_glm_keeps_its_tree_and_program_through_the_shared_mla(monkeypatch):
    """`MLA` moved to `model_zoo/common/mla.py` with a switch for the
    rotation, an optional low-rank query and a scope prefix: GLM's
    parameter tree (so its checkpoint keys) and the program of its
    gradient are the ones the commits before gave (at a tile of the
    routed walk's products that this model's 64 and 32 are whole
    multiples of, as the cell's 2,048 and 1,536 are of `moe.TILE`: the
    UNPADDED program, `tests/test_remat_plan.py` holds the padded one)."""
    import hashlib

    monkeypatch.setattr(moe, "TILE", 8)

    from model_zoo.common.mla import MLA

    assert zoo.MLA is MLA
    model = zoo.custom_model(
        hidden=64, num_layers=2, dense_layers=1, heads=2, q_lora_rank=32,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16,
        v_head_dim=32, dense_width=128, expert_width=32, num_experts=4,
        top_k=2, vocab_size=128, bf16=True, remat=True,
    )
    feats = {"input_ids": jnp.zeros((2, 128), jnp.int32)}
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), feats)[
        "params"
    ]
    paths = sorted(
        name + str(leaf.shape) for name, leaf in trees.flat(params).items()
    )
    assert hashlib.sha256("\n".join(paths).encode()).hexdigest() == (
        GLM_TREE_DIGEST
    )
    assert decoder_cases.grad_program_digest(model) == GLM_PROGRAM_DIGEST
