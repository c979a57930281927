"""The scope table: the compiled text's `op_name` paths parsed into
(scope, phase), the table built on request from a program's kept abstract
arguments, `device_ms_by_scope` over hand-made per-operation seconds, and
the four benchmark models' train steps tiled by `profiler.DEVICE_SCOPES`.
Everything compiles for the CPU here; `tests/test_tpu_compile.py` holds
the same parser to a text compiled for the chip."""

import collections
import json

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from elasticdl_tpu.common import metrics as metrics_lib
from elasticdl_tpu.common import profiler, programs
from elasticdl_tpu.worker.trainer import Trainer

# rows that run no operation of their own
PLUMBING = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")


# ---- the parser, on a step with every shape of path ------------------------


@jax.custom_vjp
def experts(x, w):
    return jnp.tanh(x @ w)


def _experts_fwd(x, w):
    return experts(x, w), (x, w)


def _experts_bwd(residuals, g):
    x, w = residuals
    with jax.named_scope("experts"):
        pulled = g * (1 - jnp.tanh(x @ w) ** 2)
    with jax.named_scope("combine"):
        return pulled @ w.T, x.T @ pulled


experts.defvjp(_experts_fwd, _experts_bwd)


class Block(nn.Module):
    @nn.compact
    def __call__(self, x):
        with jax.named_scope("glm/mla/proj"):
            h = jnp.tanh(nn.Dense(64, name="q")(x))
        with jax.named_scope("glm/dense_ffn"):
            h = nn.Dense(32, name="down")(h)
        return x + h


class Model(nn.Module):
    @nn.compact
    def __call__(self, x):
        for i in range(2):
            x = nn.remat(Block)(name=f"layer_{i}")(x)
        with jax.named_scope("glm/moe"):
            w = self.param("w", nn.initializers.lecun_normal(), (32, 32))
            x = x + experts(x, w)
            # one elementwise pass under two scopes: the CPU compiler
            # fuses it into one operation
            with jax.named_scope("router"):
                y = jnp.exp(x)
            with jax.named_scope("dispatch"):
                x = y * 2.0 + x
        with jax.named_scope("glm/head_ce"):
            out = jax.lax.map(
                lambda rows: jnp.sin(rows) @ jnp.ones((32, 4)),
                x.reshape(4, -1, 32),
            )
        return out.sum()


@pytest.fixture(scope="module")
def stepped():
    """(registry, program, arguments) of a step that has run once."""
    model, optimizer = Model(), optax.adam(1e-3)
    x = jnp.ones((16, 32))
    params = model.init(jax.random.PRNGKey(0), x)

    def step(params, opt_state, x):
        loss, grads = jax.value_and_grad(lambda p: model.apply(p, x))(params)
        with jax.named_scope("train/optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    registry = programs.ProgramRegistry(
        metrics=metrics_lib.MetricsRegistry()
    )
    program = programs.registered_jit("step", step, registry=registry)
    args = (params, optimizer.init(params), x)
    program(*args)
    return registry, program, args


@pytest.fixture(scope="module")
def table(stepped):
    return stepped[0].scope_table("step")


def rows_of(table, entry, phase=None):
    return [
        row for row in table.values()
        if row.entry == entry and row.opcode not in PLUMBING
        and (phase is None or row.phase == phase)
    ]


def test_split_op_name_on_each_shape_of_path():
    split = programs.split_op_name
    assert split("jit(step)/jvp(M)/layer_1/glm/mla/proj/q/dot_general") == (
        "M/layer_1/glm/mla/proj/q", "forward"
    )
    assert split(
        "jit(step)/transpose(jvp(M))/jvp(M)/checkpoint/layer_1/glm/"
        "dense_ffn/up/dot_general"
    ) == ("M/M/layer_1/glm/dense_ffn/up", "backward")
    assert split(
        "jit(step)/transpose(jvp(M))/jvp(M)/checkpoint/"
        "rematted_computation/layer_0/glm/dense_ffn/tanh"
    ) == ("M/M/layer_0/glm/dense_ffn", "rebuild")
    # a scope entered OUTSIDE the differentiated function lands inside
    # the wrapper's brackets; loops, branches and inner jits are structure
    assert split("jit(f)/transpose(jvp(glm/moe))/experts/mul") == (
        "glm/moe/experts", "backward"
    )
    assert split(
        "jit(step)/jvp(M)/glm/head_ce/while/body/closed_call/jit(silu)/mul"
    ) == ("M/glm/head_ce", "forward")
    assert split("jit(f)/jvp(M)/cond/branch_1_fun/cos")[0] == "M"
    assert split("jit(step)/jvp(M)/glm/head_ce/while") == (
        "M/glm/head_ce", "forward"
    )
    assert split("jit(step)/train/optimizer/sub") == (
        "train/optimizer", "forward"
    )
    # merged instructions carry several names; what JAX did not write
    # (a parameter, a compiler-made kernel) has neither scope nor phase
    assert split("jit(f)/router/exp;jit(f)/dispatch/mul")[0] == "router"
    assert split("p['params']['w']") == ("", "")
    assert split("ragged-dot-none") == ("", "")


def test_catalogue_scope_is_the_innermost_entry():
    scope = profiler.catalogue_scope
    assert scope("M/layer_1/moe/glm/moe/routed/dispatch") == "dispatch"
    assert scope("M/layer_1/moe/glm/moe/routed") == "glm/moe"
    assert scope("M/glm/embed/token_embedding/arena/lookup") == "arena/lookup"
    assert scope("M/layer_1/attn_norm") == ""
    assert scope("layer_1/glm/mla/projection") == ""   # whole components
    assert len(set(profiler.DEVICE_SCOPES)) == len(profiler.DEVICE_SCOPES)


def test_the_markers_are_in_this_jax_output(stepped):
    """A JAX that renames `rematted_computation`, `checkpoint` or the
    transposed pass's wrapper must fail here, not read 0 in a metric."""
    _, program, args = stepped
    text = program.compiled_text(*args)
    names = set(programs._OP_NAME.findall(text))
    for marker in (programs.REBUILD_MARKER, programs.BACKWARD_MARKER):
        assert any(marker in name for name in names), marker
    assert any(
        f"/{programs.CHECKPOINT_MARKER}/" in name for name in names
    )
    assert any(name.startswith("jit(") for name in names)


def test_scope_layer_and_the_three_phases(table):
    for entry in ("glm/mla/proj", "glm/dense_ffn"):
        assert rows_of(table, entry, "forward")
        assert rows_of(table, entry, "backward")
    # (the CPU's compiler merges this toy's rebuilt forward with the
    # first one; the models' steps below keep theirs)
    assert not [
        r for r in table.values()
        if r.phase == "rebuild" and "layer_" not in r.scope
    ]
    # module names stay on the path: a rule may ask for one layer
    assert {
        r.scope.split("/glm/")[0].split("/")[-1]
        for r in rows_of(table, "glm/mla/proj")
    } == {"layer_0", "layer_1"}
    # the optimizer is no pass of the model: its phase is `forward`
    assert {r.phase for r in rows_of(table, "train/optimizer")} == {
        "forward"
    }
    # a custom_vjp's hand-written backward keeps the scopes it sets
    assert rows_of(table, "experts", "backward")
    assert rows_of(table, "combine", "backward")


def test_containers_and_their_bodies(table):
    loops = [r for r in table.values() if r.opcode == "while"]
    assert loops and all(r.container for r in loops)
    assert {r.entry for r in loops} == {"glm/head_ce"}
    assert {r.phase for r in loops} == {"forward", "backward"}
    inside = [
        r for r in table.values()
        if r.computation != loops[0].computation
        and r.entry == "glm/head_ce" and r.opcode not in PLUMBING
    ]
    assert inside and not any(r.container for r in inside)


def test_a_fusion_over_two_scopes_is_mixed(table):
    mixed = [r for r in table.values() if len(r.fused) > 1]
    assert any(
        {"router", "dispatch"} <= set(r.fused) for r in mixed
    ), sorted({r.fused for r in table.values() if r.fused})
    # and it is charged whole to the scope of its own metadata
    assert all(r.entry in r.fused for r in mixed if r.entry)


def test_the_table_is_built_on_request_and_counts_no_compile(stepped):
    registry, program, args = stepped
    counts = registry.ledger()["step"]
    assert counts["compiles"] == 1 and counts["signatures"] == 1
    first = registry.scope_table("step")
    assert registry.scope_table("step") is first          # kept
    assert registry.ledger()["step"] == counts            # no ledger entry
    assert registry._scope_builds_total.child_values() == {("step",): 1.0}
    # a call that compiles nothing keeps nothing anew
    program(*args)
    assert registry.scope_table("step") is first
    # a program never compiled here has no table, and none is built
    assert registry.scope_table("never_ran") is None
    fresh = programs.ProgramRegistry(metrics=metrics_lib.MetricsRegistry())
    programs.registered_jit("idle", lambda x: x + 1, registry=fresh)
    assert fresh._scope_builds_total.child_values() == {}


def test_kept_arguments_are_abstract():
    kept = programs.abstract_arguments(
        ({"w": jnp.ones((2, 3), jnp.bfloat16)}, np.zeros((4,), np.int32), 3)
    )
    leaves = jax.tree_util.tree_leaves(kept)
    assert [type(x).__name__ for x in leaves] == [
        "ShapeDtypeStruct", "ShapeDtypeStruct", "int"
    ]
    assert leaves[0].shape == (2, 3) and leaves[0].dtype == jnp.bfloat16
    assert leaves[0].sharding is not None and leaves[1].sharding is None


# ---- the reduction, on hand-made seconds -----------------------------------


def row(opcode, scope, phase="forward", fused=(), computation="main"):
    return programs.ScopeRow(
        opcode, computation, opcode in programs.CONTAINER_OPCODES, scope,
        phase, profiler.catalogue_scope(scope), tuple(fused),
    )


HAND_TABLE = {
    "while.1": row("while", "layer_1/glm/moe/routed/combine"),
    "fusion.1": row("fusion", "layer_1/glm/moe/routed/dispatch",
                    computation="body"),
    "ragged-dot-none.1": row("custom-call",
                             "layer_1/glm/moe/routed/combine/experts",
                             computation="body"),
    "fusion.2": row("fusion", "layer_1/glm/mla/proj", "rebuild",
                    fused=("glm/mla/proj", "glm/norm")),
    "causal_attention_fwd.3": row("custom-call", "layer_1/glm/mla/core"),
    "copy.4": row("copy", "layer_1/glm/mla/core", "backward"),
    "fusion.5": row("fusion", "M/attn_norm"),
}
HAND_SECONDS = {
    "%while.1 = (s32[], f32[8]) while(%tuple.1), body=%body": 0.5,
    "%fusion.1 = f32[8] fusion(%p), calls=%f": 0.2,
    "%ragged-dot-none.1 = bf16[8,8] custom-call(%a, %b)": 0.3,
    "%fusion.2 = f32[8] fusion(%p), calls=%g": 0.1,
    "%causal_attention_fwd.3 = bf16[8] custom-call(%q)": 0.4,
    "%copy.4 = bf16[8] copy(%x)": 0.05,
    "%fusion.5 = f32[8] fusion(%y)": 0.02,
    "%fusion.99 = f32[8] fusion(%z)": 0.01,
}


def test_a_loop_and_its_body_count_once():
    whole = profiler.device_ms_by_scope(HAND_SECONDS, HAND_TABLE)
    by_scope = whole["by_scope"]
    assert by_scope[("dispatch", "forward")] == pytest.approx(0.2)
    assert by_scope[("experts", "forward")] == pytest.approx(0.3)
    assert ("combine", "forward") not in by_scope      # the loop itself
    assert whole["unjoined"] == pytest.approx(0.01)
    assert sum(by_scope.values()) + whole["unjoined"] == pytest.approx(
        sum(HAND_SECONDS.values()) - 0.5
    )
    assert by_scope[("", "forward")] == pytest.approx(0.02)
    assert whole["mixed"] == pytest.approx(0.1)
    assert whole["mixed_ops"] == {"fusion.2": pytest.approx(0.1)}


@pytest.mark.parametrize("kwargs, want", [
    (dict(scopes=["glm/moe"]), 0.0),              # the innermost entry
    (dict(scopes=["dispatch", "combine"]), 0.2),
    (dict(scopes=["dispatch", "experts"]), 0.5),
    (dict(scopes=["layer_1"]), 1.05),             # a module's name
    (dict(scopes=["layer_2"]), 0.0),
    (dict(phase="rebuild"), 0.1),
    (dict(scopes=["glm/mla/core"]), 0.45),
    (dict(scopes=["glm/mla/core"],
          exclude_ops=[r"^%causal_attention_(fwd|dkv|dq)[.\d]* = "]), 0.05),
    (dict(scopes=["glm/mla/core"], phase="forward",
          exclude_ops=["^%causal_attention"]), 0.0),
])
def test_filters_of_the_reduction(kwargs, want):
    kept = profiler.device_ms_by_scope(HAND_SECONDS, HAND_TABLE, **kwargs)
    assert sum(kept["by_scope"].values()) == pytest.approx(want)
    assert kept["unjoined"] == pytest.approx(0.01)


def test_bare_instruction_names_join_too():
    whole = profiler.device_ms_by_scope({"fusion.1": 2.0}, HAND_TABLE)
    assert whole["by_scope"] == {("dispatch", "forward"): 2.0}


def test_the_operators_summary(tmp_path, monkeypatch):
    summary = profiler.scope_summary(HAND_SECONDS, HAND_TABLE, steps=2)
    assert list(summary)[0] == "glm/mla/core"          # largest first
    assert summary["glm/mla/core"]["ms_per_step"] == pytest.approx(225.0)
    assert summary["glm/mla/proj"] == {
        "ms_per_step": pytest.approx(50.0), "rebuilt_share": 1.0
    }
    assert summary["experts"]["rebuilt_share"] == 0.0
    assert summary["(no scope)"]["ms_per_step"] == pytest.approx(10.0)
    assert summary["(unjoined)"]["ms_per_step"] == pytest.approx(5.0)
    # through the writer: one file beside the capture
    monkeypatch.setattr(profiler, "xla_op_seconds", lambda _: HAND_SECONDS)
    registry = programs.default_program_registry()
    monkeypatch.setattr(
        registry, "scope_table",
        lambda name: HAND_TABLE if name == "worker_train_step" else None,
    )
    profiler._write_scope_summary(str(tmp_path), steps=2)
    written = json.loads((tmp_path / "scope_ms.json").read_text())
    assert written["steps"] == 2
    assert written["scopes"]["dispatch"]["ms_per_step"] == pytest.approx(100)
    # no device plane (the CPU backend), no file
    monkeypatch.setattr(profiler, "xla_op_seconds", lambda _: {})
    (tmp_path / "scope_ms.json").unlink()
    profiler._write_scope_summary(str(tmp_path), steps=2)
    assert not (tmp_path / "scope_ms.json").exists()


def test_a_capture_on_the_cpu_writes_no_summary_and_does_not_raise(tmp_path):
    with profiler.trace(str(tmp_path)):
        jnp.ones((4,)).block_until_ready()
    assert not (tmp_path / "scope_ms.json").exists()
    assert profiler.xla_op_seconds(str(tmp_path)) == {}


# ---- the benchmark's four models, at test sizes ----------------------------


def glm():
    from model_zoo.glm import glm_moe_lite as zoo

    return zoo, zoo.custom_model(
        hidden=32, num_layers=3, dense_layers=1, heads=2, q_lora_rank=12,
        kv_lora_rank=8, qk_nope_head_dim=6, qk_rope_head_dim=4,
        v_head_dim=10, dense_width=48, expert_width=16, num_experts=8,
        top_k=2, held_experts=[2, 3], vocab_size=50, mtp_layers=1,
        bf16=True, remat=True,
    )


def laguna():
    from model_zoo.laguna import laguna as zoo

    return zoo, zoo.custom_model(
        hidden=32, num_layers=3, layer_types=[zoo.FULL, zoo.WINDOW, zoo.FULL],
        mlp_layer_types=["dense", "sparse", "sparse"],
        heads_per_layer=[6, 8, 6], kv_heads=2, head_dim=16, window=8,
        dense_width=48, expert_width=16, shared_width=16, num_experts=16,
        top_k=2, held_experts=[4, 4], vocab_size=50, bf16=True, remat=True,
    )


def lfm2():
    from model_zoo.lfm2 import lfm2_moe as zoo

    return zoo, zoo.custom_model(
        hidden=32, layer_types=list(zoo.PUBLISHED_LAYER_TYPES[:8]),
        num_dense_layers=2, layers=[0, 2, 3], heads=4, kv_heads=2,
        dense_width=48, expert_width=16, num_experts=16, top_k=2,
        held_experts=[4, 4], vocab_size=50, bf16=True, remat=True,
    )


def deepfm():
    from model_zoo.deepfm import deepfm_functional_api as zoo

    model = zoo.custom_model(vocab_capacity=4096, embed_dim=16, bf16=True)
    return zoo, model.clone(mlp_dims=(32, 32))


ROUTED = ("router", "dispatch", "experts", "combine")
MODELS = {
    "glm_moe_lite": (glm, ROUTED + (
        "shared", "glm/norm", "glm/mla/proj", "glm/mla/core",
        "glm/mla/out", "glm/dense_ffn", "glm/mtp", "glm/head_ce",
    )),
    "laguna": (laguna, ROUTED + (
        "shared", "laguna/norm", "laguna/attn_full",
        "laguna/attn_window", "laguna/gate", "laguna/dense_ffn",
        "laguna/head_ce",
    )),
    "lfm2_moe": (lfm2, ROUTED + (
        "lfm2/norm", "lfm2/short_conv", "lfm2/attn",
        "lfm2/dense_ffn", "lfm2/head_ce",
    )),
    "deepfm_tower": (deepfm, ("deepfm/tower",)),
}


def step_table(build):
    zoo, model = build()
    rng = np.random.default_rng(0)
    if build is deepfm:
        features = {
            "dense": rng.random((64, 13), dtype=np.float32),
            "sparse": rng.integers(0, 1000, (64, 26)).astype(np.int32),
        }
    else:
        features = {
            "input_ids": rng.integers(0, 50, (8, 16)).astype(np.int32)
        }
    batch = {"features": features, "labels": np.zeros(64, np.int32)[
        :len(next(iter(features.values())))
    ]}
    trainer = Trainer(
        model=model, optimizer=zoo.optimizer(), loss_fn=zoo.loss,
        use_bf16=True,
    )
    state = trainer.init_state(jax.random.PRNGKey(0), batch["features"])
    trainer.train_on_batch(state, batch)
    return programs.default_program_registry().scope_table(
        "worker_train_step"
    )


@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_scopes_tile_a_models_train_step(name):
    build, both_passes = MODELS[name]
    table = step_table(build)
    leaves = [
        r for r in table.values()
        if not r.container and r.opcode not in PLUMBING
    ]
    counted = collections.Counter((r.entry, r.phase) for r in leaves)
    # every scope the model computes under runs in both passes
    for entry in both_passes:
        assert counted[(entry, "forward")], (entry, "forward")
        assert counted[(entry, "backward")], (entry, "backward")
    # the update, the lookup's forward and the backward's scatter (a
    # decoder's token embedding is the arena's too; its cast is `embed`)
    assert counted[("train/optimizer", "forward")]
    assert counted[("arena/lookup", "forward")]
    assert counted[("arena/scatter", "backward")]
    if name == "deepfm_tower":
        assert counted[("arena/combine", "backward")]
    else:
        assert counted[(name.split("_")[0] + "/embed", "forward")]
    # nothing the model does not have
    stems = {"glm", "laguna", "lfm2", "deepfm"} - {name.split("_")[0]}
    assert not [
        entry for entry, _ in counted if entry.split("/")[0] in stems
    ]
    # JAX's remat rebuilds inside the blocks (and the blocked
    # cross-entropy's) only; DeepFM has none
    rebuilt = [r for r in leaves if r.phase == "rebuild"]
    assert bool(rebuilt) == (name != "deepfm_tower")
    # (a fusion with no path of its own has its instructions' entry for
    # a scope, and no layer)
    assert not [
        r for r in rebuilt
        if "layer_" not in r.scope and "mtp_block" not in r.scope
        and not r.entry.endswith("head_ce") and r.scope != r.entry
    ]
    outside = [r for r in leaves if not r.entry]
    assert len(outside) < 0.05 * len(leaves), collections.Counter(
        (r.opcode, r.scope) for r in outside
    ).most_common(8)
