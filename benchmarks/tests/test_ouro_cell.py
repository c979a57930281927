"""The ouro-2.6b cell before chip time is spent: the cell end to end on the
CPU at a tiny size through `run.py`'s driver (as test_smallthinker_cell.py
does its cell), the reference's float8 control under the cell's own rule,
`part_grads` against the batch gradient, `flops_ouro` against a hand count,
every new layer metric resolving to a reader that imports, the readers on a
made-up trace, and the accepted rules against this model's scopes, kernels
and loops.  Nothing these runs time is a measurement.

The tiny model is built ONCE a module (`seeded`) and the rehearsal runs
once: the tier-1 run's clock is nearly spent (ISSUE 59).

What the cell reports is counted from below (it MUST report these), never
as a total: the next PR appends a metric to the cell and a `len(...) ==`
breaks on it (PERF.md section 7 (9), (12))."""

import json
import os
import re
import shutil
import types

import numpy as np
import pytest
from test_rehearsal import WRAPPER, rehearse

from benchmarks import manifest

CELL = "ouro-2.6b.train-l8192-b1-v49152"
CONFIG_FILE = os.path.join(manifest.BENCH_DIR, "configs", "ouro-2.6b.json")
TRAFFIC_FILE = os.path.join(
    manifest.BENCH_DIR, "traffic", "train-l8192-b1-v49152.json"
)
# four trips through two layers: 2 heads of 32 over 2 K/V heads
TINY_CONFIG = {
    "hidden_size": 64, "num_attention_heads": 2, "num_key_value_heads": 2,
    "head_dim": 32, "intermediate_size": 96, "vocab_size": 128,
    "layers_held": [0, 1], "num_hidden_layers": 2, "use_bf16": False,
}
TINY_TRAFFIC = {
    "minibatch_size": 1, "records_per_task": 8, "seq_len": 128,
    "data": {"format": "tokens", "seq_len": 128, "vocab_size": 128},
}
NEW_METRICS = {
    "ouro_train_mfu", "ouro_gqa_core_roofline_share",
    "trip_exit_ms_per_step", "trip_exit_entropy_nats",
}


def tiny_config() -> dict:
    config = manifest.load_json(CONFIG_FILE)
    config.update(TINY_CONFIG)
    config["model_params"] = config["model_params"].replace(
        "bf16=True", "bf16=False"
    )
    return config


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny_ouro")
    shutil.copytree(
        manifest.BENCH_DIR, root / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    (root / "benchmarks/configs/ouro-2.6b.json").write_text(
        json.dumps(tiny_config())
    )
    path = root / "benchmarks/traffic/train-l8192-b1-v49152.json"
    path.write_text(json.dumps({**json.loads(path.read_text()),
                                **TINY_TRAFFIC}))
    (root / "wrapper.py").write_text(WRAPPER.format(repo=manifest.ROOT))
    return root


def test_cell_rehearsal(tiny_root):
    result, out = rehearse(tiny_root, CELL, 1)
    assert result["correct"] is True, out[-3000:]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["end_to_end"]["train_examples_per_s"] > 0
    assert result["end_to_end"]["setup_s"] > 0
    # float32 on both sides here: every number stands beside its limit
    check = re.search(
        r"\|diff\| ([0-9.e+-]+), allowed ([0-9.e+-]+)\).*relative L2 worst "
        r"([0-9.e+-]+) .* optimizer arithmetic worst ([0-9.e+-]+)", out,
    )
    assert check, out[-3000:]
    assert float(check.group(1)) <= float(check.group(2))
    assert float(check.group(3)) < 1e-3 and float(check.group(4)) <= 1.0
    angle = re.search(r"1 - cosine ([0-9.e+-]+) \(at most ([0-9.e+-]+)", out)
    assert angle and float(angle.group(1)) <= float(angle.group(2))
    # 2 x (4 attention kernels, 4 norms, gate | up and down), embedding,
    # head, final norm, the gate's kernel and bias: ONE set, no trip
    assert "0 of 25 parameter leaves never received" in out


# ---- the reference's control, at a test's size ----------------------------


@pytest.fixture(scope="module")
def seeded():
    """Seeded weights of the tiny model (flat), a batch of 8 sequences,
    and the reference's gradient on them."""
    import jax

    from benchmarks import trees
    from benchmarks.reference import ouro as reference
    from elasticdl_tpu.common.model_handler import _call_with_params
    from model_zoo.ouro import ouro as zoo

    config = dict(tiny_config(), use_bf16=True)
    ids = np.random.RandomState(0).randint(
        0, config["vocab_size"], (8, 128)
    ).astype(np.int32)
    model = _call_with_params(
        zoo.custom_model, config["model_params"].format(**config)
    )
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    flat = {k: np.asarray(v) for k, v in trees.flat(params).items()}
    features = {"input_ids": ids}
    loss, want = reference.loss_and_grads(flat, features, None, config)
    return types.SimpleNamespace(
        config=config, flat=flat, features=features,
        labels=np.zeros(8, np.int32), loss=loss,
        want={k: np.asarray(v, np.float32) for k, v in want.items()},
        reference=reference,
    )


def test_control_fails_the_cells_own_rule(seeded):
    """What the cell is held to, with no `STATED_RATIO` in the module:
    every leaf inside `LEAF_REL_L2` of its norm, the cosine over
    `GRAD_COSINE_MIN` (constants read at the cell's size on the chip; a
    test's size only shows the rule applies, that the reference itself
    passes it and that the type below fails it)."""
    from benchmarks.drivers import train

    reference = seeded.reference
    assert not hasattr(reference, "STATED_RATIO")

    def held(got):
        check = train.check_gradient(
            reference, seeded.flat, seeded.features, seeded.labels,
            dict(seeded.config), seeded.want, got,
        )
        assert check["twin_cosine"] is None
        assert check["cosine_floor"] == reference.GRAD_COSINE_MIN
        return check["ok"]

    assert held(seeded.want)
    _, control = reference.loss_and_grads(
        seeded.flat, seeded.features, seeded.labels, seeded.config,
        tower="float8_e4m3fn",
    )
    assert not held({k: np.asarray(v, np.float32)
                     for k, v in control.items()})


def test_part_grads_average_to_the_batch_gradient(seeded):
    parts = seeded.reference.part_grads(
        seeded.flat, seeded.features, seeded.labels, seeded.config, 4
    )
    for name, want in seeded.want.items():
        assert parts[name].shape == (4,) + want.shape
        np.testing.assert_allclose(
            parts[name].mean(axis=0), want, rtol=2e-4,
            atol=1e-6 * max(float(np.abs(want).max()), 1e-30),
        )


def test_the_reference_compiles_one_block_program_for_every_trip(seeded):
    """Two layers, four trips, one kind: the jitted block programs are
    traced once forward and once backward, the parameters their
    arguments, and `trip_grads`' parts sum to the gradient."""
    reference = seeded.reference
    for program in (reference._block_fwd, reference._block_bwd):
        program.clear_cache()
    parts = reference.trip_grads(
        seeded.flat, seeded.features, seeded.labels, seeded.config
    )
    assert reference._block_fwd._cache_size() == 1
    assert reference._block_bwd._cache_size() == 1
    for name, want in seeded.want.items():
        shared = name.startswith(("layer_", "final_norm"))
        assert parts[name].shape == ((4,) if shared else ()) + want.shape
        total = parts[name].sum(axis=0) if shared else parts[name]
        np.testing.assert_allclose(
            total, want, rtol=2e-4,
            atol=1e-6 * max(float(np.abs(want).max()), 1e-30),
        )


def test_the_reference_is_independent_of_the_program():
    """`benchmarks/reference/ouro.py` imports nothing of `elasticdl_tpu/`
    or `model_zoo/`, computes at the highest matmul precision, walks the
    trips as a plain loop over one dictionary of weights and takes the
    exit distribution as plain survival products."""
    path = os.path.join(manifest.BENCH_DIR, "reference", "ouro.py")
    with open(path) as f:
        text = f.read()
    imports = re.findall(r"^(?:from|import) ([\w.]+)", text, re.M)
    assert imports and not [
        name for name in imports
        if name.startswith(("elasticdl_tpu", "model_zoo", "flax"))
    ]
    assert 'default_matmul_precision("highest")' in text
    assert "for _ in range(s.trips):" in text
    assert "survive = survive * jax.nn.sigmoid(-g)" in text
    assert "lax.scan" not in text and "checkpoint_name" not in text


# ---- operations by shapes, and the metric files ---------------------------


def test_flops_ouro_against_a_hand_count():
    from benchmarks import flops_ouro as flops

    config = manifest.load_json(CONFIG_FILE)
    traffic = manifest.load_json(TRAFFIC_FILE)
    tokens = 8192
    assert flops.applications(config) == 24 == 4 * 6
    assert flops.pairs_per_head(tokens) == 33_558_528
    # ONE block application, by hand from the published widths: q, k, v, o
    # 4 x 2,048 x 2,048 and gate, up, down 3 x 2,048 x 5,632
    products = tokens * (
        flops.attn_proj_flops_per_token(config)
        + flops.dense_ffn_flops_per_token(config)
    )
    assert products == 2 * 51_380_224 * tokens
    assert 0.841e12 < products < 0.843e12
    # q k^T and p v, 16 heads of 128, the causal half
    core = tokens * flops.core_flops_per_token(config, tokens)
    assert core == 2 * 16 * (128 + 128) * 33_558_528
    assert 0.274e12 < core < 0.276e12
    head = tokens * flops.head_flops_per_token(config)
    assert head == 2 * 100_663_296 * tokens
    assert 1.649e12 < head < 1.650e12
    parts = flops.forward_flops_per_token(config, tokens)
    assert parts["attn_proj"] == 24 * 2 * 16_777_216
    assert parts["dense_ffn"] == 24 * 2 * 34_603_008
    assert parts["gqa_core"] * tokens == 24 * core
    assert parts["head"] * tokens == 4 * head
    assert parts["exit_gate"] == 3 * 2 * 2048
    step = flops.train_flops_per_token(config, tokens) * tokens
    assert 100.0e12 < step < 100.4e12          # 509 ms at the chip's peak
    # the head's four passes are a fifth of the step here, 3% uncut
    assert 0.19 < parts["head"] / sum(parts.values()) < 0.21
    uncut = dict(config, layers_held=list(range(48)))
    whole = flops.forward_flops_per_token(uncut, tokens)
    assert 0.02 < whole["head"] / sum(whole.values()) < 0.04
    assert flops.core_train_flops_per_step(config, traffic) == (
        3 * 24 * core
    )
    # q, o, dO and dQ at 16 heads (twice forward, four times backward),
    # k, v and theirs at 16 (twice and four times), 2 bytes, 128 columns,
    # every application
    assert flops.core_train_bytes_per_step(config, traffic) == (
        24 * 2 * 128 * (6 * 16 + 6 * 16) * tokens
    )
    # one trip of the same stack is a plain six-layer decoder's count
    plain = flops.forward_flops_per_token(
        dict(config, total_ut_steps=1), tokens
    )
    assert plain["exit_gate"] == 0 and plain["head"] * 4 == parts["head"]


def test_every_new_layer_metric_names_a_reader_that_imports():
    bench = manifest.load_manifest()
    # what the cell MUST report; a later cell may join any of them
    reported = {
        m["name"] for m in bench["per_layer"] if CELL in m["workloads"]
    }
    assert NEW_METRICS <= reported
    cell = manifest.resolve_cell(bench, CELL)
    for name in NEW_METRICS:
        spec = manifest.load_layer_metric(cell, name)
        assert spec["name"] == name
        assert spec["moves"] == "train_examples_per_s"
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["layer"] == spec["layer"]
        assert entry["unit"] == spec["unit"]
        assert entry["workloads"] == [CELL]
        reader = manifest.import_by_name("readers", spec["reader"])
        # nothing to read (no trace, no rate, no gauge set in this
        # process): nothing said, nothing raised
        if name != "trip_exit_entropy_nats":
            assert reader.read(spec.get("params", {}), {"cell": cell}) is None
    # a program without the gauge's family reads as nothing
    from benchmarks.readers import registry_gauge

    assert registry_gauge.read(
        {"metric": "worker_no_such_family_nats", "stat": "mean"}, {}
    ) is None


def test_the_cell_reports_what_the_issue_lists():
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    assert cell.chips == 1
    assert {"train_examples_per_s", "setup_s"} <= {
        m["name"] for m in cell.end_to_end
    }
    reported = {m["name"] for m in cell.per_layer}
    assert NEW_METRICS <= reported
    assert {
        "task_gap_ms", "train_task_rate_median", "data_wait_share",
        "pack_us_per_example", "step_device_ms", "device_idle_share.train",
        "peak_hbm_gib.train", "task_head_wait_ms", "steady_data_wait_ms",
        "task_sync_ms", "loop_unattributed_share", "read_ms_per_task",
        "producer_blocked_share", "scope_unattributed_share",
        "scope_mixed_share", "update_ms_per_step",
    } <= reported
    assert {
        "setup_boot_s", "setup_job_s", "setup_init_state_s",
        "setup_step_trace_s", "setup_step_xla_s", "setup_warmup_run_s",
        "setup_cache_hit_share", "setup_unregistered_compile_s",
        "setup_unattributed_share",
    } <= reported
    assert {
        "gqa_core_ms_per_step", "attn_proj_ms_per_step",
        "dense_ffn_ms_per_step", "head_ce_ms_per_step",
        "lm_adam_ms_per_step", "remat_rebuild_ms_per_step",
        "remat_kept_share",
    } <= reported
    # no routed, latent, delta-rule, state-space, conv, band or DeepFM
    # metric has anything to read here, nor another model's shares
    assert not {
        name for name in reported
        if name.startswith(("moe_", "mla_", "kda_", "gdn_", "ssd_", "ssm_",
                            "short_conv", "conv_proj_", "arena_",
                            "scatter_", "optimizer_", "window_core",
                            "granite_", "kimi_", "lfm2_", "laguna_",
                            "nemotron_", "qwen3_next_", "smallthinker_",
                            "lm_train", "gqa_core_roofline"))
    }
    assert cell.config["vocab_size"] == cell.traffic["data"]["vocab_size"]
    assert cell.config["vocab_size"] == 49152
    assert cell.traffic["minibatch_size"] == 1
    assert cell.traffic["seq_len"] == 8192
    assert cell.traffic["records_per_task"] == 8
    assert cell.traffic["file_tasks"] == 4
    assert cell.traffic["warmup_tasks_after_compile"] == 1
    assert cell.config["learning_rate"] == 1e-5
    entry = next(
        w for w in manifest.load_manifest()["workloads"] if w["name"] == CELL
    )
    assert "24 applications of 192" in entry["why"]
    assert len(entry["why"]) <= 200


def test_the_configuration_holds_the_catalog_rows_numbers():
    """Every number of the catalog row under its own key, but the depth,
    which the file lists as reduced beside what it was cut from."""
    config = manifest.load_json(CONFIG_FILE)
    published = {
        "head_dim": 128, "hidden_size": 2048, "intermediate_size": 5632,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "num_attention_heads": 16, "num_hidden_layers": 48,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_theta": 1000000, "total_ut_steps": 4,
        "early_exit_threshold": 1, "vocab_size": 49152,
    }
    reduced = set(config["reduced"])
    assert reduced == {"num_hidden_layers"} == set(config["reduced_from"])
    for key, value in published.items():
        if key in reduced:
            assert config[key] != value
            assert config[f"{key}_published"] == value
        else:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == len(config["layers_held"]) == 6
    assert config["layer_types"] == ["full_attention"] * 48
    assert config["hidden_act"] == "silu"
    assert config["model_type"] == "ouro"
    assert config["tie_word_embeddings"] is False
    assert config["rope_scaling"] is None
    assert config["sliding_window"] is None
    assert config["use_sliding_window"] is False
    entry = next(
        c for c in manifest.load_manifest()["configs"]
        if c["name"] == "ouro-2.6b"
    )
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    for key in ("assumed", "deployment", "eight_layers", "parameters_held",
                "papers"):
        assert config[key], key
    assert "eight pipeline stages of six" in config["deployment"]
    for key in ("sandwich_norm", "trips", "exit_gate", "objective",
                "exit_beta", "early_exit_threshold", "learning_rate"):
        assert config["assumed"][key], key
    assert "12,185,740,800" in config["eight_layers"]
    assert "14,747,673,088" in config["eight_layers"]


def test_scope_rules_match_this_models_scopes():
    """`attn_proj_ms_per_step`'s patterns match this model's attention
    scope and no other of its scopes, `dense_ffn_ms_per_step` its MLP's,
    and `trip_exit_ms_per_step` names the exit's entry."""
    from benchmarks.readers import scope_ops
    from elasticdl_tpu.common import profiler

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    ours = [s for s in profiler.DEVICE_SCOPES if s.startswith("ouro/")]
    assert set(ours) == {
        "ouro/embed", "ouro/trips", "ouro/norm", "ouro/attn",
        "ouro/dense_ffn", "ouro/exit", "ouro/head_ce",
    }

    def matched(metric):
        spec = manifest.load_layer_metric(cell, metric)
        return scope_ops.spelled_out(
            spec["params"]["scopes"], profiler.DEVICE_SCOPES
        )

    assert [s for s in matched("attn_proj_ms_per_step")
            if s.startswith("ouro/")] == ["ouro/attn"]
    assert [s for s in matched("dense_ffn_ms_per_step")
            if s.startswith("ouro/")] == ["ouro/dense_ffn"]
    assert matched("trip_exit_ms_per_step") == ["ouro/exit"]
    # inside the trips' loop and its remat, forward, rebuilt and backward
    for path, entry in (
        ("Ouro/ouro/trips/layer_3/attn/ouro/attn/q", "ouro/attn"),
        ("Ouro/ouro/trips/layer_3/ouro/norm", "ouro/norm"),
        ("Ouro/ouro/trips/layer_0/ouro/dense_ffn/mlp/down",
         "ouro/dense_ffn"),
        ("Ouro/ouro/trips/final_norm/ouro/norm", "ouro/norm"),
        # what the loop does beside its blocks
        ("Ouro/ouro/trips", "ouro/trips"),
        ("Ouro/ouro/exit/exit_gate", "ouro/exit"),
    ):
        assert profiler.catalogue_scope(path) == entry, path


def test_kernel_and_loop_rules_read_the_names_a_trace_carries():
    """The names the kernels and the loops carry in a trace, as XLA prints
    them: the accepted attention and head metrics read this cell's
    kernels and the cross-entropy's loops, `head_ce_ms_per_step` does NOT
    take the trips' loops (which carry no (rows, vocabulary) array) and
    `attn_proj_ms_per_step` leaves the cores out."""
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    from benchmarks.readers import trace_ops

    ops = {
        "%causal_attention_fwd.3 = (bf16[1,8192,2048]{2,1,0}, "
        "f32[1,16,8192,1]{3,2,1,0}) custom-call(...)": 3.0,
        "%transpose_jvp_causal_attention_dkv.2 = (bf16[1,8192,2048]) "
        "custom-call(...)": 6.0,
        # the cross-entropy's loops, forward and backward: 64 blocks
        "%while.15 = (s32[], f32[64,2048], bf16[64,2048,2048], "
        "bf16[2048,49152]) while(...)": 2.5,
        "%while.14 = (s32[], f32[2048,49152], bf16[64,2048,2048]) "
        "while(...)": 4.0,
        # the trips' loops: the blocks' weights, the stacked states, the
        # float32 gradient of the blocks; the embedding is (49152, 2048)
        "%while.17 = (s32[], bf16[1,8192,2048], bf16[4,1,8192,2048], "
        "bf16[2048,11264], f32[49152,2048]) while(...)": 50.0,
        "%while.16 = (s32[], f32[5632,2048], f32[2048,11264], "
        "bf16[4,1,8192,2048]) while(...)": 70.0,
        "%fusion.9 = bf16[8192,2048]{1,0} fusion(...)": 100.0,
    }
    context = {"cell": cell, "trace_steps": 1, "trace": {"op_seconds": ops}}

    def ms(metric):
        spec = manifest.load_layer_metric(cell, metric)
        return trace_ops.read(spec["params"], context)

    assert ms("gqa_core_ms_per_step") == pytest.approx(9e3)
    assert ms("head_ce_ms_per_step") == pytest.approx(6.5e3)
    spec = manifest.load_layer_metric(cell, "attn_proj_ms_per_step")
    excluded = [re.compile(p) for p in spec["params"]["exclude_ops"]]
    kept = [t for t in ops if not any(p.search(t) for p in excluded)]
    assert len(kept) == 5


def test_the_head_rule_matches_the_cross_entropys_loop_in_the_lowered_step():
    """In the step the model lowers to: the only loops that carry a
    (rows, vocabulary) array are the cross-entropy's; the trips' loop,
    forward and backward, carries none."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.common.model_handler import _call_with_params
    from elasticdl_tpu.layers.step_metrics import AUX_LOSS, STEP_METRICS
    from model_zoo.ouro import ouro as zoo

    config = dict(tiny_config(), vocab_size=384)   # no other size is 384
    model = _call_with_params(
        zoo.custom_model, config["model_params"].format(**config)
    )
    # four states of 1,024 rows are two of the cross-entropy's blocks
    ids = jnp.zeros((1, 1024), jnp.int32)
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), {"input_ids": ids}
    )

    def loss(params):
        out, sown = model.apply(
            {"params": params, STEP_METRICS: shapes[STEP_METRICS]},
            {"input_ids": ids}, mutable=[AUX_LOSS, STEP_METRICS],
        )
        return out.mean() + sum(jax.tree.leaves(sown[AUX_LOSS]))

    text = jax.jit(jax.grad(loss)).lower(shapes["params"]).compile().as_text()
    spec = manifest.load_layer_metric(
        manifest.resolve_cell(manifest.load_manifest(), CELL),
        "head_ce_ms_per_step",
    )
    rule = re.compile(spec["params"]["include"][0].format(vocab_size=384))
    loops = [
        line.strip() for line in text.splitlines()
        if re.match(r"\s+(ROOT )?%while[.\d]* = ", line)
    ]
    head = [line for line in loops if rule.search(line)]
    trips = [line for line in loops if "[4,1,1024,64]" in line]
    assert head and trips, (len(loops), len(head), len(trips))
    assert not set(head) & set(trips)


def test_roofline_and_mfu_readers_on_a_made_up_trace():
    """Half the least time is 50%; a trace without the kernels says
    nothing; a step at the peak's pace reads 100% and a run without a
    trace reads as nothing."""
    from benchmarks import flops_ouro as flops
    from benchmarks.readers import ouro_flops
    from benchmarks.readers import roofline_ouro as roofline

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    peaks = manifest.load_peaks(cell)["TPU v5 lite"]
    ops = flops.core_train_flops_per_step(cell.config, cell.traffic)
    bytes_ = flops.core_train_bytes_per_step(cell.config, cell.traffic)
    # the cores are held to their operations at 8,192 positions
    least = ops / peaks["bf16_flops_per_s"]
    assert least > bytes_ / peaks["hbm_bytes_per_s"]
    context = {
        "cell": cell, "peaks": peaks, "trace_steps": 2, "trace": {
            "op_seconds": {"%k = bf16[1] custom-call()": 4 * least},
        },
    }
    params = {"work": "gqa_core", "include": ["custom-call"]}
    assert roofline.read(params, context) == pytest.approx(50.0)
    assert roofline.read(
        {"work": "gqa_core", "include": ["no such kernel"]}, context
    ) is None
    with pytest.raises(ValueError, match="unknown work"):
        roofline.read({"work": "mla_core", "include": ["custom"]}, context)
    at_peak = 8192 * flops.train_flops_per_token(cell.config, 8192) / (
        peaks["bf16_flops_per_s"]
    )
    traced = {"cell": cell, "peaks": peaks, "chips": 1, "trace_steps": 8,
              "trace": {"window_s": 16 * at_peak, "busy_s": 16 * at_peak}}
    assert ouro_flops.read({}, traced) == pytest.approx(50.0)
    plain = {k: v for k, v in traced.items() if k != "trace"}
    assert ouro_flops.read({}, plain) is None
