"""The Olmo-Hybrid train cell (`olmo-hybrid-7b.train-l8192-b1-v12544`):
the cell resolves and reports what ISSUE 64 lists; the operations by
shapes against the built model's parameters and a hand count; the plain
reference against the model at a tiny size, its control under the cell's
own rule and its independence of the program; the cell rehearsed end to
end on the CPU at a tiny size (`correct: true`); and every new metric
file read on a recorded (made-up) trace.

The tiny model is built ONCE a module (`seeded`) and the rehearsal runs
once.  What the cell reports is counted from below (it MUST report these),
never as a total (PERF.md section 7 (9), (12))."""

import json
import os
import re
import shutil
import types

import numpy as np
import pytest
from test_rehearsal import WRAPPER, rehearse

from benchmarks import manifest

CELL = "olmo-hybrid-7b.train-l8192-b1-v12544"
CONFIG_FILE = os.path.join(
    manifest.BENCH_DIR, "configs", "olmo-hybrid-7b.json"
)
TRAFFIC_FILE = os.path.join(
    manifest.BENCH_DIR, "traffic", "train-l8192-b1-v12544.json"
)
# one delta-rule layer and one attention layer (published layers 2 and 3),
# heads 2-3 of 6 held, the PUBLISHED delta-rule head widths 96 | 192 (128
# positions are two whole chunks: the kernels run, interpreted, on heads
# padded to 128 | 256), attention heads of 32
TINY_CONFIG = {
    "hidden_size": 64, "intermediate_size": 96, "vocab_size": 128,
    "head_dim": 32, "layers_held": [2, 3], "num_hidden_layers": 2,
    "heads_held": [2, 2], "num_attention_heads": 2,
    "num_key_value_heads": 2, "linear_num_key_heads": 2,
    "linear_num_value_heads": 2, "num_attention_heads_published": 6,
    "num_key_value_heads_published": 6,
    "linear_num_key_heads_published": 6,
    "linear_num_value_heads_published": 6, "use_bf16": False,
}
TINY_TRAFFIC = {
    "minibatch_size": 1, "records_per_task": 8, "seq_len": 128,
    "data": {"format": "tokens", "seq_len": 128, "vocab_size": 128},
}
NEW_METRICS = {
    "olmo_hybrid_train_mfu", "olmo_hybrid_gdn_core_roofline_share",
    "olmo_hybrid_short_conv_roofline_share",
    "olmo_hybrid_gqa_core_roofline_share",
    "olmo_hybrid_gdn_proj_ms_per_step", "gdn_beta_over_one_share",
    "gdn_padded_lanes_share",
}
GDN_RULE = "^%(\\w+_)?gdn_\\w*(fwd|bwd)[_.\\d]* = "


def tiny_config() -> dict:
    config = manifest.load_json(CONFIG_FILE)
    config.update(TINY_CONFIG)
    config["model_params"] = config["model_params"].replace(
        "bf16=True", "bf16=False"
    )
    return config


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny_olmo_hybrid")
    shutil.copytree(
        manifest.BENCH_DIR, root / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    (root / "benchmarks/configs/olmo-hybrid-7b.json").write_text(
        json.dumps(tiny_config())
    )
    path = root / "benchmarks/traffic/train-l8192-b1-v12544.json"
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
    # a delta-rule mixer's 11 leaves and attention's 6, two blocks' two
    # norms and two MLP kernels, embedding, head, final norm
    assert "0 of 28 parameter leaves never received" in out


# ---- the reference at a test's size ---------------------------------------


@pytest.fixture(scope="module")
def seeded():
    """Seeded weights of the tiny model (flat), a batch of 8 sequences,
    the model's own float32 loss and gradients and the reference's."""
    import jax
    import jax.numpy as jnp

    from benchmarks import trees
    from benchmarks.reference import olmo_hybrid as reference
    from elasticdl_tpu.common.model_handler import _call_with_params
    from elasticdl_tpu.layers.step_metrics import STEP_METRICS
    from model_zoo.olmo_hybrid import olmo_hybrid as zoo

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

    def loss_of(params):
        out, _ = model.apply(
            {"params": params}, features, mutable=[STEP_METRICS]
        )
        return out.astype(jnp.float32).mean()

    with jax.default_matmul_precision("highest"):
        got_loss, got = jax.jit(jax.value_and_grad(loss_of))(params)
    loss, want = reference.loss_and_grads(flat, features, None, config)
    return types.SimpleNamespace(
        config=config, flat=flat, features=features,
        labels=np.zeros(8, np.int32), loss=loss,
        want={k: np.asarray(v, np.float32) for k, v in want.items()},
        got_loss=float(got_loss),
        got={k: np.asarray(v, np.float32)
             for k, v in trees.flat(got).items()},
        reference=reference,
    )


def test_the_reference_agrees_with_the_model_at_a_tiny_size(seeded):
    """Float32 on both sides, the kernels interpreted on heads padded
    from 96 | 192: the loss and every one of the 28 leaves."""
    assert seeded.got_loss == pytest.approx(seeded.loss, rel=1e-5)
    assert set(seeded.got) == set(seeded.want) and len(seeded.want) == 28
    for name, want in seeded.want.items():
        error = np.linalg.norm(seeded.got[name] - want) / np.linalg.norm(want)
        assert error < 2e-4, (name, error)


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

    assert held(seeded.want) and held(seeded.got)
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


def test_the_reference_is_independent_of_the_program():
    """`benchmarks/reference/olmo_hybrid.py` imports nothing of
    `elasticdl_tpu/` or `model_zoo/`, computes at the highest matmul
    precision, walks the delta rule token by token and norms q and k over
    the whole projection."""
    path = os.path.join(manifest.BENCH_DIR, "reference", "olmo_hybrid.py")
    with open(path) as f:
        text = f.read()
    imports = re.findall(r"^(?:from|import) ([\w.]+)", text, re.M)
    assert imports and not [
        name for name in imports
        if name.startswith(("elasticdl_tpu", "model_zoo", "flax"))
    ]
    assert 'default_matmul_precision("highest")' in text
    assert "delta_recurrence" in text and "pallas" not in text
    assert "s.beta_scale * jax.nn.sigmoid(b)" in text
    assert "checkpoint_name" not in text and "solve_triangular" not in text


# ---- operations by shapes, and the metric files ---------------------------


def test_flops_olmo_hybrid_against_the_built_model_and_a_hand_count():
    from benchmarks import flops_olmo_hybrid as flops

    config = manifest.load_json(CONFIG_FILE)
    traffic = manifest.load_json(TRAFFIC_FILE)
    tokens = 8192
    assert flops.tokens_per_step(traffic) == tokens
    assert flops.layers(config) == ["linear_attention"] * 3 + [
        "full_attention"
    ]
    assert flops.count(config, True) == 3 and flops.count(config, False) == 1
    assert flops.conv_columns(config) == 3840 == 10 * (96 + 96 + 192)
    # a product is 2 operations a weight: the dense parts are the built
    # model's kernels (tests/test_olmo_hybrid.py counts them from the
    # model): a delta-rule mixer's seven 29,568,000, attention's four
    # 19,660,800, an MLP's 126,812,160, the head's 48,168,960
    assert flops.gdn_proj_flops_per_token(config) == 2 * 29_568_000
    assert flops.attn_proj_flops_per_token(config) == 2 * 19_660_800
    assert flops.dense_ffn_flops_per_token(config) == 2 * 126_812_160
    parts = flops.forward_flops_per_token(config, tokens)
    assert parts["head"] == 2 * 48_168_960
    assert parts["gdn_proj"] == 3 * 2 * 29_568_000
    assert parts["dense_ffn"] == 4 * 2 * 126_812_160
    dense_weights = 3 * 29_568_000 + 19_660_800 + 4 * 126_812_160 + (
        48_168_960
    )
    # every parameter but the embedding's rows, the conv's taps, the
    # decays' seeds and the norms' scales is a product's weight
    assert config["parameters_held"] - dense_weights == (
        48_168_960 + 3 * (15_360 + 20 + 192) + 2_560 + 9 * 3_840
    )
    # the chunked delta rule by hand at the PUBLISHED widths 96 | 192 (not
    # the 128 | 256 the kernels pad to): once a key head Q K^T and K K^T,
    # once a value head three (dk, dv) products and three (C, dv) ones
    core = flops.gdn_core_flops_per_token(config)
    assert core == 10 * 2 * (2 * 64 * 96) + 10 * (
        3 * 2 * 96 * 192 + 3 * 64 * 192
    )
    padded = dict(config, linear_key_head_dim=128, linear_value_head_dim=256)
    assert flops.gdn_core_flops_per_token(padded) > 1.5 * core
    # q k^T and p v, 10 heads of 128, the causal half
    attn = tokens * flops.attn_core_flops_per_token(config, tokens)
    assert attn == 2 * 10 * (128 + 128) * (tokens * (tokens + 1) // 2)
    step = flops.train_flops_per_token(config, tokens) * tokens
    assert 33.2e12 < step < 33.3e12          # 169 ms at the chip's peak
    assert 0.74 < parts["dense_ffn"] / sum(parts.values()) < 0.76
    assert flops.gdn_core_train_flops_per_step(config, traffic) == (
        3 * 3 * core * tokens
    )
    # q, k and theirs at 10 heads of 96, v, o and theirs at 10 of 192
    # (2 bytes), g, beta and theirs float32, three layers
    assert flops.gdn_core_train_bytes_per_step(config, traffic) == 3 * (
        tokens * (4 * 960 * 2 + 4 * 1920 * 2 + 4 * 10 * 4)
    )
    assert flops.short_conv_train_bytes_per_step(config, traffic) == (
        3 * 5 * 2 * tokens * 3840
    )
    assert flops.gqa_core_train_flops_per_step(config, traffic) == 3 * attn
    assert flops.gqa_core_train_bytes_per_step(config, traffic) == (
        2 * 128 * (6 * 10 + 6 * 10) * tokens
    )
    # uncut heads and depth: the delta rule's share of a layer's operations
    uncut = dict(
        config, layers_held=list(range(32)), num_attention_heads=30,
        num_key_value_heads=30, linear_num_key_heads=30,
        linear_num_value_heads=30, vocab_size=100352,
    )
    whole = flops.forward_flops_per_token(uncut, tokens)
    assert 14.0e9 < sum(whole.values()) < 15.5e9     # 2 x 7B and the cores


def test_every_new_layer_metric_names_a_reader_that_imports():
    bench = manifest.load_manifest()
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
        assert CELL in entry["workloads"]
        reader = manifest.import_by_name("readers", spec["reader"])
        # nothing to read (no trace, no gauge set in this process):
        # nothing said, nothing raised
        if spec["reader"] != "registry_gauge":
            assert reader.read(spec.get("params", {}), {"cell": cell}) is None


def test_the_cell_reports_what_the_issue_lists():
    bench = manifest.load_manifest()
    cell = manifest.resolve_cell(bench, CELL)
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
        "gdn_core_ms_per_step", "short_conv_ms_per_step",
        "gqa_core_ms_per_step", "attn_proj_ms_per_step",
        "dense_ffn_ms_per_step", "head_ce_ms_per_step",
        "head_ce_backward_ms_per_step", "lm_adam_ms_per_step",
        "remat_rebuild_ms_per_step", "remat_kept_share",
    } <= reported
    # no routed, latent, KDA, state-space, band or DeepFM metric has
    # anything to read here, nor another model's shares
    assert not {
        name for name in reported
        if name.startswith(("moe_", "mla_", "kda_", "ssd_", "ssm_",
                            "conv_proj_", "arena_", "scatter_",
                            "optimizer_", "window_core", "granite_",
                            "kimi_", "lfm2_", "laguna_", "nemotron_",
                            "qwen3_next_", "smallthinker_", "ouro_",
                            "trip_", "lm_train", "gqa_core_roofline",
                            "gdn_core_roofline", "gdn_proj_",
                            "short_conv_roofline"))
    }
    assert cell.config["vocab_size"] == cell.traffic["data"]["vocab_size"]
    assert cell.config["vocab_size"] == 12544
    assert cell.traffic["minibatch_size"] == 1
    assert cell.traffic["seq_len"] == 8192
    assert cell.traffic["records_per_task"] == 8
    assert cell.traffic["file_tasks"] == 4
    assert cell.traffic["warmup_tasks_after_compile"] == 1
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert "3x their share" in entry["why"] and len(entry["why"]) <= 200
    assert bench["workloads"][-1] == entry
    assert bench["configs"][-1]["name"] == "olmo-hybrid-7b"
    rate = next(
        m for m in bench["end_to_end"] if m["name"] == "train_examples_per_s"
    )
    assert rate["workloads"][-1] == CELL


def test_the_configuration_holds_the_catalog_rows_numbers():
    """Every number of the catalog row under its own key, but the depth,
    the vocabulary and the four head counts, which the file lists as
    reduced beside what each was cut from."""
    config = manifest.load_json(CONFIG_FILE)
    published = {
        "vocab_size": 100352, "hidden_size": 3840,
        "intermediate_size": 11008, "num_hidden_layers": 32,
        "num_attention_heads": 30, "num_key_value_heads": 30,
        "max_position_embeddings": 65536, "rms_norm_eps": 1e-06,
        "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4,
    }
    reduced = set(config["reduced"])
    assert reduced == {
        "num_hidden_layers", "vocab_size", "num_attention_heads",
        "num_key_value_heads", "linear_num_key_heads",
        "linear_num_value_heads",
    } == set(config["reduced_from"])
    for key, value in published.items():
        if key in reduced:
            assert config[key] != value
            assert config[f"{key}_published"] == value
        else:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == len(config["layers_held"]) == 4
    assert config["heads_held"] == [0, 10]
    assert config["layer_types"] == (
        ["linear_attention"] * 3 + ["full_attention"]
    ) * 8
    assert config["linear_allow_neg_eigval"] is True
    assert config["rope_parameters"] == {"rope_theta": None}
    assert config["hidden_act"] == "silu"
    assert config["model_type"] == "olmo_hybrid"
    assert config["attention_bias"] is False
    assert config["tie_word_embeddings"] is False
    # no width is cut: a head of attention is hidden / heads published
    assert config["head_dim"] * 30 == config["hidden_size"]
    entry = next(
        c for c in manifest.load_manifest()["configs"]
        if c["name"] == "olmo-hybrid-7b"
    )
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    for key in ("assumed", "deployment", "fifteen_heads", "parameters_held",
                "papers"):
        assert config[key], key
    assert "head-parallel 3" in config["deployment"]
    for key in ("block_order", "qk_norm", "no_rotary", "conv",
                "projections", "output_norm", "l2norm", "beta",
                "decay_seeds", "mlp", "no_aux_loss", "learning_rate",
                "weights"):
        assert config["assumed"][key], key
    assert "10,915,110,912" in config["fifteen_heads"]
    assert "10,010,150,912" in config["fifteen_heads"]


def test_scope_rules_match_this_models_scopes():
    """`attn_proj_ms_per_step`'s patterns match this model's attention
    scope and no delta-rule scope, `dense_ffn_ms_per_step` its MLP's, and
    the model's own five delta-rule scopes are the new metric's."""
    from benchmarks.readers import scope_ops
    from elasticdl_tpu.common import profiler

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    ours = [s for s in profiler.DEVICE_SCOPES if s.startswith("olmo_hybrid/")]
    assert set(ours) == {
        "olmo_hybrid/embed", "olmo_hybrid/norm", "olmo_hybrid/gdn/proj",
        "olmo_hybrid/gdn/conv", "olmo_hybrid/gdn/decay",
        "olmo_hybrid/gdn/core", "olmo_hybrid/gdn/out", "olmo_hybrid/attn",
        "olmo_hybrid/attn/qk_norm", "olmo_hybrid/dense_ffn",
        "olmo_hybrid/head_ce",
    }

    def matched(metric):
        spec = manifest.load_layer_metric(cell, metric)
        return scope_ops.spelled_out(
            spec["params"]["scopes"], profiler.DEVICE_SCOPES
        )

    assert [s for s in matched("attn_proj_ms_per_step")
            if s.startswith("olmo_hybrid/")] == ["olmo_hybrid/attn"]
    assert [s for s in matched("dense_ffn_ms_per_step")
            if s.startswith("olmo_hybrid/")] == ["olmo_hybrid/dense_ffn"]
    assert matched("olmo_hybrid_gdn_proj_ms_per_step") == [
        s for s in ours if "/gdn/" in s
    ]
    for path, entry in (
        ("OlmoHybrid/layer_0/gdn/olmo_hybrid/gdn/proj/q",
         "olmo_hybrid/gdn/proj"),
        ("OlmoHybrid/layer_3/attn/olmo_hybrid/attn/q", "olmo_hybrid/attn"),
        ("OlmoHybrid/layer_3/attn/olmo_hybrid/attn/qk_norm/q_norm",
         "olmo_hybrid/attn/qk_norm"),
        ("OlmoHybrid/layer_1/olmo_hybrid/dense_ffn/mlp/down",
         "olmo_hybrid/dense_ffn"),
        ("OlmoHybrid/layer_1/olmo_hybrid/norm/ffn_norm", "olmo_hybrid/norm"),
    ):
        assert profiler.catalogue_scope(path) == entry, path


def test_roofline_mfu_and_gauge_readers_on_a_made_up_trace():
    """Half the least time is 50%; the delta rule's share counts the
    PUBLISHED widths whatever the kernels pad to; a trace without the
    kernels says nothing; a step at the peak's pace reads 100% and a run
    without a trace reads as nothing; the gauges read the registry."""
    from benchmarks import flops_olmo_hybrid as flops
    from benchmarks.readers import olmo_hybrid_flops, registry_gauge
    from benchmarks.readers import roofline_olmo_hybrid as roofline

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    peaks = manifest.load_peaks(cell)["TPU v5 lite"]
    for work, spec_name, kernel in (
        ("gdn_core", "olmo_hybrid_gdn_core_roofline_share",
         "%gdn_chunk_fwd.3 = (bf16[1,8192,2560]{2,1,0}, "
         "f32[1,10,128,256,128]{4,3,2,1,0}) custom-call(...)"),
        ("short_conv", "olmo_hybrid_short_conv_roofline_share",
         "%silu_short_conv_fwd.1 = bf16[1,8192,3840]{2,1,0} "
         "custom-call(...)"),
        ("gqa_core", "olmo_hybrid_gqa_core_roofline_share",
         "%causal_attention_fwd.2 = (bf16[1,8192,1280]{2,1,0}) "
         "custom-call(...)"),
    ):
        spec = manifest.load_layer_metric(cell, spec_name)
        assert spec["params"]["work"] == work
        ops, bytes_ = roofline.work_of(work, cell)
        least = max(
            ops / peaks["bf16_flops_per_s"], bytes_ / peaks["hbm_bytes_per_s"]
        )
        if spec["params"].get("bound") == "bytes":
            least = bytes_ / peaks["hbm_bytes_per_s"]
        context = {
            "cell": cell, "peaks": peaks, "trace_steps": 2,
            "trace": {"op_seconds": {
                kernel: 4 * least, "%fusion.9 = bf16[8192,3840] fusion()": 9.0,
            }},
        }
        assert roofline.read(spec["params"], context) == pytest.approx(50.0)
        other = {**context, "trace": {"op_seconds": {"%fusion.1 = f()": 1.0}}}
        assert roofline.read(spec["params"], other) is None
    # the delta rule is held to its bytes at heads this narrow
    ops, bytes_ = roofline.work_of("gdn_core", cell)
    assert bytes_ / peaks["hbm_bytes_per_s"] > ops / peaks["bf16_flops_per_s"]
    with pytest.raises(ValueError, match="unknown work"):
        roofline.read({"work": "mla_core", "include": ["custom"]}, context)
    # the accepted delta-rule and conv metrics read the same names
    for name in ("gdn_core_ms_per_step", "short_conv_ms_per_step",
                 "gqa_core_ms_per_step"):
        spec = manifest.load_layer_metric(cell, name)
        assert CELL in next(
            m for m in manifest.load_manifest()["per_layer"]
            if m["name"] == name
        )["workloads"]
    assert manifest.load_layer_metric(
        cell, "gdn_core_ms_per_step"
    )["params"]["include"] == [GDN_RULE]
    at_peak = 8192 * flops.train_flops_per_token(cell.config, 8192) / (
        peaks["bf16_flops_per_s"]
    )
    traced = {"cell": cell, "peaks": peaks, "chips": 1, "trace_steps": 8,
              "trace": {"window_s": 16 * at_peak, "busy_s": 16 * at_peak}}
    assert olmo_hybrid_flops.read({}, traced) == pytest.approx(50.0)
    plain = {k: v for k, v in traced.items() if k != "trace"}
    assert olmo_hybrid_flops.read({}, plain) is None
    # the two gauges: what the layers of one step set, a mean over them
    import model_zoo.common.delta_net  # noqa: F401  (declares the gauges)
    from elasticdl_tpu.common import metrics as metrics_lib

    registry = metrics_lib.default_registry()
    families = {f.name: f for f in registry.families()}
    for metric, values, spec_name in (
        ("worker_gdn_beta_over_one_ratio", (0.25, 0.75),
         "gdn_beta_over_one_share"),
        ("worker_gdn_padded_lanes_ratio", (0.25, 0.25),
         "gdn_padded_lanes_share"),
    ):
        for layer, value in zip(("layer_0/gdn", "layer_1/gdn"), values):
            families[metric].labels(layer=layer).set(value)
        spec = manifest.load_layer_metric(cell, spec_name)
        assert registry_gauge.read(spec["params"], {}) == pytest.approx(
            sum(values) / 2
        )
    assert registry_gauge.read(
        {"metric": "worker_no_such_family_ratio", "stat": "mean"}, {}
    ) is None
