"""The qwen3-next-80b-a3b cell before chip time is spent: the cell end to
end on the CPU at a tiny size through `run.py`'s driver (as
test_nemotron_cell.py does its cell), the reference's float8 control under
the cell's own rule, two reference programs for four layers,
`flops_qwen3_next` against a hand count, every new layer metric resolving
to a reader that imports, the readers on a made-up trace, and the accepted
rules against this model's scopes and kernels.  Nothing these runs time is
a measurement.

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

CELL = "qwen3-next-80b-a3b.train-l8192-b2-v18992"
CONFIG_FILE = os.path.join(
    manifest.BENCH_DIR, "configs", "qwen3-next-80b-a3b.json"
)
TRAFFIC_FILE = os.path.join(
    manifest.BENCH_DIR, "traffic", "train-l8192-b2-v18992.json"
)
# the interval and the cut's four layers stay; 2 key and 4 value heads of
# 8, 4 query heads of 16 over 2 K/V heads with 4 columns rotated, top-10 of
# 512 with experts 0-31 held, 24 wide beside a gated shared expert 24 wide
TINY_CONFIG = {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    "moe_intermediate_size": 24, "shared_expert_intermediate_size": 24,
    "vocab_size": 50, "use_bf16": False,
}
TINY_TRAFFIC = {
    "minibatch_size": 2, "records_per_task": 16, "seq_len": 32,
    "data": {"format": "tokens", "seq_len": 32, "vocab_size": 50},
}
NEW_METRICS = {
    "gdn_core_ms_per_step", "gdn_core_roofline_share",
    "gdn_proj_ms_per_step", "qwen3_next_gqa_core_roofline_share",
    "qwen3_next_short_conv_roofline_share",
    "qwen3_next_moe_experts_roofline_share", "qwen3_next_train_mfu",
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
    root = tmp_path_factory.mktemp("tiny_qwen3_next")
    shutil.copytree(
        manifest.BENCH_DIR, root / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    (root / "benchmarks/configs/qwen3-next-80b-a3b.json").write_text(
        json.dumps(tiny_config())
    )
    path = root / "benchmarks/traffic/train-l8192-b2-v18992.json"
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
    # 3 x 7 delta-rule and 6 attention leaves, 4 x (6 expert leaves and 2
    # norms), embedding, head and final norm
    assert "0 of 62 parameter leaves never received" in out


# ---- the reference's control, at a test's size ----------------------------


@pytest.fixture(scope="module")
def seeded():
    """Seeded weights of the tiny model (flat), a batch of 8 sequences,
    and the reference's gradient on them."""
    import jax

    from benchmarks import trees
    from benchmarks.reference import qwen3_next as reference
    from elasticdl_tpu.common.model_handler import _call_with_params
    from model_zoo.qwen3_next import qwen3_next as zoo

    config = dict(tiny_config(), use_bf16=True)
    ids = np.random.RandomState(0).randint(
        0, config["vocab_size"], (8, 32)
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


def test_the_reference_compiles_one_program_a_layer_kind(seeded):
    """Four layers, two kinds: the jitted block programs are traced twice
    forward and twice backward, the parameters their arguments."""
    reference = seeded.reference
    for program in (reference._block_fwd, reference._block_bwd):
        program.clear_cache()
    reference.loss_and_grads(
        seeded.flat, seeded.features, seeded.labels, seeded.config
    )
    assert reference._block_fwd._cache_size() == 2
    assert reference._block_bwd._cache_size() == 2
    assert reference.layers_of(seeded.config) == [True, True, True, False]


def test_the_reference_is_independent_of_the_program():
    """`benchmarks/reference/qwen3_next.py` imports nothing of `ops/` or
    `model_zoo/`, and computes at the highest matmul precision."""
    path = os.path.join(manifest.BENCH_DIR, "reference", "qwen3_next.py")
    with open(path) as f:
        text = f.read()
    imports = re.findall(r"^(?:from|import) ([\w.]+)", text, re.M)
    assert imports and not [
        name for name in imports
        if name.startswith(("elasticdl_tpu", "model_zoo"))
    ]
    assert 'default_matmul_precision("highest")' in text
    assert "lax.scan(step, state, tokens)" in text       # token by token


# ---- operations by shapes, and the metric files ---------------------------


def test_flops_qwen3_next_against_a_hand_count():
    from benchmarks import flops_qwen3_next as flops

    config = manifest.load_json(CONFIG_FILE)
    traffic = manifest.load_json(TRAFFIC_FILE)
    assert flops.layers(config) == [True, True, True, False]
    assert flops.conv_columns(config) == 8192
    parts = flops.forward_flops_per_token(config, 8192)
    # by hand, from the published widths: W_qkvz 2048 x 12288, W_ba 2048 x
    # 64, W_o 4096 x 2048, in 3 layers
    assert parts["gdn_proj"] == 3 * 2 * (
        2048 * 12288 + 2048 * 64 + 4096 * 2048
    )
    # the chunked form at C = 64: Q K^T and K K^T once a KEY head (16),
    # three passes over a VALUE head's 128 x 128 state and three chunk-wide
    # products (the substitution's half, P U) a value head (32)
    assert parts["gdn_core"] == 3 * (
        16 * 2 * 2 * 64 * 128 + 32 * (3 * 2 * 128 * 128 + 3 * 64 * 128)
    )
    # q | gate at 16 heads of 2 x 256, k and v at 2 heads, o at 16
    assert parts["attn_proj"] == 2 * (
        16_777_216 + 2 * 1_048_576 + 8_388_608
    )
    # the causal half: (L + 1) / 2 keys a query, 16 heads of 256
    assert parts["attn_core"] == 2 * 16 * (256 + 256) * 8193 / 2
    assert parts["moe_router"] == 4 * 2 * 2048 * 512
    assert parts["moe_shared"] == 4 * (2 * 3 * 2048 * 512 + 2 * 2048)
    # ten slots a token, a sixteenth of them on held experts
    assert parts["moe_experts"] == 4 * 2 * 3 * 2048 * 512 * 10 / 16
    assert parts["head"] == 2 * 2048 * 18992
    tokens = 16384
    step = flops.train_flops_per_token(config, 8192) * tokens
    assert 22.7e12 < step < 22.9e12          # 116 ms at the chip's peak
    assert flops.gdn_core_train_flops_per_step(config, traffic) == (
        3 * parts["gdn_core"] * tokens
    )
    # q, k, dq, dk once a KEY head (2,048 columns), v, o and theirs once a
    # value head (4,096) at 2 bytes, g, beta and theirs at 4 a value head
    assert flops.gdn_core_train_bytes_per_step(config, traffic) == (
        (4 * 2048 * 2 + 4 * 4096 * 2 + 4 * 32 * 4) * tokens * 3
    )
    assert flops.short_conv_train_bytes_per_step(config, traffic) == (
        2 * 5 * tokens * 8192 * 3
    )
    assert flops.gqa_core_train_bytes_per_step(config, traffic) == (
        2 * 256 * (6 * 16 + 6 * 2) * tokens
    )
    assert flops.gqa_core_train_flops_per_step(config, traffic) == (
        3 * parts["attn_core"] * tokens
    )
    rows = tokens * 10 / 16
    assert flops.moe_experts_train_flops_per_step(
        config, traffic, 1 / 16
    ) == 3 * 4 * rows * 6 * 2048 * 512
    assert flops.moe_experts_train_bytes_per_step(
        config, traffic, 1 / 16
    ) == 4 * (
        32 * 3 * 2048 * 512 * (3 * 2 + 4)
        + rows * 2 * 3 * (2 * 2048 + 3 * 512)
    )
    # the op's chunk, which the count may not import
    from elasticdl_tpu.ops import gdn

    assert flops.GDN_CHUNK == gdn.CHUNK


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
        reader = manifest.import_by_name("readers", spec["reader"])
        # nothing to read (no trace, no rate, no gauge set in this
        # process): nothing said, nothing raised
        assert reader.read(spec.get("params", {}), {"cell": cell}) is None


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
        "remat_rebuild_ms_per_step", "remat_kept_share",
        "lm_adam_ms_per_step", "head_ce_ms_per_step", "gqa_core_ms_per_step",
        "short_conv_ms_per_step", "attn_proj_ms_per_step",
        "dense_ffn_ms_per_step", "moe_experts_ms_per_step",
        "moe_dispatch_ms_per_step", "moe_walk_ms_per_step",
        "moe_expert_load_max_over_mean", "moe_live_chunks_share",
        "moe_padded_work_share",
    } <= reported
    # no latent, windowed, per-channel delta-rule, state-space, gated-conv
    # or DeepFM metric has anything to read here, nor another model's
    # shares
    assert not {
        name for name in reported
        if name.startswith(("mla_", "window_", "kda_", "ssd_", "ssm_",
                            "conv_proj_", "arena_", "scatter_", "optimizer_",
                            "granite_", "kimi_", "lfm2_", "laguna_",
                            "nemotron_", "lm_train"))
    }
    assert cell.config["vocab_size"] == cell.traffic["data"]["vocab_size"]
    assert cell.config["vocab_size"] == 18992 == 151936 // 8
    assert cell.traffic["minibatch_size"] == 2
    assert cell.traffic["seq_len"] == 8192
    assert cell.traffic["records_per_task"] == 16
    assert cell.config["learning_rate"] == 1e-5
    # `moe_dispatch_ms_per_step`'s {slots}: ten slots a token
    from benchmarks.readers import trace_ops_cell

    assert trace_ops_cell.with_traffic(cell).config["slots"] == 163840


def test_the_configuration_holds_the_catalog_rows_numbers():
    """Every number of the catalog row under its own key, but the three
    the file lists as reduced; each of those beside what it was cut
    from."""
    config = manifest.load_json(CONFIG_FILE)
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_size": 2048, "intermediate_size": 5120,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_value_head_dim": 128, "max_position_embeddings": 262144,
        "moe_intermediate_size": 512, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
        "vocab_size": 151936,
    }
    reduced = set(config["reduced"])
    assert reduced == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert reduced == set(config["reduced_from"])
    for key, value in published.items():
        if key in reduced:
            assert config[key] != value
            assert config[f"{key}_published"] == value
        else:
            assert config[key] == value, key
    entry = next(
        c for c in manifest.load_manifest()["configs"]
        if c["name"] == "qwen3-next-80b-a3b"
    )
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    for key in ("assumed", "deployment", "fifth_layer", "parameters_held"):
        assert config[key], key
    assert "16 v5e chips share each layer" in config["deployment"]


def test_scope_rules_match_this_models_scopes():
    """`attn_proj_ms_per_step`'s patterns match `qwen3_next/attn` ALONE of
    this model's scopes (the scan's gate scope is `decay`, so `*/gate`
    does not take it), `dense_ffn_ms_per_step` reads the shared expert
    (`shared`, inside `qwen3_next/moe`), and the new
    `gdn_proj_ms_per_step` names the scan's five scopes."""
    from benchmarks.readers import scope_ops
    from elasticdl_tpu.common import profiler

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    ours = [s for s in profiler.DEVICE_SCOPES if s.startswith("qwen3_next/")]
    assert len(ours) == 10

    def matched(metric):
        spec = manifest.load_layer_metric(cell, metric)
        return scope_ops.spelled_out(
            spec["params"]["scopes"], profiler.DEVICE_SCOPES
        )

    assert [s for s in matched("attn_proj_ms_per_step")
            if s.startswith("qwen3_next/")] == ["qwen3_next/attn"]
    assert "shared" in matched("dense_ffn_ms_per_step")
    assert not [s for s in matched("dense_ffn_ms_per_step")
                if s.startswith("qwen3_next/")]
    assert matched("gdn_proj_ms_per_step") == [
        "qwen3_next/gdn/proj", "qwen3_next/gdn/conv", "qwen3_next/gdn/decay",
        "qwen3_next/gdn/core", "qwen3_next/gdn/out",
    ]
    # Kimi's metric names Kimi's scopes and reads nothing here
    assert not [s for s in matched("kda_proj_ms_per_step")
                if s.startswith("qwen3_next/")]
    assert set(ours) == {
        "qwen3_next/embed", "qwen3_next/norm", "qwen3_next/head_ce",
        "qwen3_next/attn", "qwen3_next/moe",
        *matched("gdn_proj_ms_per_step"),
    }
    assert profiler.catalogue_scope(
        "layer_1/moe/qwen3_next/moe/routed/experts"
    ) == "experts"
    assert profiler.catalogue_scope(
        "layer_1/moe/qwen3_next/moe/shared"
    ) == "shared"
    assert profiler.catalogue_scope(
        "layer_0/gdn/qwen3_next/gdn/decay"
    ) == "qwen3_next/gdn/decay"


def test_kernel_rules_read_the_names_a_trace_carries():
    """The names the kernels carry in a trace, as XLA prints them: the new
    scan metrics read `gdn_chunk_*` and the accepted KDA metric does not;
    the accepted conv, attention, expert and dispatch metrics read this
    cell's kernels; the scan's projections' metric leaves the scan's and
    the conv's kernels out."""
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    from benchmarks.readers import trace_ops, trace_ops_cell

    ops = {
        "%gdn_chunk_fwd.3 = (bf16[2,8192,4096]{2,1,0}, "
        "f32[2,32,128,128,128]{4,3,2,1,0}) custom-call(...)": 3.0,
        "%checkpoint_gdn_chunk_fwd_.2 = (bf16[2,8192,4096]{2,1,0}) "
        "custom-call(...)": 2.0,
        "%transpose_jvp_gdn_chunk_bwd__.1 = (bf16[2,8192,2048]{2,1,0}) "
        "custom-call(...)": 6.0,
        "%silu_short_conv_fwd.1 = bf16[2,8192,8192]{2,1,0} "
        "custom-call(...)": 5.0,
        "%silu_short_conv_bwd = (bf16[2,8192,8192]{2,1,0}) "
        "custom-call(...)": 8.0,
        "%causal_attention_dkv.1 = (bf16[2,8192,4096]) custom-call(...)": 7.0,
        "%causal_attention_fwd = (bf16[2,8192,4096]) custom-call(...)": 4.0,
        "%ragged-dot-none.4 = bf16[16384,1024]{1,0} custom-call(...)": 9.0,
        "%sort.2 = (s32[163840]{0}, s32[163840]{0}) sort(...)": 1.5,
        "%fusion.9 = bf16[16384,12288]{1,0} fusion(...)": 100.0,
    }
    context = {"cell": cell, "trace_steps": 1, "trace": {"op_seconds": ops}}

    def ms(metric, reader=trace_ops):
        spec = manifest.load_layer_metric(cell, metric)
        return reader.read(spec["params"], context)

    assert ms("gdn_core_ms_per_step") == pytest.approx(11e3)
    assert ms("kda_core_ms_per_step") is None
    assert ms("short_conv_ms_per_step") == pytest.approx(13e3)
    assert ms("gqa_core_ms_per_step") == pytest.approx(11e3)
    assert ms("moe_experts_ms_per_step") == pytest.approx(9e3)
    assert ms("moe_dispatch_ms_per_step", trace_ops_cell) == (
        pytest.approx(1.5e3)
    )
    spec = manifest.load_layer_metric(cell, "gdn_proj_ms_per_step")
    excluded = [re.compile(p) for p in spec["params"]["exclude_ops"]]
    kept = [t for t in ops if not any(p.search(t) for p in excluded)]
    assert sorted(t.split(" ")[0] for t in kept) == [
        "%causal_attention_dkv.1", "%causal_attention_fwd", "%fusion.9",
        "%ragged-dot-none.4", "%sort.2",
    ]


def test_roofline_readers_on_a_made_up_trace():
    """Half the least time is 50%; a trace without the kernels says
    nothing; the experts' work follows the gauge the layers set."""
    from benchmarks import flops_qwen3_next as flops
    from benchmarks.readers import roofline_qwen3_next as roofline
    from elasticdl_tpu.common import metrics as metrics_lib

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    peaks = manifest.load_peaks(cell)["TPU v5 lite"]

    def least(work, *extra):
        ops = getattr(flops, f"{work}_train_flops_per_step")
        bytes_ = getattr(flops, f"{work}_train_bytes_per_step")
        return max(
            ops(cell.config, cell.traffic, *extra)
            / peaks["bf16_flops_per_s"],
            bytes_(cell.config, cell.traffic, *extra)
            / peaks["hbm_bytes_per_s"],
        )

    def context_of(seconds):
        return {
            "cell": cell, "peaks": peaks, "trace_steps": 2, "trace": {
                "op_seconds": {"%k = bf16[1] custom-call()": 4 * seconds},
            },
        }

    for work in ("gdn_core", "short_conv", "gqa_core"):
        context = context_of(least(work))
        params = {"work": work, "include": ["custom-call"]}
        assert roofline.read(params, context) == pytest.approx(50.0)
        assert roofline.read(
            {"work": work, "include": ["no such kernel"]}, context
        ) is None
    # the scalar scan is held to its operations (two value heads share a
    # key head's traffic), the attention core to its operations too
    assert flops.gdn_core_train_flops_per_step(
        cell.config, cell.traffic
    ) / peaks["bf16_flops_per_s"] > flops.gdn_core_train_bytes_per_step(
        cell.config, cell.traffic
    ) / peaks["hbm_bytes_per_s"]
    spec = manifest.load_layer_metric(
        cell, "qwen3_next_short_conv_roofline_share"
    )
    assert spec["params"]["bound"] == "bytes"
    gauge = metrics_lib.default_registry().gauge(
        "worker_moe_routed_here_ratio", labelnames=("layer",)
    )
    params = {"work": "moe_experts", "include": ["custom-call"]}
    for layer, share in (("layer_1/moe/routed", 0.05),
                         ("layer_3/moe/routed", 0.075)):
        gauge.labels(layer=layer).set(share)
    assert roofline.read(
        params, context_of(least("moe_experts", 0.0625))
    ) == pytest.approx(50.0)
    with pytest.raises(ValueError, match="unknown work"):
        roofline.read({"work": "kda_core", "include": ["custom"]},
                      context_of(1.0))


def test_mfu_reader_counts_the_rows_routed_here(monkeypatch):
    """The whole step's operations with the held experts' products over
    the rows the gauge says were routed here, against the traced steps'
    device time: a step at the peak's pace reads 100%, and a run without
    a trace or a program without the gauge reads as nothing."""
    from benchmarks import flops_qwen3_next as flops
    from benchmarks.readers import qwen3_next_flops

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    peaks = manifest.load_peaks(cell)["TPU v5 lite"]

    def per_step(share):
        return 16384 * flops.train_flops_per_token(cell.config, 8192, share)

    def context_of(seconds):
        return {"cell": cell, "peaks": peaks, "chips": 1, "trace_steps": 8,
                "trace": {"window_s": 8 * seconds, "busy_s": 8 * seconds}}

    monkeypatch.setattr(
        qwen3_next_flops.registry_gauge, "children",
        lambda metric: {"worker_moe_routed_here_ratio": [0.1, 0.2]}[metric],
    )
    at_peak = per_step(0.15) / peaks["bf16_flops_per_s"]
    assert qwen3_next_flops.read({}, context_of(2 * at_peak)) == (
        pytest.approx(50.0)
    )
    assert per_step(0.15) > per_step(None) == per_step(0.0625)
    plain = {k: v for k, v in context_of(1.0).items() if k != "trace"}
    assert qwen3_next_flops.read({}, plain) is None
    monkeypatch.setattr(
        qwen3_next_flops.registry_gauge, "children", lambda metric: None
    )
    assert qwen3_next_flops.read({}, context_of(1.0)) is None
