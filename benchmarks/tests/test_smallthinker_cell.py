"""The smallthinker-21b-a3b cell before chip time is spent: the cell end to
end on the CPU at a tiny size through `run.py`'s driver (as
test_qwen3_next_cell.py does its cell), the reference's float8 control
under the cell's own rule, two reference programs for four layers,
`flops_smallthinker` against a hand count, every new layer metric resolving
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

CELL = "smallthinker-21b-a3b.train-l16384-b1-v18992"
CONFIG_FILE = os.path.join(
    manifest.BENCH_DIR, "configs", "smallthinker-21b-a3b.json"
)
TRAFFIC_FILE = os.path.join(
    manifest.BENCH_DIR, "traffic", "train-l16384-b1-v18992.json"
)
# the two lists and the cut's four layers stay; 6 query heads of 16 over 2
# K/V heads (groups of 3), a band of 12 over 32 positions, top-6 of 64
# with experts 0-7 held, 24 wide
TINY_CONFIG = {
    "hidden_size": 32, "num_attention_heads": 6, "num_key_value_heads": 2,
    "head_dim": 16, "sliding_window_size": 12, "moe_ffn_hidden_size": 24,
    "vocab_size": 50, "use_bf16": False,
}
TINY_TRAFFIC = {
    "minibatch_size": 1, "records_per_task": 8, "seq_len": 32,
    "data": {"format": "tokens", "seq_len": 32, "vocab_size": 50},
}
NEW_METRICS = {
    "smallthinker_train_mfu", "smallthinker_window_core_roofline_share",
    "smallthinker_gqa_core_roofline_share",
    "smallthinker_moe_experts_roofline_share", "moe_route_ahead_ms_per_step",
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
    root = tmp_path_factory.mktemp("tiny_smallthinker")
    shutil.copytree(
        manifest.BENCH_DIR, root / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    (root / "benchmarks/configs/smallthinker-21b-a3b.json").write_text(
        json.dumps(tiny_config())
    )
    path = root / "benchmarks/traffic/train-l16384-b1-v18992.json"
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
    # 4 x (4 attention kernels, 2 norms, the router and two stacks),
    # embedding, head and final norm
    assert "0 of 39 parameter leaves never received" in out


# ---- the reference's control, at a test's size ----------------------------


@pytest.fixture(scope="module")
def seeded():
    """Seeded weights of the tiny model (flat), a batch of 8 sequences,
    and the reference's gradient on them."""
    import jax

    from benchmarks import trees
    from benchmarks.reference import smallthinker as reference
    from elasticdl_tpu.common.model_handler import _call_with_params
    from model_zoo.smallthinker import smallthinker as zoo

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
    assert reference.layers_of(seeded.config) == [False, True, True, True]


def test_the_reference_is_independent_of_the_program():
    """`benchmarks/reference/smallthinker.py` imports nothing of
    `elasticdl_tpu/` or `model_zoo/`, computes at the highest matmul
    precision, and routes the published way: the top k of the logits,
    then a softmax over them, from the block's input."""
    path = os.path.join(manifest.BENCH_DIR, "reference", "smallthinker.py")
    with open(path) as f:
        text = f.read()
    imports = re.findall(r"^(?:from|import) ([\w.]+)", text, re.M)
    assert imports and not [
        name for name in imports
        if name.startswith(("elasticdl_tpu", "model_zoo"))
    ]
    assert 'default_matmul_precision("highest")' in text
    assert "top_k(jax.lax.stop_gradient(logits), top_k)" in text
    assert 'routing(x, held["router_kernel"], s.top_k)' in text


# ---- operations by shapes, and the metric files ---------------------------


def test_flops_smallthinker_against_a_hand_count():
    from benchmarks import flops_smallthinker as flops

    config = manifest.load_json(CONFIG_FILE)
    traffic = manifest.load_json(TRAFFIC_FILE)
    assert flops.layers(config) == [False, True, True, True]
    # the pairs a head scores over one sequence of 16,384: the causal
    # half, and the band of 4,096 (a whole triangle, then 12,288 rows of
    # 4,096)
    assert flops.pairs_per_head(False, config, 16384) == 134_225_920
    assert flops.pairs_per_head(True, config, 16384) == 58_722_304 == (
        4096 * 4097 // 2 + 12288 * 4096
    )
    # a band as long as the sequence hides nothing
    assert flops.pairs_per_head(True, config, 4096) == (
        flops.pairs_per_head(False, config, 4096)
    )
    parts = flops.forward_flops_per_token(config, 16384)
    # by hand, from the published widths: q and o 2,560 x 3,584, k and v
    # 2,560 x 512, in 4 layers
    assert parts["attn_proj"] == 4 * 2 * (2 * 9_175_040 + 2 * 1_310_720)
    # q k^T and p v, 28 heads of 128
    assert parts["gqa_core"] == 2 * 28 * (128 + 128) * 134_225_920 / 16384
    assert parts["window_core"] == (
        3 * 2 * 28 * (128 + 128) * 58_722_304 / 16384
    )
    assert parts["moe_router"] == 4 * 2 * 2560 * 64
    # six slots a token, an eighth of them on held experts
    assert parts["moe_experts"] == 4 * 2 * 3 * 2560 * 768 * 6 / 8
    assert parts["head"] == 2 * 2560 * 18992
    tokens = 16384
    step = flops.train_flops_per_token(config, 16384) * tokens
    assert 28.0e12 < step < 28.4e12          # 143 ms at the chip's peak
    for banded, part in ((True, "window_core"), (False, "gqa_core")):
        assert flops.core_train_flops_per_step(config, traffic, banded) == (
            3 * parts[part] * tokens
        )
    # q, o, dO and dQ at 28 heads (twice forward, four times backward),
    # k, v and theirs at 4 (twice and four times), 2 bytes, 128 columns
    assert flops.core_train_bytes_per_step(config, traffic, False) == (
        2 * 128 * (6 * 28 + 6 * 4) * tokens
    )
    assert flops.core_train_bytes_per_step(config, traffic, True) == (
        3 * 2 * 128 * (6 * 28 + 6 * 4) * tokens
    )
    rows = tokens * 6 / 8
    assert flops.moe_experts_train_flops_per_step(
        config, traffic, 1 / 8
    ) == 3 * 4 * rows * 6 * 2560 * 768
    assert flops.moe_experts_train_bytes_per_step(
        config, traffic, 1 / 8
    ) == 4 * (
        8 * 3 * 2560 * 768 * (3 * 2 + 4)
        + rows * 2 * 3 * (2 * 2560 + 3 * 768)
    )
    # the attention core is about half of the step's operations
    core = parts["gqa_core"] + parts["window_core"]
    assert 0.45 < core / sum(parts.values()) < 0.6


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
        "setup_boot_s", "setup_job_s", "setup_init_state_s",
        "setup_step_trace_s", "setup_step_xla_s", "setup_warmup_run_s",
        "setup_cache_hit_share", "setup_unregistered_compile_s",
        "setup_unattributed_share",
    } <= reported
    assert {
        "window_core_ms_per_step", "gqa_core_ms_per_step",
        "attn_proj_ms_per_step", "moe_experts_ms_per_step",
        "moe_dispatch_ms_per_step", "moe_walk_ms_per_step",
        "moe_expert_load_max_over_mean", "moe_live_chunks_share",
        "moe_padded_work_share", "head_ce_ms_per_step",
        "lm_adam_ms_per_step", "remat_rebuild_ms_per_step",
        "remat_kept_share",
    } <= reported
    # no dense layer and no shared expert, no latent, delta-rule,
    # state-space, conv or DeepFM metric has anything to read here, nor
    # another model's shares
    assert not {
        name for name in reported
        if name.startswith(("dense_ffn", "mla_", "kda_", "gdn_", "ssd_",
                            "ssm_", "short_conv", "conv_proj_", "arena_",
                            "scatter_", "optimizer_", "granite_", "kimi_",
                            "lfm2_", "laguna_", "nemotron_", "qwen3_next_",
                            "lm_train", "window_core_roofline",
                            "gqa_core_roofline", "moe_experts_roofline"))
    }
    assert cell.config["vocab_size"] == cell.traffic["data"]["vocab_size"]
    assert cell.config["vocab_size"] == 18992 == 151936 // 8
    assert cell.traffic["minibatch_size"] == 1
    assert cell.traffic["seq_len"] == 16384 == (
        cell.config["max_position_embeddings"]
    )
    assert cell.traffic["records_per_task"] == 8
    assert cell.traffic["file_tasks"] == 4
    assert cell.config["learning_rate"] == 1e-5
    # `moe_dispatch_ms_per_step`'s {slots}: six slots a token
    from benchmarks.readers import trace_ops_cell

    assert trace_ops_cell.with_traffic(cell).config["slots"] == 98304


def test_the_configuration_holds_the_catalog_rows_numbers():
    """Every number of the catalog row under its own key, but the three
    the file lists as reduced; each of those beside what it was cut
    from."""
    config = manifest.load_json(CONFIG_FILE)
    published = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384, "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
        "num_attention_heads": 28, "num_hidden_layers": 52,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_theta": 1500000, "sliding_window_size": 4096,
        "vocab_size": 151936,
    }
    reduced = set(config["reduced"])
    assert reduced == {
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size"
    }
    assert reduced == set(config["reduced_from"])
    for key, value in published.items():
        if key in reduced:
            assert config[key] != value
            assert config[f"{key}_published"] == value
        else:
            assert config[key] == value, key
    layout = [int(i % 4 != 0) for i in range(52)]
    assert config["rope_layout"] == config["sliding_window_layout"] == layout
    assert config["moe_primary_router_apply_softmax"] is True
    assert config["norm_topk_prob"] is True
    assert config["tie_word_embeddings"] is False
    assert config["rope_scaling"] is None
    assert config["model_name"] == "smallthinker_21b_instruct"
    assert config["moe_num_primary_experts"] == config["held_experts"][1] == 8
    entry = next(
        c for c in manifest.load_manifest()["configs"]
        if c["name"] == "smallthinker-21b-a3b"
    )
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    for key in ("assumed", "deployment", "eight_layers", "parameters_held"):
        assert config[key], key
    assert "8 v5e chips share each layer" in config["deployment"]


def test_scope_rules_match_this_models_scopes():
    """`attn_proj_ms_per_step`'s patterns match both attention scopes of
    this model and no other of its scopes, `dense_ffn_ms_per_step` none
    (no dense layer, no shared expert), and the new
    `moe_route_ahead_ms_per_step` names a PATH, not a catalogue entry, so
    that the routing's leaves stay `router`'s and `dispatch`'s for
    `moe_walk_ms_per_step`."""
    from benchmarks.readers import scope_ops
    from elasticdl_tpu.common import profiler

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    ours = [s for s in profiler.DEVICE_SCOPES if s.startswith("smallthinker/")]
    assert set(ours) == {
        "smallthinker/embed", "smallthinker/norm", "smallthinker/attn_full",
        "smallthinker/attn_window", "smallthinker/moe",
        "smallthinker/head_ce",
    }

    def matched(metric):
        spec = manifest.load_layer_metric(cell, metric)
        return scope_ops.spelled_out(
            spec["params"]["scopes"], profiler.DEVICE_SCOPES
        )

    assert [s for s in matched("attn_proj_ms_per_step")
            if s.startswith("smallthinker/")] == [
        "smallthinker/attn_full", "smallthinker/attn_window",
    ]
    assert not [s for s in matched("dense_ffn_ms_per_step")
                if s.startswith("smallthinker/")]
    assert matched("moe_walk_ms_per_step") == ["router", "dispatch",
                                               "combine"]
    assert matched("moe_route_ahead_ms_per_step") == ["smallthinker/route"]
    assert "smallthinker/route" not in profiler.DEVICE_SCOPES
    route = "layer_1/moe/smallthinker/moe/routed/smallthinker/route"
    assert profiler.catalogue_scope(f"{route}/router") == "router"
    assert profiler.catalogue_scope(f"{route}/dispatch") == "dispatch"
    assert profiler.catalogue_scope(
        "layer_1/moe/smallthinker/moe/routed/experts"
    ) == "experts"
    assert profiler.catalogue_scope(
        "layer_0/attn/smallthinker/attn_full/q"
    ) == "smallthinker/attn_full"


def test_route_ahead_reads_the_path_and_the_walk_still_reads_its_leaves():
    """On a made-up scope table: `moe_route_ahead_ms_per_step` keeps the
    leaves under `smallthinker/route` whatever their innermost entry, and
    `moe_walk_ms_per_step` keeps the same leaves (`router`, `dispatch`)
    beside the walk's own."""
    from elasticdl_tpu.common import profiler, programs

    route = "layer_1/moe/smallthinker/moe/routed/smallthinker/route"
    walk = "layer_1/moe/smallthinker/moe/routed"
    paths = {
        "fusion.1": f"{route}/router", "sort.2": f"{route}/dispatch",
        "fusion.3": f"{walk}/dispatch", "fusion.4": f"{walk}/combine",
        "fusion.5": f"{walk}/experts",
        "fusion.6": "layer_1/attn/smallthinker/attn_window",
    }
    fields = programs.ScopeRow._fields
    table = {
        name: programs.ScopeRow(**{
            **dict.fromkeys(fields, ""), "scope": path,
            "entry": profiler.catalogue_scope(path), "phase": "forward",
            "container": False, "fused": (),
        })
        for name, path in paths.items()
    }
    seconds = {f"%{name} = f32[1] fusion()": 1.0 for name in paths}
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)

    def kept(metric):
        spec = manifest.load_layer_metric(cell, metric)
        return sum(profiler.device_ms_by_scope(
            seconds, table, scopes=spec["params"]["scopes"]
        )["by_scope"].values())

    assert kept("moe_route_ahead_ms_per_step") == 2.0
    assert kept("moe_walk_ms_per_step") == 4.0


def test_kernel_rules_read_the_names_a_trace_carries():
    """The names the kernels carry in a trace, as XLA prints them: the
    accepted window, attention, expert, dispatch and head metrics read
    this cell's kernels and `attn_proj_ms_per_step` leaves the cores
    out."""
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    from benchmarks.readers import trace_ops, trace_ops_cell

    ops = {
        "%window_attention_fwd.8 = (bf16[1,16384,3584]{2,1,0}, "
        "f32[1,28,16384,1]{3,2,1,0}) custom-call(...)": 3.0,
        "%transpose_jvp_window_attention_dkv.2 = (bf16[1,16384,3584]) "
        "custom-call(...)": 6.0,
        "%causal_attention_dkv.1 = (bf16[1,16384,3584]) custom-call(...)": 7.0,
        "%causal_attention_fwd = (bf16[1,16384,3584]) custom-call(...)": 4.0,
        "%ragged-dot-none.4 = bf16[16384,1536]{1,0} custom-call(...)": 9.0,
        "%sort.2 = (s32[98304]{0}, s32[98304]{0}) sort(...)": 1.5,
        "%while.7 = (s32[], f32[8], bf16[8,2048,2560], f32[2048,18992]) "
        "while(...)": 2.5,
        "%fusion.9 = bf16[16384,3584]{1,0} fusion(...)": 100.0,
    }
    context = {"cell": cell, "trace_steps": 1, "trace": {"op_seconds": ops}}

    def ms(metric, reader=trace_ops):
        spec = manifest.load_layer_metric(cell, metric)
        return reader.read(spec["params"], context)

    assert ms("window_core_ms_per_step") == pytest.approx(9e3)
    assert ms("gqa_core_ms_per_step") == pytest.approx(11e3)
    assert ms("moe_experts_ms_per_step") == pytest.approx(9e3)
    assert ms("head_ce_ms_per_step") == pytest.approx(2.5e3)
    assert ms("moe_dispatch_ms_per_step", trace_ops_cell) == (
        pytest.approx(1.5e3)
    )
    spec = manifest.load_layer_metric(cell, "attn_proj_ms_per_step")
    excluded = [re.compile(p) for p in spec["params"]["exclude_ops"]]
    kept = [t for t in ops if not any(p.search(t) for p in excluded)]
    assert sorted(t.split(" ")[0] for t in kept) == [
        "%fusion.9", "%ragged-dot-none.4", "%sort.2", "%while.7",
    ]


def test_roofline_readers_on_a_made_up_trace():
    """Half the least time is 50%; a trace without the kernels says
    nothing; the experts' work follows the gauge the layers set."""
    from benchmarks import flops_smallthinker as flops
    from benchmarks.readers import roofline_smallthinker as roofline
    from elasticdl_tpu.common import metrics as metrics_lib

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    peaks = manifest.load_peaks(cell)["TPU v5 lite"]

    def least(ops, bytes_):
        return max(
            ops / peaks["bf16_flops_per_s"], bytes_ / peaks["hbm_bytes_per_s"]
        )

    def context_of(seconds):
        return {
            "cell": cell, "peaks": peaks, "trace_steps": 2, "trace": {
                "op_seconds": {"%k = bf16[1] custom-call()": 4 * seconds},
            },
        }

    for work, banded in (("window_core", True), ("gqa_core", False)):
        ops = flops.core_train_flops_per_step(
            cell.config, cell.traffic, banded
        )
        bytes_ = flops.core_train_bytes_per_step(
            cell.config, cell.traffic, banded
        )
        # both cores are held to their operations at 16,384 positions
        assert ops / peaks["bf16_flops_per_s"] > (
            bytes_ / peaks["hbm_bytes_per_s"]
        )
        context = context_of(least(ops, bytes_))
        params = {"work": work, "include": ["custom-call"]}
        assert roofline.read(params, context) == pytest.approx(50.0)
        assert roofline.read(
            {"work": work, "include": ["no such kernel"]}, context
        ) is None
    gauge = metrics_lib.default_registry().gauge(
        "worker_moe_routed_here_ratio", labelnames=("layer",)
    )
    params = {"work": "moe_experts", "include": ["custom-call"]}
    for layer, share in (("layer_1/moe/routed", 0.1),
                         ("layer_3/moe/routed", 0.15)):
        gauge.labels(layer=layer).set(share)
    shares = [
        v for v in gauge.child_values().values()
    ]
    here = sum(shares) / len(shares)
    assert roofline.read(params, context_of(least(
        flops.moe_experts_train_flops_per_step(
            cell.config, cell.traffic, here
        ),
        flops.moe_experts_train_bytes_per_step(
            cell.config, cell.traffic, here
        ),
    ))) == pytest.approx(50.0)
    with pytest.raises(ValueError, match="unknown work"):
        roofline.read({"work": "mla_core", "include": ["custom"]},
                      context_of(1.0))


def test_mfu_reader_counts_the_rows_routed_here(monkeypatch):
    """The whole step's operations with the held experts' products over
    the rows the gauge says were routed here, against the traced steps'
    device time: a step at the peak's pace reads 100%, and a run without
    a trace or a program without the gauge reads as nothing."""
    from benchmarks import flops_smallthinker as flops
    from benchmarks.readers import smallthinker_flops

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    peaks = manifest.load_peaks(cell)["TPU v5 lite"]

    def per_step(share):
        return 16384 * flops.train_flops_per_token(cell.config, 16384, share)

    def context_of(seconds):
        return {"cell": cell, "peaks": peaks, "chips": 1, "trace_steps": 8,
                "trace": {"window_s": 8 * seconds, "busy_s": 8 * seconds}}

    monkeypatch.setattr(
        smallthinker_flops.registry_gauge, "children",
        lambda metric: {"worker_moe_routed_here_ratio": [0.1, 0.2]}[metric],
    )
    at_peak = per_step(0.15) / peaks["bf16_flops_per_s"]
    assert smallthinker_flops.read({}, context_of(2 * at_peak)) == (
        pytest.approx(50.0)
    )
    assert per_step(0.15) > per_step(None) == per_step(0.125)
    plain = {k: v for k, v in context_of(1.0).items() if k != "trace"}
    assert smallthinker_flops.read({}, plain) is None
    monkeypatch.setattr(
        smallthinker_flops.registry_gauge, "children", lambda metric: None
    )
    assert smallthinker_flops.read({}, context_of(1.0)) is None
