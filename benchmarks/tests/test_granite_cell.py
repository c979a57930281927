"""The granite-4.0-h-micro cell before chip time is spent: the cell end to
end on the CPU at a tiny size through `run.py`'s driver (as
test_kimi_cell.py does its cell), the reference's float8 control under the
cell's own rule, `flops_granite` against a hand count, every new layer
metric resolving to a reader that imports, and the readers on a made-up
trace.  Nothing these runs time is a measurement.

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

CELL = "granite-4.0-h-micro.train-l8192-b1"
CONFIG_FILE = os.path.join(
    manifest.BENCH_DIR, "configs", "granite-4.0-h-micro.json"
)
TRAFFIC_FILE = os.path.join(
    manifest.BENCH_DIR, "traffic", "train-l8192-b1.json"
)
# the published layer list and the cut's ten layers stay; 4 state-space
# heads of 8 over 16 state columns, 4 query heads over 2 K/V heads of 8
TINY_CONFIG = {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "mamba_n_heads": 4, "mamba_d_head": 8, "mamba_d_state": 16,
    "shared_intermediate_size": 48, "intermediate_size": 48,
    "attention_multiplier": 0.125, "vocab_size": 50, "use_bf16": False,
}
TINY_TRAFFIC = {
    "minibatch_size": 1, "records_per_task": 8, "seq_len": 32,
    "data": {"format": "tokens", "seq_len": 32, "vocab_size": 50},
}
NEW_METRICS = {
    "ssd_core_ms_per_step", "ssd_core_roofline_share",
    "ssd_proj_ms_per_step", "granite_short_conv_roofline_share",
    "granite_gqa_core_roofline_share", "granite_train_mfu",
    "ssm_state_kept_share",
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
    root = tmp_path_factory.mktemp("tiny_granite")
    shutil.copytree(
        manifest.BENCH_DIR, root / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    (root / "benchmarks/configs/granite-4.0-h-micro.json").write_text(
        json.dumps(tiny_config())
    )
    path = root / "benchmarks/traffic/train-l8192-b1.json"
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
    # 9 x 12 + 8 block leaves, the tied table and the final norm
    assert "0 of 118 parameter leaves never received" in out


# ---- the reference's control, at a test's size ----------------------------


@pytest.fixture(scope="module")
def seeded():
    """Seeded weights of the tiny model (flat), a batch of 8 sequences,
    and the reference's gradient on them."""
    import jax

    from benchmarks import trees
    from benchmarks.reference import granite_hybrid as reference
    from elasticdl_tpu.common.model_handler import _call_with_params
    from model_zoo.granite import granite_hybrid as zoo

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
    """Ten layers, two kinds: the jitted block programs are traced twice
    forward and twice backward, the parameters their arguments."""
    reference = seeded.reference
    for program in (reference._block_fwd, reference._block_bwd):
        program.clear_cache()
    reference.loss_and_grads(
        seeded.flat, seeded.features, seeded.labels, seeded.config
    )
    assert reference._block_fwd._cache_size() == 2
    assert reference._block_bwd._cache_size() == 2
    assert reference.layers_of(seeded.config) == (
        [True] * 5 + [False] + [True] * 4
    )


# ---- operations by shapes, and the metric files ---------------------------


def test_flops_granite_against_a_hand_count():
    from benchmarks import flops_granite

    config = manifest.load_json(CONFIG_FILE)
    traffic = manifest.load_json(TRAFFIC_FILE)
    assert flops_granite.layers(config) == (
        ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    )
    parts = flops_granite.forward_flops_per_token(config, 8192)
    # by hand, from the published widths: in_proj 2048 x 8512 (4096 + 4352
    # + 64), out_proj 4096 x 2048, in 9 layers
    assert parts["ssm_proj"] == 9 * 2 * (2048 * 8512 + 4096 * 2048)
    # the recurrence: 5 passes over a head's 64 x 128 state and 3 x 64
    assert parts["ssd_core"] == 9 * 64 * (5 * 64 * 128 + 3 * 64)
    assert parts["attn_proj"] == 2 * 10_485_760
    # the causal half: (L + 1) / 2 keys a query, 32 heads of 64
    assert parts["attn_core"] == 2 * 32 * (64 + 64) * 8193 / 2
    assert parts["dense_ffn"] == 10 * 2 * 3 * 2048 * 8192
    assert parts["head"] == 2 * 2048 * 12544
    total = sum(parts.values())
    assert total == pytest.approx(1601.1e6, rel=1e-3)
    # the scan's own operations are 1.5% of a token's: it is the layer's
    # projections, its conv's and its scan's TIME that make nine of ten
    # blocks the cell's subject
    assert parts["ssd_core"] / total == pytest.approx(0.0148, abs=0.001)
    assert (parts["ssm_proj"] + parts["ssd_core"]) / total == (
        pytest.approx(0.305, abs=0.005)
    )
    tokens = 8192
    step = flops_granite.train_flops_per_token(config, 8192) * tokens
    assert 39.0e12 < step < 39.7e12                      # "~39 TFLOP"
    assert flops_granite.gqa_core_train_flops_per_step(config, traffic) == (
        3 * parts["attn_core"] * tokens
    )
    # bytes at 2 a number: q read twice, o written and read, dO read, dQ
    # written (6 x 32 heads); k, v read twice and dK, dV written (6 x 8)
    assert flops_granite.gqa_core_train_bytes_per_step(config, traffic) == (
        2 * 64 * (6 * 32 + 6 * 8) * tokens
    )
    # the scan's least traffic: x, y, dx, dy at 4,096 columns and B, C,
    # dB, dC at 128 at 2 bytes, dt and its gradient at 4 a head, 9 layers
    assert flops_granite.ssd_core_train_bytes_per_step(config, traffic) == (
        (4 * 4096 * 2 + 4 * 128 * 2 + 2 * 64 * 4) * tokens * 9
    )
    assert flops_granite.ssd_core_train_flops_per_step(config, traffic) == (
        3 * parts["ssd_core"] * tokens
    )
    # the conv pass: 2 streams forward and 3 backward of tokens x 4,352
    # in each of the 9 Mamba-2 layers
    assert flops_granite.short_conv_train_bytes_per_step(config, traffic) == (
        2 * 5 * tokens * 4352 * 9
    )
    peaks = manifest.load_json(
        os.path.join(manifest.BENCH_DIR, "peaks.json")
    )["TPU v5 lite"]
    by_bytes = flops_granite.ssd_core_train_bytes_per_step(
        config, traffic
    ) / peaks["hbm_bytes_per_s"]
    by_flops = flops_granite.ssd_core_train_flops_per_step(
        config, traffic
    ) / peaks["bf16_flops_per_s"]
    # bytes and operations bound the recurrence nearly alike: 3.1 and 3.0
    # ms a step
    assert by_bytes == pytest.approx(3.09e-3, rel=0.02)
    assert by_flops == pytest.approx(2.96e-3, rel=0.02)
    assert flops_granite.short_conv_train_bytes_per_step(
        config, traffic
    ) / peaks["hbm_bytes_per_s"] == pytest.approx(3.92e-3, rel=0.02)


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
    assert {"remat_rebuild_ms_per_step", "lm_adam_ms_per_step",
            "head_ce_ms_per_step", "gqa_core_ms_per_step",
            "short_conv_ms_per_step", "attn_proj_ms_per_step",
            "dense_ffn_ms_per_step"} <= reported
    # a dense model: no routed, latent, windowed, delta-rule or DeepFM
    # metric has anything to read here
    assert not {
        name for name in reported
        if name.startswith(("moe_", "mla_", "window_", "kda_", "conv_proj_",
                            "arena_", "scatter_", "optimizer_"))
    }
    assert cell.config["vocab_size"] == cell.traffic["data"]["vocab_size"]
    assert cell.config["vocab_size"] == 12544
    assert cell.traffic["minibatch_size"] == 1
    assert cell.traffic["seq_len"] == 8192
    assert cell.traffic["records_per_task"] == 8


def test_scope_rules_match_this_models_scopes():
    """`attn_proj_ms_per_step`'s patterns match `granite/attn` ALONE of
    this model's scopes (which is why the gated norm's scope is not named
    `gate`), `dense_ffn_ms_per_step`'s `granite/dense_ffn`, and the new
    `ssd_proj_ms_per_step` names the five state-space scopes."""
    from benchmarks.readers import scope_ops
    from elasticdl_tpu.common import profiler

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    ours = [s for s in profiler.DEVICE_SCOPES if s.startswith("granite/")]
    assert len(ours) == 10

    def matched(metric):
        spec = manifest.load_layer_metric(cell, metric)
        return [
            s for s in scope_ops.spelled_out(
                spec["params"]["scopes"], profiler.DEVICE_SCOPES
            ) if s.startswith("granite/")
        ]

    assert matched("attn_proj_ms_per_step") == ["granite/attn"]
    assert matched("dense_ffn_ms_per_step") == ["granite/dense_ffn"]
    assert matched("ssd_proj_ms_per_step") == [
        "granite/ssm/proj", "granite/ssm/conv", "granite/ssm/core",
        "granite/ssm/gated_norm", "granite/ssm/out",
    ]
    # with `granite/embed`, `granite/norm` and `granite/head_ce` they are
    # all ten: the scopes tile the model
    assert set(ours) == {
        "granite/embed", "granite/norm", "granite/head_ce",
        "granite/attn", "granite/dense_ffn",
        *matched("ssd_proj_ms_per_step"),
    }


def test_kernel_rules_read_the_names_a_trace_carries():
    """The names the kernels carry in a trace, as XLA prints them: the
    scan's metric reads both scan kernels (the remat's second forward too)
    and nothing else, the accepted conv and attention metrics read this
    cell's conv and attention kernels."""
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    from benchmarks.readers import trace_ops

    ops = {
        "%ssd_fwd.3 = (bf16[1,8192,4096]{2,1,0}, "
        "f32[1,32,4096,128]{3,2,1,0}) custom-call(...)": 3.0,
        "%checkpoint_ssd_fwd_.2 = (bf16[1,8192,4096]{2,1,0}) "
        "custom-call(...)": 2.0,
        "%ssd_bwd.1 = (bf16[1,8192,4096]{2,1,0}) custom-call(...)": 6.0,
        "%silu_short_conv_fwd.1 = bf16[1,8192,4352]{2,1,0} "
        "custom-call(...)": 5.0,
        "%silu_short_conv_bwd = (bf16[1,8192,4352]{2,1,0}) "
        "custom-call(...)": 8.0,
        "%causal_attention_dkv.1 = (bf16[1,8192,2048]) custom-call(...)": 7.0,
        "%causal_attention_fwd = (bf16[1,8192,2048]) custom-call(...)": 4.0,
        "%fusion.9 = bf16[8192,8512]{1,0} fusion(...)": 100.0,
    }
    context = {"cell": cell, "trace_steps": 1, "trace": {"op_seconds": ops}}

    def ms(metric):
        spec = manifest.load_layer_metric(cell, metric)
        return trace_ops.read(spec["params"], context)

    assert ms("ssd_core_ms_per_step") == pytest.approx(11e3)
    assert ms("short_conv_ms_per_step") == pytest.approx(13e3)
    assert ms("gqa_core_ms_per_step") == pytest.approx(11e3)
    spec = manifest.load_layer_metric(cell, "ssd_proj_ms_per_step")
    excluded = [re.compile(p) for p in spec["params"]["exclude_ops"]]
    kept = [t for t in ops if not any(p.search(t) for p in excluded)]
    assert sorted(t.split(" ")[0] for t in kept) == [
        "%causal_attention_dkv.1", "%causal_attention_fwd", "%fusion.9",
    ]


def test_roofline_readers_on_a_made_up_trace():
    """Half the least time is 50%; a trace without the kernels says
    nothing.  The scan and the conv are held to their bytes, the core to
    its operations."""
    from benchmarks import flops_granite
    from benchmarks.readers import roofline_granite

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    peaks = manifest.load_peaks(cell)["TPU v5 lite"]
    least = {
        "ssd_core": flops_granite.ssd_core_train_bytes_per_step(
            cell.config, cell.traffic
        ) / peaks["hbm_bytes_per_s"],
        "short_conv": flops_granite.short_conv_train_bytes_per_step(
            cell.config, cell.traffic
        ) / peaks["hbm_bytes_per_s"],
        "gqa_core": flops_granite.gqa_core_train_flops_per_step(
            cell.config, cell.traffic
        ) / peaks["bf16_flops_per_s"],
    }
    # the core is FLOP-bound at these shapes
    assert least["gqa_core"] > flops_granite.gqa_core_train_bytes_per_step(
        cell.config, cell.traffic
    ) / peaks["hbm_bytes_per_s"]
    for work, seconds in least.items():
        context = {
            "cell": cell, "peaks": peaks, "trace_steps": 2, "trace": {
                "op_seconds": {"%k = bf16[1] custom-call()": 4 * seconds},
            },
        }
        params = {"work": work, "include": ["custom-call"]}
        assert roofline_granite.read(params, context) == pytest.approx(50.0)
        assert roofline_granite.read(
            {"work": work, "include": ["no such kernel"]}, context
        ) is None
    spec = manifest.load_layer_metric(
        cell, "granite_short_conv_roofline_share"
    )
    assert spec["params"]["bound"] == "bytes"
    with pytest.raises(ValueError, match="unknown work"):
        roofline_granite.read({"work": "kda_core", "include": ["custom"]},
                              context)


def test_mfu_reader_counts_tokens():
    from benchmarks import flops_granite
    from benchmarks.readers import granite_flops

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    peaks = manifest.load_peaks(cell)["TPU v5 lite"]
    per_step = flops_granite.train_flops_per_token(cell.config, 8192) * 8192
    # one step (one sequence) a second
    context = {"cell": cell, "peaks": peaks, "chips": 1,
               "train_examples_per_s": 1.0}
    assert granite_flops.read({}, context) == pytest.approx(
        100 * per_step / peaks["bf16_flops_per_s"]
    )


def test_the_gauge_reader_reads_what_the_worker_sets():
    from benchmarks.readers import registry_gauge
    from elasticdl_tpu.worker import worker

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    spec = manifest.load_layer_metric(cell, "ssm_state_kept_share")
    gauge = worker._moe_gauges["ssm_state_kept_ratio"]
    gauge.labels(layer="layer_0/mamba").set(0.25)
    gauge.labels(layer="layer_1/mamba").set(0.75)
    assert registry_gauge.read(spec["params"], {"cell": cell}) == (
        pytest.approx(0.5)
    )
