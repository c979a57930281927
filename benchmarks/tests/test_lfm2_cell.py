"""The LFM2-24B-A2B cell before chip time is spent: the cell end to end on
the CPU at a tiny size through `run.py`'s driver (as test_laguna_cell.py
does its cell), the reference's bfloat16 twin and its float8 control under
the cell's own rule, `flops_lfm2` against a hand count, every new layer
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

CELL = "lfm2-24b-a2b.train-l8192-b4"
CONFIG_FILE = os.path.join(manifest.BENCH_DIR, "configs", "lfm2-24b-a2b.json")
TRAFFIC_FILE = os.path.join(
    manifest.BENCH_DIR, "traffic", "train-l8192-b4.json"
)
# the published layer list and the cut's layers stay; 4 heads of 8 over 2
# K/V heads, 16 experts of which 4 are held
TINY_CONFIG = {
    "hidden_size": 32, "head_dim": 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 48,
    "moe_intermediate_size": 16, "num_experts_published": 16,
    "num_experts": 4, "held_experts": [0, 4], "num_experts_per_tok": 2,
    "vocab_size": 50, "use_bf16": False,
}
TINY_TRAFFIC = {
    "minibatch_size": 2, "records_per_task": 8, "seq_len": 32,
    "data": {"format": "tokens", "seq_len": 32, "vocab_size": 50},
}
NEW_METRICS = {
    "short_conv_ms_per_step", "short_conv_roofline_share",
    "lfm2_gqa_core_roofline_share", "lfm2_train_mfu",
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
    root = tmp_path_factory.mktemp("tiny_lfm2")
    shutil.copytree(
        manifest.BENCH_DIR, root / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    (root / "benchmarks/configs/lfm2-24b-a2b.json").write_text(
        json.dumps(tiny_config())
    )
    path = root / "benchmarks/traffic/train-l8192-b4.json"
    path.write_text(json.dumps({**json.loads(path.read_text()),
                                **TINY_TRAFFIC}))
    (root / "wrapper.py").write_text(WRAPPER.format(repo=manifest.ROOT))
    return root


def test_cell_rehearsal(tiny_root):
    result, out = rehearse(tiny_root, CELL, 1)
    assert result["correct"] is True, out[-3000:]
    assert result["attempted"] >= 3 and result["failed"] == 0
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
    # 7 + 11 + 3 x 8 block leaves, the tied table and the final norm
    assert "0 of 44 parameter leaves never received" in out


# ---- the reference's twin and its control, at a test's size ---------------


@pytest.fixture(scope="module")
def seeded():
    """Seeded weights of the tiny model (flat), a batch of 8 sequences,
    and the reference's gradient on them."""
    import jax

    from benchmarks import trees
    from benchmarks.reference import lfm2_moe as reference
    from elasticdl_tpu.common.model_handler import _call_with_params
    from model_zoo.lfm2 import lfm2_moe as zoo

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


# ---- operations by shapes, and the metric files ---------------------------


def test_flops_lfm2_against_a_hand_count():
    from benchmarks import flops_lfm2

    config = manifest.load_json(CONFIG_FILE)
    traffic = manifest.load_json(TRAFFIC_FILE)
    assert flops_lfm2.layers(config) == [
        ("conv", False), ("full_attention", True), ("conv", True),
        ("conv", True), ("conv", True),
    ]
    parts = flops_lfm2.forward_flops_per_token(config, 8192)
    # by hand, from the published widths: in_proj d x 3d and out_proj d x d
    assert parts["conv_proj"] == 4 * 2 * 2048 * (3 * 2048 + 2048)
    # q and o at 32 heads of 64, k and v at 8
    assert parts["attn_proj"] == 2 * 2048 * (2 * 32 * 64 + 2 * 8 * 64)
    # the causal half: (L + 1) / 2 keys a query, 2 x 2 x 64 a key, 32 heads
    assert parts["full_core"] == 2 * 32 * 2 * 64 * 8193 / 2
    assert parts["dense_ffn"] == 2 * 3 * 2048 * 11776
    assert parts["moe_router"] == 4 * 2 * 2048 * 64
    # 4 slots a token, an eighth of them land here: half an expert's worth
    assert parts["moe_experts"] == 4 * 2 * 3 * 2048 * 1536 / 2
    assert parts["head"] == 2 * 2048 * 8192
    assert "moe_shared" not in parts
    total = sum(parts.values())
    assert total == pytest.approx(405.8e6, rel=1e-3)     # "406 MFLOP a token"
    assert parts["conv_proj"] / total == pytest.approx(0.33, abs=0.01)
    tokens = 4 * 8192
    step = flops_lfm2.train_flops_per_token(config, 8192) * tokens
    assert 39.5e12 < step < 40.3e12                      # "~40 TFLOP"
    assert flops_lfm2.core_train_flops_per_step(config, traffic) == (
        3 * parts["full_core"] * tokens
    )
    # bytes at 2 a number: q, o, dO, dQ and q, o again a QUERY head (8
    # arrays... 6 reads and 2 writes), k, v, dK, dV and k, v again a K/V head
    assert flops_lfm2.core_train_bytes_per_step(config, traffic) == (
        2 * 64 * (6 * 32 + 6 * 8) * tokens
    )
    # the conv pass: 4 streams forward and 7 backward of tokens x d in each
    # of the 4 conv layers; 7 and 15 operations an element at 3 taps
    assert flops_lfm2.short_conv_train_bytes_per_step(config, traffic) == (
        2 * 11 * tokens * 2048 * 4
    )
    assert flops_lfm2.short_conv_train_flops_per_step(config, traffic) == (
        22 * tokens * 2048 * 4
    )
    peaks = manifest.load_json(
        os.path.join(manifest.BENCH_DIR, "peaks.json")
    )["TPU v5 lite"]
    by_bytes = flops_lfm2.short_conv_train_bytes_per_step(
        config, traffic
    ) / peaks["hbm_bytes_per_s"]
    by_flops = flops_lfm2.short_conv_train_flops_per_step(
        config, traffic
    ) / peaks["bf16_flops_per_s"]
    assert by_bytes > 100 * by_flops                     # memory bounds it
    assert by_bytes == pytest.approx(7.2e-3, rel=0.01)   # 7.2 ms a step


def test_every_new_layer_metric_names_a_reader_that_imports():
    bench = manifest.load_manifest()
    alone = {
        m["name"] for m in bench["per_layer"] if m["workloads"] == [CELL]
    }
    assert NEW_METRICS <= alone
    cell = manifest.resolve_cell(bench, CELL)
    for name in NEW_METRICS:
        spec = manifest.load_layer_metric(cell, name)
        assert spec["name"] == name
        assert spec["moves"] == "train_examples_per_s"
        reader = manifest.import_by_name("readers", spec["reader"])
        # nothing to read (no trace, no rate): nothing said, nothing raised
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
        "producer_blocked_share",
    } <= reported
    assert {"gqa_core_ms_per_step", "moe_experts_ms_per_step",
            "moe_dispatch_ms_per_step", "lm_adam_ms_per_step",
            "moe_expert_load_max_over_mean",
            "moe_live_chunks_share"} <= reported
    # other models' kernels and counts are not this cell's
    assert not reported & {
        "mla_core_ms_per_step", "mla_core_roofline_share",
        "window_core_ms_per_step", "gqa_core_roofline_share",
        "moe_experts_roofline_share", "lm_train_mfu", "laguna_train_mfu",
    }
    # the routed buffer's rule reads tokens x top-4 rows
    from benchmarks.readers import trace_ops_cell

    with_traffic = trace_ops_cell.with_traffic(cell).config
    assert with_traffic["slots"] == 131072 and with_traffic["tokens"] == 32768
    assert cell.config["vocab_size"] == cell.traffic["data"]["vocab_size"]
    assert cell.traffic["minibatch_size"] == 4


def test_kernel_rules_read_the_names_a_trace_carries():
    """The names the kernels carry in a trace, as XLA prints them: the
    conv metric reads both conv kernels (the remat's second forward too)
    and nothing of attention, the grouped metric the three attention
    kernels and nothing of the conv."""
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    from benchmarks.readers import trace_ops

    ops = {
        "%short_conv_fwd.3 = bf16[4,8192,2048]{2,1,0} custom-call(...)": 3.0,
        "%checkpoint_short_conv_fwd_.2 = bf16[4,8192,2048]{2,1,0} "
        "custom-call(...)": 2.0,
        "%short_conv_bwd.1 = (bf16[4,8192,6144]{2,1,0}, "
        "f32[4,32,8,2048]{3,2,1,0}) custom-call(...)": 6.0,
        "%causal_attention_dq.1 = (bf16[4,32,8192,64]) custom-call(...)": 7.0,
        "%causal_attention_fwd = (bf16[4,32,8192,64]) custom-call(...)": 4.0,
        "%fusion.9 = bf16[131072,128]{1,0} fusion(...)": 100.0,
    }
    context = {"cell": cell, "trace_steps": 1, "trace": {"op_seconds": ops}}

    def ms(metric):
        spec = manifest.load_layer_metric(cell, metric)
        return trace_ops.read(spec["params"], context)

    assert ms("short_conv_ms_per_step") == pytest.approx(11e3)
    assert ms("gqa_core_ms_per_step") == pytest.approx(11e3)


def test_roofline_readers_on_a_made_up_trace():
    """Half the least time is 50%; a trace without the kernels says
    nothing.  The conv is held to its bytes, the core to its operations."""
    from benchmarks import flops_lfm2
    from benchmarks.readers import roofline_lfm2

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    peaks = manifest.load_peaks(cell)["TPU v5 lite"]
    least = {
        "short_conv": flops_lfm2.short_conv_train_bytes_per_step(
            cell.config, cell.traffic
        ) / peaks["hbm_bytes_per_s"],
        "gqa_core": flops_lfm2.core_train_flops_per_step(
            cell.config, cell.traffic
        ) / peaks["bf16_flops_per_s"],
    }
    # the core is FLOP-bound at these shapes
    assert least["gqa_core"] > flops_lfm2.core_train_bytes_per_step(
        cell.config, cell.traffic
    ) / peaks["hbm_bytes_per_s"]
    for work, seconds in least.items():
        context = {
            "cell": cell, "peaks": peaks, "trace_steps": 2, "trace": {
                "op_seconds": {"%k = bf16[1] custom-call()": 4 * seconds},
            },
        }
        params = {"work": work, "include": ["custom-call"]}
        assert roofline_lfm2.read(params, context) == pytest.approx(50.0)
        assert roofline_lfm2.read(
            {"work": work, "include": ["no such kernel"]}, context
        ) is None
    spec = manifest.load_layer_metric(cell, "short_conv_roofline_share")
    assert spec["params"]["bound"] == "bytes"
    with pytest.raises(ValueError, match="unknown work"):
        roofline_lfm2.read({"work": "mla_core", "include": ["custom"]},
                           context)


def test_mfu_reader_counts_tokens():
    from benchmarks import flops_lfm2
    from benchmarks.readers import lfm2_flops

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    peaks = manifest.load_peaks(cell)["TPU v5 lite"]
    per_step = flops_lfm2.train_flops_per_token(cell.config, 8192) * 32768
    # one step (four sequences) a second
    context = {"cell": cell, "peaks": peaks, "chips": 1,
               "train_examples_per_s": 4.0}
    assert lfm2_flops.read({}, context) == pytest.approx(
        100 * per_step / peaks["bf16_flops_per_s"]
    )
