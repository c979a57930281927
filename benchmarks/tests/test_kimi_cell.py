"""The Kimi-Linear-48B-A3B cell before chip time is spent: the cell end to
end on the CPU at a tiny size through `run.py`'s driver (as
test_lfm2_cell.py does its cell), the reference's float8 control under the
cell's own rule, `flops_kimi` against a hand count, every new layer metric
resolving to a reader that imports, and the readers on a made-up trace.
Nothing these runs time is a measurement.

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

CELL = "kimi-linear-48b-a3b.train-l8192-b2"
CONFIG_FILE = os.path.join(
    manifest.BENCH_DIR, "configs", "kimi-linear-48b-a3b.json"
)
TRAFFIC_FILE = os.path.join(
    manifest.BENCH_DIR, "traffic", "train-l8192-b2.json"
)
# the published lists and the cut's layers stay; 2 heads of 16, keys of
# 16 + 8 over values of 16, 16 experts of which 4 are held
TINY_CONFIG = {
    "hidden_size": 32, "num_attention_heads": 2, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 48, "moe_intermediate_size": 16,
    "num_experts_published": 16, "num_experts": 4, "held_experts": [0, 4],
    "num_experts_per_token": 2, "num_experts_per_tok": 2, "vocab_size": 50,
    "use_bf16": False,
}
TINY_LINEAR = {"num_heads": 2, "head_dim": 16}
TINY_TRAFFIC = {
    "minibatch_size": 2, "records_per_task": 8, "seq_len": 32,
    "data": {"format": "tokens", "seq_len": 32, "vocab_size": 50},
}
NEW_METRICS = {
    "kda_core_ms_per_step", "kda_core_roofline_share",
    "kda_proj_ms_per_step", "kimi_short_conv_roofline_share",
    "kimi_mla_core_roofline_share", "kimi_train_mfu",
}


def tiny_config() -> dict:
    config = manifest.load_json(CONFIG_FILE)
    config.update(TINY_CONFIG)
    config["linear_attn_config"] = {
        **config["linear_attn_config"], **TINY_LINEAR
    }
    config["model_params"] = config["model_params"].replace(
        "bf16=True", "bf16=False"
    )
    return config


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny_kimi")
    shutil.copytree(
        manifest.BENCH_DIR, root / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    (root / "benchmarks/configs/kimi-linear-48b-a3b.json").write_text(
        json.dumps(tiny_config())
    )
    path = root / "benchmarks/traffic/train-l8192-b2.json"
    path.write_text(json.dumps({**json.loads(path.read_text()),
                                **TINY_TRAFFIC}))
    (root / "wrapper.py").write_text(WRAPPER.format(repo=manifest.ROOT))
    return root


def test_cell_rehearsal(tiny_root):
    result, out = rehearse(tiny_root, CELL, 1)
    assert result["correct"] is True, out[-3000:]
    # the scan's jnp form is slow on the CPU: a 1.5 s window may hold one task
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
    # 15 + 3 x 18 + 12 block leaves, embedding, head and the final norm
    assert "0 of 84 parameter leaves never received" in out


# ---- the reference's control, at a test's size ----------------------------


@pytest.fixture(scope="module")
def seeded():
    """Seeded weights of the tiny model (flat), a batch of 8 sequences,
    and the reference's gradient on them."""
    import jax

    from benchmarks import trees
    from benchmarks.reference import kimi_linear as reference
    from elasticdl_tpu.common.model_handler import _call_with_params
    from model_zoo.kimi import kimi_linear as zoo

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


def test_flops_kimi_against_a_hand_count():
    from benchmarks import flops_kimi

    config = manifest.load_json(CONFIG_FILE)
    traffic = manifest.load_json(TRAFFIC_FILE)
    assert flops_kimi.layers(config) == [
        ("kda", False), ("kda", True), ("kda", True), ("mla", True),
        ("kda", True),
    ]
    parts = flops_kimi.forward_flops_per_token(config, 8192)
    # by hand, from the published widths: q | k | v 2304 x 12288, o 4096 x
    # 2304, two low-rank gates through 128, beta 2304 x 32, in 4 layers
    assert parts["kda_proj"] == 4 * 2 * (
        2304 * 12288 + 4096 * 2304 + 2 * (2304 * 128 + 128 * 4096)
        + 2304 * 32
    )
    # the recurrence: 7 passes over a head's 128 x 128 state and 2 x 128
    assert parts["kda_core"] == 4 * 32 * (7 * 128 * 128 + 2 * 128)
    assert parts["mla_proj"] == 2 * (
        2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304
    )
    # the causal half: (L + 1) / 2 keys a query, keys of 192, values of 128
    assert parts["mla_core"] == 2 * 32 * (192 + 128) * 8193 / 2
    assert parts["dense_ffn"] == 2 * 3 * 2304 * 9216
    assert parts["moe_router"] == 4 * 2 * 2304 * 256
    assert parts["moe_shared"] == 4 * 2 * 3 * 2304 * 1024
    # 8 slots a token, a 32nd of them land here: a quarter of an expert
    assert parts["moe_experts"] == 4 * 2 * 3 * 2304 * 1024 / 4
    assert parts["head"] == 2 * 2304 * 20480
    total = sum(parts.values())
    assert total == pytest.approx(769.5e6, rel=2e-3)
    assert (parts["kda_proj"] + parts["kda_core"]) / total == (
        pytest.approx(0.43, abs=0.01)
    )
    tokens = 2 * 8192
    step = flops_kimi.train_flops_per_token(config, 8192) * tokens
    assert 37.5e12 < step < 38.2e12                      # "~38 TFLOP"
    assert flops_kimi.mla_core_train_flops_per_step(config, traffic) == (
        3 * parts["mla_core"] * tokens
    )
    # bytes at 2 a number: q and k read twice and dQ, dK written (6 x 192);
    # v read twice, o written and read, dO read, dV written (6 x 128); 32
    # heads
    assert flops_kimi.mla_core_train_bytes_per_step(config, traffic) == (
        2 * 32 * (6 * 192 + 6 * 128) * tokens
    )
    # the scan's least traffic: 8 streams of 4,096 columns at 2 bytes, g
    # and dg at 4, beta and its gradient at 4 a head, in 4 layers
    assert flops_kimi.kda_core_train_bytes_per_step(config, traffic) == (
        (8 * 4096 * 2 + 2 * 4096 * 4 + 2 * 32 * 4) * tokens * 4
    )
    assert flops_kimi.kda_core_train_flops_per_step(config, traffic) == (
        3 * parts["kda_core"] * tokens
    )
    # the conv pass: 2 streams forward and 3 backward of tokens x 12,288
    # in each of the 4 KDA layers
    assert flops_kimi.short_conv_train_bytes_per_step(config, traffic) == (
        2 * 5 * tokens * 12288 * 4
    )
    peaks = manifest.load_json(
        os.path.join(manifest.BENCH_DIR, "peaks.json")
    )["TPU v5 lite"]
    by_bytes = flops_kimi.kda_core_train_bytes_per_step(
        config, traffic
    ) / peaks["hbm_bytes_per_s"]
    by_flops = flops_kimi.kda_core_train_flops_per_step(
        config, traffic
    ) / peaks["bf16_flops_per_s"]
    assert by_bytes > 2 * by_flops                       # memory bounds it
    assert by_bytes == pytest.approx(7.9e-3, rel=0.02)   # 7.9 ms a step
    assert flops_kimi.short_conv_train_bytes_per_step(
        config, traffic
    ) / peaks["hbm_bytes_per_s"] == pytest.approx(9.8e-3, rel=0.02)


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
        "producer_blocked_share", "scope_unattributed_share",
        "scope_mixed_share", "update_ms_per_step",
    } <= reported
    assert {"moe_experts_ms_per_step", "moe_dispatch_ms_per_step",
            "moe_walk_ms_per_step", "moe_expert_load_max_over_mean",
            "moe_live_chunks_share", "head_ce_ms_per_step",
            "lm_adam_ms_per_step", "remat_rebuild_ms_per_step",
            "mla_core_ms_per_step", "short_conv_ms_per_step"} <= reported
    # their files list scopes by model and would understate this cell
    assert not reported & {"attn_proj_ms_per_step", "dense_ffn_ms_per_step"}
    # the routed buffer's rule reads tokens x top-8 rows, by the readers'
    # name for the row's `num_experts_per_token`
    from benchmarks.readers import trace_ops_cell

    with_traffic = trace_ops_cell.with_traffic(cell).config
    assert with_traffic["slots"] == 131072 and with_traffic["tokens"] == 16384
    assert cell.config["vocab_size"] == cell.traffic["data"]["vocab_size"]
    assert cell.traffic["minibatch_size"] == 2


def test_kernel_rules_read_the_names_a_trace_carries():
    """The names the kernels carry in a trace, as XLA prints them: the
    scan's metric reads both scan kernels (the remat's second forward too)
    and nothing else, the accepted conv and MLA metrics read this cell's
    conv and attention kernels."""
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    from benchmarks.readers import trace_ops

    ops = {
        "%kda_chunk_fwd.3 = (bf16[2,8192,4096]{2,1,0}, "
        "f32[2,32,128,128,128]{4,3,2,1,0}) custom-call(...)": 3.0,
        "%checkpoint_kda_chunk_fwd_.2 = (bf16[2,8192,4096]{2,1,0}) "
        "custom-call(...)": 2.0,
        "%kda_chunk_bwd.1 = (bf16[2,8192,4096]{2,1,0}) custom-call(...)": 6.0,
        "%silu_short_conv_fwd.1 = bf16[2,8192,12288]{2,1,0} "
        "custom-call(...)": 5.0,
        "%silu_short_conv_bwd = (bf16[2,8192,12288]{2,1,0}) "
        "custom-call(...)": 8.0,
        "%causal_attention_dkv.1 = (bf16[2,8192,8192]) custom-call(...)": 7.0,
        "%causal_attention_fwd = (bf16[2,8192,4096]) custom-call(...)": 4.0,
        "%fusion.9 = bf16[131072,128]{1,0} fusion(...)": 100.0,
    }
    context = {"cell": cell, "trace_steps": 1, "trace": {"op_seconds": ops}}

    def ms(metric):
        spec = manifest.load_layer_metric(cell, metric)
        return trace_ops.read(spec["params"], context)

    assert ms("kda_core_ms_per_step") == pytest.approx(11e3)
    assert ms("short_conv_ms_per_step") == pytest.approx(13e3)
    assert ms("mla_core_ms_per_step") == pytest.approx(11e3)
    spec = manifest.load_layer_metric(cell, "kda_proj_ms_per_step")
    excluded = [re.compile(p) for p in spec["params"]["exclude_ops"]]
    kept = [t for t in ops if not any(p.search(t) for p in excluded)]
    assert sorted(t.split(" ")[0] for t in kept) == [
        "%causal_attention_dkv.1", "%causal_attention_fwd", "%fusion.9",
    ]
    from elasticdl_tpu.common import profiler

    assert set(spec["params"]["scopes"]) <= set(profiler.DEVICE_SCOPES)


def test_roofline_readers_on_a_made_up_trace():
    """Half the least time is 50%; a trace without the kernels says
    nothing.  The scan and the conv are held to their bytes, the core to
    its operations."""
    from benchmarks import flops_kimi
    from benchmarks.readers import roofline_kimi

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    peaks = manifest.load_peaks(cell)["TPU v5 lite"]
    least = {
        "kda_core": flops_kimi.kda_core_train_bytes_per_step(
            cell.config, cell.traffic
        ) / peaks["hbm_bytes_per_s"],
        "short_conv": flops_kimi.short_conv_train_bytes_per_step(
            cell.config, cell.traffic
        ) / peaks["hbm_bytes_per_s"],
        "mla_core": flops_kimi.mla_core_train_flops_per_step(
            cell.config, cell.traffic
        ) / peaks["bf16_flops_per_s"],
    }
    # the core is FLOP-bound at these shapes
    assert least["mla_core"] > flops_kimi.mla_core_train_bytes_per_step(
        cell.config, cell.traffic
    ) / peaks["hbm_bytes_per_s"]
    for work, seconds in least.items():
        context = {
            "cell": cell, "peaks": peaks, "trace_steps": 2, "trace": {
                "op_seconds": {"%k = bf16[1] custom-call()": 4 * seconds},
            },
        }
        params = {"work": work, "include": ["custom-call"]}
        assert roofline_kimi.read(params, context) == pytest.approx(50.0)
        assert roofline_kimi.read(
            {"work": work, "include": ["no such kernel"]}, context
        ) is None
    spec = manifest.load_layer_metric(cell, "kimi_short_conv_roofline_share")
    assert spec["params"]["bound"] == "bytes"
    with pytest.raises(ValueError, match="unknown work"):
        roofline_kimi.read({"work": "gqa_core", "include": ["custom"]},
                           context)


def test_mfu_reader_counts_tokens():
    from benchmarks import flops_kimi
    from benchmarks.readers import kimi_flops

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    peaks = manifest.load_peaks(cell)["TPU v5 lite"]
    per_step = flops_kimi.train_flops_per_token(cell.config, 8192) * 16384
    # one step (two sequences) a second
    context = {"cell": cell, "peaks": peaks, "chips": 1,
               "train_examples_per_s": 2.0}
    assert kimi_flops.read({}, context) == pytest.approx(
        100 * per_step / peaks["bf16_flops_per_s"]
    )
