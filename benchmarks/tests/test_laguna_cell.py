"""The Laguna-XS.2 cell before chip time is spent: the cell end to end on
the CPU at a tiny size through `run.py`'s driver (as test_glm_cell.py does
its cell), the reference's bfloat16 twin and its float8 control under the
cell's own rule, `flops_laguna` against a hand count, every new layer
metric resolving to a reader that imports, and the roofline readers on a
made-up trace.  Nothing these runs time is a measurement."""

import json
import os
import re
import shutil
import types

import numpy as np
import pytest
from test_rehearsal import WRAPPER, rehearse

from benchmarks import manifest

CELL = "laguna-xs.2.train-l8192"
CONFIG_FILE = os.path.join(manifest.BENCH_DIR, "configs", "laguna-xs.2.json")
TINY_ROPES = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 16, "beta_slow": 1,
        "beta_fast": 4, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5,
    },
    "sliding_attention": {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1,
    },
}
# the published per-layer lists stay; the heads a layer has are cut with
# the widths (6 over 2 K/V heads where the list says 48, 8 where 64)
TINY_CONFIG = {
    "hidden_size": 32, "num_hidden_layers": 5, "head_dim": 16,
    "num_key_value_heads": 2, "sliding_window": 12,
    "rope_parameters": TINY_ROPES, "intermediate_size": 48,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
    "num_experts_published": 16, "num_experts": 4, "held_experts": [0, 4],
    "num_experts_per_tok": 2, "vocab_size": 50, "use_bf16": False,
}
TINY_TRAFFIC = {
    "minibatch_size": 2, "records_per_task": 8, "seq_len": 32,
    "data": {"format": "tokens", "seq_len": 32, "vocab_size": 50},
}


def tiny_config() -> dict:
    config = manifest.load_json(CONFIG_FILE)
    config.update(TINY_CONFIG)
    config["num_attention_heads_per_layer"] = [
        heads // 8 for heads in config["num_attention_heads_per_layer"]
    ]
    config["model_params"] = config["model_params"].replace(
        "bf16=True", "bf16=False"
    )
    return config


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny_laguna")
    shutil.copytree(
        manifest.BENCH_DIR, root / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    (root / "benchmarks/configs/laguna-xs.2.json").write_text(
        json.dumps(tiny_config())
    )
    path = root / "benchmarks/traffic/train-l8192.json"
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
    assert "0 of 60 parameter leaves never received" in out


# ---- the reference's twin and its control, at a test's size ---------------


@pytest.fixture(scope="module")
def seeded():
    """Seeded weights of the tiny model (flat), a batch of 8 sequences,
    and the reference's gradient on them."""
    import jax

    from benchmarks import trees
    from benchmarks.reference import laguna as reference
    from elasticdl_tpu.common.model_handler import _call_with_params
    from model_zoo.laguna import laguna as zoo

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


def test_flops_laguna_against_a_hand_count():
    from benchmarks import flops_laguna

    config = manifest.load_json(CONFIG_FILE)
    traffic = manifest.load_json(
        os.path.join(manifest.BENCH_DIR, "traffic", "train-l8192.json")
    )
    assert flops_laguna.layers(config) == [
        ("full_attention", 48, False), ("sliding_attention", 64, True),
        ("sliding_attention", 64, True), ("sliding_attention", 64, True),
        ("full_attention", 48, True),
    ]
    parts = flops_laguna.forward_flops_per_token(config, 8192)
    # by hand, from the published widths: q, k, v, gate, o
    full = 2 * 2048 * (48 * 128 + 2 * 8 * 128 + 48 + 48 * 128)
    window = 2 * 2048 * (64 * 128 + 2 * 8 * 128 + 64 + 64 * 128)
    assert parts["attn_proj"] == 2 * full + 3 * window
    # the causal half: (L + 1) / 2 keys a query, 2 x 2 x 128 a key, 48 heads
    assert parts["full_core"] == 2 * 48 * 512 * 8193 / 2
    # the band: the first 512 queries see t + 1 keys, the others 512
    keys = (512 * 513 / 2 + (8192 - 512) * 512) / 8192
    assert keys == pytest.approx(496.03, abs=0.01)
    assert parts["window_core"] == 3 * 64 * 512 * keys
    assert parts["dense_ffn"] == 2 * 3 * 2048 * 8192
    assert parts["moe_router"] == 4 * 2 * 2048 * 256
    assert parts["moe_shared"] == 4 * 2 * 3 * 2048 * 512
    # 8 slots a token, an eighth of them land here: one expert's worth
    assert parts["moe_experts"] == 4 * 2 * 3 * 2048 * 512
    assert parts["head"] == 2 * 2048 * 12544
    tokens = 2 * 8192
    step = flops_laguna.train_flops_per_token(config, 8192) * tokens
    assert 39.0e12 < step < 39.8e12                  # "~39 TFLOP"
    assert flops_laguna.core_train_flops_per_step(
        config, traffic, "full_attention"
    ) == 3 * parts["full_core"] * tokens
    assert flops_laguna.core_train_flops_per_step(
        config, traffic, "sliding_attention"
    ) == 3 * parts["window_core"] * tokens
    # without a banded kernel the window layers would be 8.3x the work
    causal = flops_laguna.core_flops_per_token(
        "full_attention", 64, config, 8192
    )
    assert 3 * causal / parts["window_core"] == pytest.approx(8.26, abs=0.01)
    # bytes at 2 a number: q, o, dO, dQ and q, o again a QUERY head (6
    # reads, 2 writes... 8 arrays), k, v, dK, dV and k, v again a K/V head
    assert flops_laguna.core_train_bytes_per_step(
        config, traffic, "sliding_attention"
    ) == 3 * 2 * 128 * (6 * 64 + 6 * 8) * tokens
    assert flops_laguna.core_train_bytes_per_step(
        config, traffic, "full_attention"
    ) == 2 * 2 * 128 * (6 * 48 + 6 * 8) * tokens


def new_metrics():
    bench = manifest.load_manifest()
    return [
        m["name"] for m in bench["per_layer"] if m["workloads"] == [CELL]
    ]


def test_every_new_layer_metric_names_a_reader_that_imports():
    names = new_metrics()
    assert sorted(names) == [
        "gqa_core_ms_per_step", "gqa_core_roofline_share",
        "laguna_train_mfu", "window_core_ms_per_step",
        "window_core_roofline_share",
    ]
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    for name in names:
        spec = manifest.load_layer_metric(cell, name)
        assert spec["name"] == name
        assert spec["moves"] == "train_examples_per_s"
        reader = manifest.import_by_name("readers", spec["reader"])
        # nothing to read (no trace, no rate): nothing said, nothing raised
        assert reader.read(spec.get("params", {}), {"cell": cell}) is None


def test_the_cell_reports_what_the_issue_lists():
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    assert cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == [
        "train_examples_per_s", "setup_s",
    ]
    reported = {m["name"] for m in cell.per_layer}
    assert len(reported) == 13 + 5 + 5
    assert {"moe_experts_ms_per_step", "moe_dispatch_ms_per_step",
            "head_ce_ms_per_step", "lm_adam_ms_per_step",
            "moe_expert_load_max_over_mean"} <= reported
    assert not reported & {
        "mla_core_ms_per_step", "mla_core_roofline_share",
        "moe_experts_roofline_share", "lm_train_mfu",
    }
    # the routed buffer's rule reads tokens x top-8 rows, the head's the
    # sliced vocabulary
    from benchmarks.readers import trace_ops_cell

    with_traffic = trace_ops_cell.with_traffic(cell).config
    assert with_traffic["slots"] == 131072 and with_traffic["tokens"] == 16384
    assert cell.config["vocab_size"] == 12544


def test_kernel_rules_tell_the_two_kinds_of_layer_apart():
    """The names the streaming kernels carry in a trace, as XLA prints
    them: a windowed call is the window metric's alone, a causal one the
    grouped metric's alone."""
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    from benchmarks.readers import trace_ops

    ops = {
        "%window_attention_fwd.3 = (bf16[2,8192,8192]{2,1,0}, "
        "f32[2,64,8192,1]{3,2,1,0}) custom-call(...)": 3.0,
        "%window_attention_dkv = (bf16[2,8192,1024]) custom-call(...)": 2.0,
        "%causal_attention_dq.1 = (bf16[2,8192,6144]) custom-call(...)": 7.0,
        "%checkpoint_causal_attention_fwd_.2 = (bf16[2,8192,6144]) "
        "custom-call(...)": 4.0,
        "%fusion.9 = bf16[131072,128]{1,0} fusion(...)": 100.0,
    }
    context = {"cell": cell, "trace_steps": 1, "trace": {"op_seconds": ops}}

    def ms(metric):
        spec = manifest.load_layer_metric(cell, metric)
        return trace_ops.read(spec["params"], context)

    assert ms("window_core_ms_per_step") == pytest.approx(5e3)
    assert ms("gqa_core_ms_per_step") == pytest.approx(11e3)


def test_roofline_readers_on_a_made_up_trace():
    """Half the least time is 50%; a trace without the kernels says
    nothing."""
    from benchmarks import flops_laguna
    from benchmarks.readers import roofline_laguna

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    peaks = manifest.load_peaks(cell)["TPU v5 lite"]
    for work, kind in (("window_core", "sliding_attention"),
                       ("gqa_core", "full_attention")):
        flops = flops_laguna.core_train_flops_per_step(
            cell.config, cell.traffic, kind
        )
        moved = flops_laguna.core_train_bytes_per_step(
            cell.config, cell.traffic, kind
        )
        least = flops / peaks["bf16_flops_per_s"]
        # both kinds are FLOP-bound at these shapes
        assert least > moved / peaks["hbm_bytes_per_s"]
        context = {
            "cell": cell, "peaks": peaks, "trace_steps": 2, "trace": {
                "op_seconds": {"%k = bf16[1] custom-call()": 4 * least},
            },
        }
        params = {"work": work, "include": ["custom-call"]}
        assert roofline_laguna.read(params, context) == pytest.approx(50.0)
        assert roofline_laguna.read(
            {"work": work, "include": ["no such kernel"]}, context
        ) is None
    with pytest.raises(ValueError, match="unknown work"):
        roofline_laguna.read({"work": "mla_core", "include": ["custom"]},
                             context)


def test_mfu_reader_counts_tokens():
    from benchmarks import flops_laguna
    from benchmarks.readers import laguna_flops

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    peaks = manifest.load_peaks(cell)["TPU v5 lite"]
    per_step = flops_laguna.train_flops_per_token(cell.config, 8192) * 16384
    # one step (two sequences) a second
    context = {"cell": cell, "peaks": peaks, "chips": 1,
               "train_examples_per_s": 2.0}
    assert laguna_flops.read({}, context) == pytest.approx(
        100 * per_step / peaks["bf16_flops_per_s"]
    )
