"""The GLM-4.7-Flash cell before chip time is spent: the cell end to end
on the CPU at a tiny size through `run.py`'s driver (as test_rehearsal.py
does the cells it lists), the reference's bfloat16 twin and its float8
control under `check_gradient`, `flops_lm` against a hand count, and
every layer metric of the cell resolving to a reader that imports.
Nothing these runs time is a measurement."""

import json
import os
import re
import shutil
import types

import numpy as np
import pytest
from test_rehearsal import WRAPPER, rehearse

from benchmarks import manifest

CELL = "glm-4.7-flash.train-l4096"
TINY_CONFIG = {
    "hidden_size": 32, "num_hidden_layers": 3, "num_attention_heads": 2,
    "q_lora_rank": 12, "kv_lora_rank": 8, "qk_nope_head_dim": 6,
    "qk_rope_head_dim": 4, "v_head_dim": 10, "intermediate_size": 48,
    "moe_intermediate_size": 16, "n_routed_experts_published": 8,
    "n_routed_experts": 4, "held_experts": [0, 4],
    "num_experts_per_tok": 2, "vocab_size": 50, "use_bf16": False,
}
TINY_TRAFFIC = {
    "minibatch_size": 4, "records_per_task": 16, "seq_len": 16,
    "data": {"format": "tokens", "seq_len": 16, "vocab_size": 50},
}


def tiny_config() -> dict:
    config = manifest.load_json(
        os.path.join(manifest.BENCH_DIR, "configs", "glm-4.7-flash.json")
    )
    config.update(TINY_CONFIG)
    config["model_params"] = config["model_params"].replace(
        "bf16=True", "bf16=False"
    )
    return config


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny_glm")
    shutil.copytree(
        manifest.BENCH_DIR, root / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    (root / "benchmarks/configs/glm-4.7-flash.json").write_text(
        json.dumps(tiny_config())
    )
    path = root / "benchmarks/traffic/train-l4096.json"
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
    from benchmarks.reference import glm_moe_lite as reference
    from elasticdl_tpu.common.model_handler import _call_with_params
    from model_zoo.glm import glm_moe_lite as zoo

    config = dict(tiny_config(), use_bf16=True)
    ids = np.random.RandomState(0).randint(
        0, config["vocab_size"], (8, 16)
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
        # the module as the driver will see it the day `TWIN_RATIO` is
        # named `STATED_RATIO` (reference/glm_moe_lite.py says when)
        held=types.SimpleNamespace(
            **{k: getattr(reference, k) for k in dir(reference)
               if not k.startswith("__")},
            STATED_RATIO=reference.TWIN_RATIO,
        ),
        reference=reference,
    )


def twin_of(seeded, tower):
    loss, grads = seeded.reference.loss_and_grads(
        seeded.flat, seeded.features, seeded.labels, seeded.config,
        tower=tower,
    )
    return loss, {k: np.asarray(v, np.float32) for k, v in grads.items()}


@pytest.mark.parametrize("tower, passes", [
    ("bfloat16", True), ("float8_e4m3fn", False),
])
def test_twin_and_control_under_check_gradient(seeded, tower, passes):
    """The stated type's twin in the step's place passes the twin-held
    rule (it IS the yardstick: every share 1 / TWIN_RATIO); the type
    below fails it."""
    from benchmarks.drivers import train

    _, got = twin_of(seeded, tower)
    check = train.check_gradient(
        seeded.held, seeded.flat, seeded.features, seeded.labels,
        seeded.config, seeded.want, got,
    )
    assert check["ok"] is passes, sorted(
        check["shares"].items(), key=lambda kv: -kv[1]
    )[:4]


def test_control_fails_the_cells_own_rule(seeded):
    """What the cell is held to today, with no `STATED_RATIO` in the
    module: every leaf inside `LEAF_REL_L2` of its norm, the cosine over
    `GRAD_COSINE_MIN`, the loss inside `LOSS_ATOL` (constants read at the
    cell's size on the chip; a test's size only shows the rule applies
    and that the type below fails it, the reference itself passes it)."""
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
    loss, control = twin_of(seeded, "float8_e4m3fn")
    assert not held(control)


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


def test_flops_lm_against_a_hand_count():
    from benchmarks import flops_lm

    config = manifest.load_json(
        os.path.join(manifest.BENCH_DIR, "configs", "glm-4.7-flash.json")
    )
    traffic = manifest.load_json(
        os.path.join(manifest.BENCH_DIR, "traffic", "train-l4096.json")
    )
    parts = flops_lm.forward_flops_per_token(config, 4096)
    # by hand, from the published widths (ISSUE 29's arithmetic)
    proj = 2 * (2048 * 768 + 768 * 20 * 256 + 2048 * 576
                + 512 * 20 * 448 + 20 * 256 * 2048)
    assert proj == 43_515_904
    assert parts["mla_proj"] == 6 * proj             # 5 layers + MTP
    assert parts["mla_core"] == 6 * 20 * 512 * 4097  # causal half
    assert parts["dense_ffn"] == 2 * 3 * 2048 * 10240
    assert parts["moe_shared"] == 5 * 2 * 3 * 2048 * 1536
    # 4 slots a token, an eighth of them land here: half an expert
    assert parts["moe_experts"] == 5 * 0.5 * 2 * 3 * 2048 * 1536
    assert parts["head"] == 2 * 2 * 2048 * 19360
    assert parts["mtp_proj"] == 2 * 4096 * 2048
    per_token = flops_lm.train_flops_per_token(config, 4096)
    assert 0.93e9 < per_token / 3 < 0.99e9           # "0.96 GFLOP"
    assert flops_lm.mla_core_train_flops_per_step(config, traffic) == (
        3 * parts["mla_core"] * 4 * 4096
    )
    # q, k, v, o at 2 bytes: 4 arrays forward, 8 backward, 6 cores
    assert flops_lm.mla_core_train_bytes_per_step(config, traffic) == (
        2 * 20 * 256 * 12 * 16384 * 6
    )
    twice = flops_lm.moe_experts_train_flops_per_step(config, traffic, 0.25)
    assert twice == 2 * flops_lm.moe_experts_train_flops_per_step(
        config, traffic, 0.125
    )


def new_metrics():
    bench = manifest.load_manifest()
    return [
        m["name"] for m in bench["per_layer"] if m["workloads"] == [CELL]
    ]


def test_every_new_layer_metric_names_a_reader_that_imports():
    names = new_metrics()
    assert len(names) == 9, names
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    for name in names:
        spec = manifest.load_layer_metric(cell, name)
        assert spec["name"] == name
        assert spec["moves"] == "train_examples_per_s"
        reader = manifest.import_by_name("readers", spec["reader"])
        # nothing to read (no trace, no rate, no counter): nothing said
        assert reader.read(spec.get("params", {}), {"cell": cell}) is None


def test_roofline_reader_on_a_made_up_trace():
    """Half the least time is 50%; the experts' share reads the counter
    the program sets and says nothing without it."""
    from benchmarks import flops_lm
    from benchmarks.readers import roofline_lm
    from elasticdl_tpu.common import metrics as metrics_lib

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    peaks = manifest.load_peaks(cell)["TPU v5 lite"]
    flops = flops_lm.mla_core_train_flops_per_step(cell.config, cell.traffic)
    least = flops / peaks["bf16_flops_per_s"]
    context = {
        "cell": cell, "peaks": peaks, "trace_steps": 2,
        "trace": {"op_seconds": {"%k = bf16[1] custom-call()": 4 * least}},
    }
    params = {"work": "mla_core", "include": ["custom-call"], "bound": "flops"}
    assert roofline_lm.read(params, context) == pytest.approx(50.0)
    experts = {"work": "moe_experts", "include": ["custom-call"]}
    gauge = metrics_lib.default_registry().gauge(
        "worker_moe_routed_here_ratio", "", labelnames=("layer",)
    )
    gauge.reset()
    assert roofline_lm.read(experts, context) is None
    gauge.labels(layer="layer_1/moe/routed").set(0.125)
    assert 0 < roofline_lm.read(experts, context) < 100
    gauge.reset()
