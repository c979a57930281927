"""The nemotron-3-nano-30b-a3b cell before chip time is spent: the cell end
to end on the CPU at a tiny size through `run.py`'s driver (as
test_granite_cell.py does its cell), the reference's float8 control under
the cell's own rule, four reference programs for nine layers,
`flops_nemotron` against a hand count, every new layer metric resolving to
a reader that imports, the readers on a made-up trace, and the accepted
`head_ce_ms_per_step` rule against the step compiled for a described v5e
(the vocabulary slice equals the tokens a step here).  Nothing these runs
time is a measurement.

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

CELL = "nemotron-3-nano-30b-a3b.train-l8192-b2-v16k"
CONFIG_FILE = os.path.join(
    manifest.BENCH_DIR, "configs", "nemotron-3-nano-30b-a3b.json"
)
TRAFFIC_FILE = os.path.join(
    manifest.BENCH_DIR, "traffic", "train-l8192-b2-v16k.json"
)
# the published pattern and the cut's nine layers stay; 4 state-space
# heads of 8 over 16 state columns in 2 groups, 4 query heads of 16 over 2
# K/V heads, top-6 of 128 with experts 0-7 held, 24 wide beside a shared
# expert 40 wide
TINY_CONFIG = {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "mamba_num_heads": 4, "mamba_head_dim": 8,
    "ssm_state_size": 16, "n_groups": 2, "moe_intermediate_size": 24,
    "moe_shared_expert_intermediate_size": 40, "vocab_size": 50,
    "use_bf16": False,
}
TINY_TRAFFIC = {
    "minibatch_size": 2, "records_per_task": 16, "seq_len": 32,
    "data": {"format": "tokens", "seq_len": 32, "vocab_size": 50},
}
NEW_METRICS = {
    "nemotron_train_mfu", "nemotron_ssd_core_roofline_share",
    "nemotron_ssd_proj_ms_per_step", "nemotron_short_conv_roofline_share",
    "nemotron_gqa_core_roofline_share",
    "nemotron_moe_experts_roofline_share",
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
    root = tmp_path_factory.mktemp("tiny_nemotron")
    shutil.copytree(
        manifest.BENCH_DIR, root / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    (root / "benchmarks/configs/nemotron-3-nano-30b-a3b.json").write_text(
        json.dumps(tiny_config())
    )
    path = root / "benchmarks/traffic/train-l8192-b2-v16k.json"
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
    # 4 x 9 Mamba-2, 4 x 6 expert and 5 attention leaves (ONE norm a
    # layer), embedding, head and final norm
    assert "0 of 68 parameter leaves never received" in out


# ---- the reference's control, at a test's size ----------------------------


@pytest.fixture(scope="module")
def seeded():
    """Seeded weights of the tiny model (flat), a batch of 8 sequences,
    and the reference's gradient on them."""
    import jax

    from benchmarks import trees
    from benchmarks.reference import nemotron_h as reference
    from elasticdl_tpu.common.model_handler import _call_with_params
    from model_zoo.nemotron import nemotron_h as zoo

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
    """Nine layers, three kinds: the jitted block programs are traced
    three times forward and three times backward, the parameters their
    arguments; with the embedding-and-tail that is FOUR kinds of
    program."""
    reference = seeded.reference
    for program in (reference._block_fwd, reference._block_bwd):
        program.clear_cache()
    reference.loss_and_grads(
        seeded.flat, seeded.features, seeded.labels, seeded.config
    )
    assert reference._block_fwd._cache_size() == 3
    assert reference._block_bwd._cache_size() == 3
    assert "".join(reference.layers_of(seeded.config)) == "MEMEM*EME"


# ---- operations by shapes, and the metric files ---------------------------


def test_flops_nemotron_against_a_hand_count():
    from benchmarks import flops_nemotron

    config = manifest.load_json(CONFIG_FILE)
    traffic = manifest.load_json(TRAFFIC_FILE)
    assert "".join(flops_nemotron.layers(config)) == "MEMEM*EME"
    parts = flops_nemotron.forward_flops_per_token(config, 8192)
    # by hand, from the published widths: in_proj 2688 x 10304 (4096 + 6144
    # + 64), out_proj 4096 x 2688, in 4 layers
    assert parts["ssm_proj"] == 4 * 2 * (2688 * 10304 + 4096 * 2688)
    # the recurrence: 5 passes over a head's 64 x 128 state and 3 x 64
    assert parts["ssd_core"] == 4 * 64 * (5 * 64 * 128 + 3 * 64)
    # q and o at 32 heads of 128, k and v at 2
    assert parts["attn_proj"] == 2 * (2 * 11_010_048 + 2 * 688_128)
    # the causal half: (L + 1) / 2 keys a query, 32 heads of 128
    assert parts["attn_core"] == 2 * 32 * (128 + 128) * 8193 / 2
    assert parts["moe_router"] == 4 * 2 * 2688 * 128
    # TWO products an expert, not three: no gate projection
    assert parts["moe_shared"] == 4 * 2 * 2 * 2688 * 3712
    # six slots a token, a sixteenth of them on held experts
    assert parts["moe_experts"] == 4 * 2 * 2 * 2688 * 1856 * 6 / 16
    assert parts["head"] == 2 * 2688 * 16384
    total = sum(parts.values())
    assert total == pytest.approx(714.5e6, rel=1e-3)
    tokens = 16384
    step = flops_nemotron.train_flops_per_token(config, 8192) * tokens
    assert 35.0e12 < step < 35.3e12                       # "~3.5e13"
    # bytes at 2 a number: q read twice, o written and read, dO read, dQ
    # written (6 x 32 heads); k, v read twice and dK, dV written (6 x 2)
    assert flops_nemotron.gqa_core_train_bytes_per_step(config, traffic) == (
        2 * 128 * (6 * 32 + 6 * 2) * tokens
    )
    assert flops_nemotron.gqa_core_train_flops_per_step(config, traffic) == (
        3 * parts["attn_core"] * tokens
    )
    # the scan's least traffic: x, y, dx, dy at 4,096 columns and B, C,
    # dB, dC at 8 x 128 at 2 bytes, dt and its gradient at 4 a head, 4
    # layers
    assert flops_nemotron.ssd_core_train_bytes_per_step(config, traffic) == (
        (4 * 4096 * 2 + 4 * 1024 * 2 + 2 * 64 * 4) * tokens * 4
    )
    assert flops_nemotron.ssd_core_train_flops_per_step(config, traffic) == (
        3 * parts["ssd_core"] * tokens
    )
    # the conv pass: 2 streams forward and 3 backward of tokens x 6,144
    # in each of the 4 Mamba-2 layers
    assert flops_nemotron.short_conv_train_bytes_per_step(
        config, traffic
    ) == 2 * 5 * tokens * 6144 * 4
    # the experts at the rows actually routed here: 6 x 16,384 / 16 rows a
    # layer at balanced load, 4 d w a row forward
    rows = tokens * 6 / 16
    assert flops_nemotron.moe_experts_train_flops_per_step(
        config, traffic, 1 / 16
    ) == 3 * 4 * rows * 4 * 2688 * 1856
    assert flops_nemotron.moe_experts_train_bytes_per_step(
        config, traffic, 1 / 16
    ) == 4 * (
        8 * 2 * 2688 * 1856 * (3 * 2 + 4)
        + rows * 2 * 3 * (2 * 2688 + 2 * 1856)
    )
    peaks = manifest.load_json(
        os.path.join(manifest.BENCH_DIR, "peaks.json")
    )["TPU v5 lite"]
    # the step at the chip's peak: "185 ms" by the issue's rounder count
    assert step / peaks["bf16_flops_per_s"] == pytest.approx(0.1783, rel=0.01)


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
    assert {
        "remat_rebuild_ms_per_step", "remat_kept_share",
        "lm_adam_ms_per_step", "head_ce_ms_per_step", "gqa_core_ms_per_step",
        "short_conv_ms_per_step", "ssd_core_ms_per_step",
        "ssm_state_kept_share", "attn_proj_ms_per_step",
        "dense_ffn_ms_per_step", "moe_experts_ms_per_step",
        "moe_dispatch_ms_per_step", "moe_walk_ms_per_step",
        "moe_expert_load_max_over_mean", "moe_live_chunks_share",
    } <= reported
    # no latent, windowed, delta-rule, gated-conv or DeepFM metric has
    # anything to read here, nor another model's shares
    assert not {
        name for name in reported
        if name.startswith(("mla_", "window_", "kda_", "conv_proj_",
                            "arena_", "scatter_", "optimizer_", "granite_",
                            "kimi_", "lfm2_", "laguna_", "lm_train"))
    }
    assert cell.config["vocab_size"] == cell.traffic["data"]["vocab_size"]
    assert cell.config["vocab_size"] == 16384
    assert cell.traffic["minibatch_size"] == 2
    assert cell.traffic["seq_len"] == 8192
    assert cell.traffic["records_per_task"] == 16
    # `moe_dispatch_ms_per_step`'s {slots}: six slots a token
    from benchmarks.readers import trace_ops_cell

    assert trace_ops_cell.with_traffic(cell).config["slots"] == 98304


def test_scope_rules_match_this_models_scopes():
    """`attn_proj_ms_per_step`'s patterns match `nemotron/attn` ALONE of
    this model's scopes, `dense_ffn_ms_per_step` reads the shared expert
    (`shared`, inside `nemotron/moe`), and the new
    `nemotron_ssd_proj_ms_per_step` names the five state-space scopes."""
    from benchmarks.readers import scope_ops
    from elasticdl_tpu.common import profiler

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    ours = [s for s in profiler.DEVICE_SCOPES if s.startswith("nemotron/")]
    assert len(ours) == 10

    def matched(metric):
        spec = manifest.load_layer_metric(cell, metric)
        return scope_ops.spelled_out(
            spec["params"]["scopes"], profiler.DEVICE_SCOPES
        )

    assert [s for s in matched("attn_proj_ms_per_step")
            if s.startswith("nemotron/")] == ["nemotron/attn"]
    assert "shared" in matched("dense_ffn_ms_per_step")
    assert not [s for s in matched("dense_ffn_ms_per_step")
                if s.startswith("nemotron/")]
    assert matched("nemotron_ssd_proj_ms_per_step") == [
        "nemotron/ssm/proj", "nemotron/ssm/conv", "nemotron/ssm/core",
        "nemotron/ssm/gated_norm", "nemotron/ssm/out",
    ]
    # Granite's metric names Granite's scopes and reads nothing here
    assert not [s for s in matched("ssd_proj_ms_per_step")
                if s.startswith("nemotron/")]
    # with `nemotron/embed`, `nemotron/norm`, `nemotron/moe` (inside it
    # the walk's four and `shared`) and `nemotron/head_ce` they are all
    # ten: the scopes tile the model
    assert set(ours) == {
        "nemotron/embed", "nemotron/norm", "nemotron/head_ce",
        "nemotron/attn", "nemotron/moe",
        *matched("nemotron_ssd_proj_ms_per_step"),
    }
    assert profiler.catalogue_scope(
        "layer_1/moe/nemotron/moe/routed/experts"
    ) == "experts"
    assert profiler.catalogue_scope("layer_1/moe/nemotron/moe/shared") == (
        "shared"
    )


def test_kernel_rules_read_the_names_a_trace_carries():
    """The names the kernels carry in a trace, as XLA prints them: the
    accepted scan, conv, attention, expert and dispatch metrics read this
    cell's kernels; the state-space projections' metric leaves the scan's
    and the conv's kernels out."""
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    from benchmarks.readers import trace_ops, trace_ops_cell

    ops = {
        "%ssd_fwd.3 = (bf16[2,8192,4096]{2,1,0}, "
        "f32[2,32,4096,128]{3,2,1,0}) custom-call(...)": 3.0,
        "%checkpoint_ssd_fwd_.2 = (bf16[2,8192,4096]{2,1,0}) "
        "custom-call(...)": 2.0,
        "%ssd_bwd.1 = (bf16[2,8192,4096]{2,1,0}) custom-call(...)": 6.0,
        "%silu_short_conv_fwd.1 = bf16[2,8192,6144]{2,1,0} "
        "custom-call(...)": 5.0,
        "%silu_short_conv_bwd = (bf16[2,8192,6144]{2,1,0}) "
        "custom-call(...)": 8.0,
        "%causal_attention_dkv.1 = (bf16[2,8192,4096]) custom-call(...)": 7.0,
        "%causal_attention_fwd = (bf16[2,8192,4096]) custom-call(...)": 4.0,
        "%ragged-dot-none.4 = bf16[16384,1856]{1,0} custom-call(...)": 9.0,
        "%sort.2 = (s32[98304]{0}, s32[98304]{0}) sort(...)": 1.5,
        "%fusion.9 = bf16[16384,10304]{1,0} fusion(...)": 100.0,
    }
    context = {"cell": cell, "trace_steps": 1, "trace": {"op_seconds": ops}}

    def ms(metric, reader=trace_ops):
        spec = manifest.load_layer_metric(cell, metric)
        return reader.read(spec["params"], context)

    assert ms("ssd_core_ms_per_step") == pytest.approx(11e3)
    assert ms("short_conv_ms_per_step") == pytest.approx(13e3)
    assert ms("gqa_core_ms_per_step") == pytest.approx(11e3)
    assert ms("moe_experts_ms_per_step") == pytest.approx(9e3)
    assert ms("moe_dispatch_ms_per_step", trace_ops_cell) == (
        pytest.approx(1.5e3)
    )
    spec = manifest.load_layer_metric(cell, "nemotron_ssd_proj_ms_per_step")
    excluded = [re.compile(p) for p in spec["params"]["exclude_ops"]]
    kept = [t for t in ops if not any(p.search(t) for p in excluded)]
    assert sorted(t.split(" ")[0] for t in kept) == [
        "%causal_attention_dkv.1", "%causal_attention_fwd", "%fusion.9",
        "%ragged-dot-none.4", "%sort.2",
    ]


def test_roofline_readers_on_a_made_up_trace():
    """Half the least time is 50%; a trace without the kernels says
    nothing; the experts' work follows the gauge the layers set."""
    from benchmarks import flops_nemotron
    from benchmarks.readers import roofline_nemotron
    from elasticdl_tpu.common import metrics as metrics_lib

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    peaks = manifest.load_peaks(cell)["TPU v5 lite"]

    def least(work, *extra):
        flops = getattr(flops_nemotron, f"{work}_train_flops_per_step")
        bytes_ = getattr(flops_nemotron, f"{work}_train_bytes_per_step")
        return max(
            flops(cell.config, cell.traffic, *extra)
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

    for work in ("ssd_core", "short_conv", "gqa_core"):
        context = context_of(least(work))
        params = {"work": work, "include": ["custom-call"]}
        assert roofline_nemotron.read(params, context) == pytest.approx(50.0)
        assert roofline_nemotron.read(
            {"work": work, "include": ["no such kernel"]}, context
        ) is None
    # the scan is held to its bytes at 8 groups (B and C are a fifth of
    # its traffic), the attention core to its operations
    by_bytes = flops_nemotron.ssd_core_train_bytes_per_step(
        cell.config, cell.traffic
    ) / peaks["hbm_bytes_per_s"]
    assert by_bytes > flops_nemotron.ssd_core_train_flops_per_step(
        cell.config, cell.traffic
    ) / peaks["bf16_flops_per_s"]
    spec = manifest.load_layer_metric(
        cell, "nemotron_short_conv_roofline_share"
    )
    assert spec["params"]["bound"] == "bytes"
    gauge = metrics_lib.default_registry().gauge(
        "worker_moe_routed_here_ratio", labelnames=("layer",)
    )
    params = {"work": "moe_experts", "include": ["custom-call"]}
    for layer, share in (("layer_1/moe/routed", 0.05),
                         ("layer_3/moe/routed", 0.075)):
        gauge.labels(layer=layer).set(share)
    assert roofline_nemotron.read(
        params, context_of(least("moe_experts", 0.0625))
    ) == pytest.approx(50.0)
    with pytest.raises(ValueError, match="unknown work"):
        roofline_nemotron.read({"work": "kda_core", "include": ["custom"]},
                               context_of(1.0))


def test_mfu_reader_counts_the_rows_routed_here(monkeypatch):
    """The whole step's operations with the held experts' products over
    the rows the gauge says were routed here, against the traced steps'
    device time: a step at the peak's pace reads 100%, balanced load
    reads less work than the routers' plateau, and a run without a trace
    or a program without the gauge reads as nothing."""
    from benchmarks import flops_nemotron
    from benchmarks.readers import nemotron_flops

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    peaks = manifest.load_peaks(cell)["TPU v5 lite"]

    def per_step(share):
        return 16384 * flops_nemotron.train_flops_per_token(
            cell.config, 8192, share
        )

    def context_of(seconds):
        return {"cell": cell, "peaks": peaks, "chips": 1, "trace_steps": 8,
                "trace": {"window_s": 8 * seconds, "busy_s": 8 * seconds}}

    monkeypatch.setattr(
        nemotron_flops.registry_gauge, "children",
        lambda metric: {"worker_moe_routed_here_ratio": [0.6, 0.7]}[metric],
    )
    at_peak = per_step(0.65) / peaks["bf16_flops_per_s"]
    assert nemotron_flops.read({}, context_of(2 * at_peak)) == (
        pytest.approx(50.0)
    )
    # the plateau's rows are over a third more work than balanced load's
    assert per_step(0.65) > 1.35 * per_step(None)
    assert per_step(None) == per_step(0.0625)
    plain = {k: v for k, v in context_of(1.0).items() if k != "trace"}
    assert nemotron_flops.read({}, plain) is None
    monkeypatch.setattr(
        nemotron_flops.registry_gauge, "children", lambda metric: None
    )
    assert nemotron_flops.read({}, context_of(1.0)) is None


# ---- the accepted head's rule, where the slice equals the tokens a step ----


def test_the_head_rule_matches_the_cross_entropys_loops_alone():
    """`head_ce_ms_per_step` matches `^%while... [\\d+,{vocab_size}]`, and
    here the vocabulary slice (16,384) equals the tokens a step (16,384):
    in the cell's train step compiled for a described v5e (two layers of
    each kind are enough: every loop of the step is there) the rule
    matches loops under `nemotron/head_ce` and no other."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from elasticdl_tpu.common.model_handler import _call_with_params
    from elasticdl_tpu.layers.step_metrics import AUX_LOSS, STEP_METRICS
    from elasticdl_tpu.ops import flash_attention, short_conv, ssd
    from model_zoo.nemotron import nemotron_h as zoo

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    chip = SingleDeviceSharding(topo.devices[0])
    config = dict(manifest.load_json(CONFIG_FILE), layers_held=[4, 5, 6])
    model = _call_with_params(
        zoo.custom_model, config["model_params"].format(**config)
    )
    assert "".join(model.config.layers) == "M*E"
    optimizer = zoo.optimizer(1e-4)
    ids = jax.ShapeDtypeStruct((2, 8192), jnp.int32)
    variables = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), {"input_ids": ids}
    )
    params = variables["params"]
    state = {k: v for k, v in variables.items() if k != "params"}

    def step(params, opt_state, state, ids):
        def loss_of(params):
            out, _ = model.apply(
                {"params": params, **state}, {"input_ids": ids},
                mutable=list(state) + [AUX_LOSS, STEP_METRICS],
            )
            return zoo.loss(None, out.astype(jnp.float32))

        loss, grads = jax.value_and_grad(loss_of)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip
        ), tree)

    kernels = (flash_attention, short_conv, ssd)
    before = [module.use_interpret for module in kernels]
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        for module in kernels:
            module.use_interpret = lambda: False
        text = jax.jit(step, donate_argnums=(0, 1)).lower(
            placed(params), placed(jax.eval_shape(optimizer.init, params)),
            placed(state), placed(ids),
        ).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        for module, plain in zip(kernels, before):
            module.use_interpret = plain
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    spec = manifest.load_layer_metric(cell, "head_ce_ms_per_step")
    (pattern,) = spec["params"]["include"]
    rule = re.compile(pattern.format(**cell.config))
    loops = [
        line.strip().removeprefix("ROOT ") for line in text.splitlines()
        if re.match(r"\s*(ROOT )?%while[.\d]* = \(", line)
    ]
    matched = [line for line in loops if rule.search(line)]
    assert len(loops) >= 4 and len(matched) == 2        # forward, backward
    for line in matched:
        assert "nemotron/head_ce/while" in line, line[-200:]
    for line in set(loops) - set(matched):
        assert "nemotron/head_ce" not in line or "[8,2048]" in line
    # the step runs the scan's kernels at 8 groups and the walk's grouped
    # products, not the jnp forms
    assert "ssd_fwd" in text and "ssd_bwd" in text
    assert "ragged-dot" in text
