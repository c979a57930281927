"""The `scope_ops` reader on a hand-made trace and table, what it says of
a program without a table (the parent of the PR that added it), and the
ten metric files that use it."""

import re

import pytest

from benchmarks import manifest
from benchmarks.readers import scope_ops
from elasticdl_tpu.common import profiler, programs

CELLS = [w["name"] for w in manifest.load_manifest()["workloads"]]
METRICS = {
    "scope_unattributed_share": CELLS,
    "scope_mixed_share": CELLS,
    "update_ms_per_step": CELLS,
    "remat_rebuild_ms_per_step": CELLS[1:],
    "moe_walk_ms_per_step": CELLS[1:],
    "attn_proj_ms_per_step": CELLS[1:],
    "dense_ffn_ms_per_step": CELLS[1:],
    "conv_proj_ms_per_step": ["lfm2-24b-a2b.train-l8192-b4"],
    "arena_lookup_ms_per_step": ["deepfm-criteo-kaggle.train-stream"],
    "arena_backward_ms_per_step": ["deepfm-criteo-kaggle.train-stream"],
}


def row(opcode, scope, phase="forward", fused=()):
    return programs.ScopeRow(
        opcode, "main", opcode in programs.CONTAINER_OPCODES, scope, phase,
        profiler.catalogue_scope(scope), tuple(fused),
    )


TABLE = {
    "while.1": row("while", "layer_1/lfm2/moe/routed/combine"),
    "fusion.1": row("fusion", "layer_1/lfm2/moe/routed/dispatch"),
    "fusion.2": row("fusion", "layer_1/lfm2/moe/routed/combine", "backward"),
    "ragged-dot-none.1": row("custom-call", "layer_1/lfm2/moe/experts"),
    "fusion.3": row("fusion", "layer_1/conv/lfm2/short_conv/in_proj",
                    "rebuild", fused=("lfm2/norm", "lfm2/short_conv")),
    "short_conv_fwd.4": row("custom-call", "layer_1/conv/lfm2/short_conv"),
    "causal_attention_dq.2": row("custom-call", "layer_2/attn/lfm2/attn",
                                 "backward"),
    "fusion.5": row("fusion", "layer_2/attn/lfm2/attn/q", "backward"),
    "fusion.6": row("fusion", "train/optimizer"),
    "fusion.7": row("fusion", "layer_0/lfm2/dense_ffn/mlp/down"),
    "copy.8": row("copy", "Lfm2Moe"),
}
SECONDS = {
    "%while.1 = (s32[]) while(%t)": 1.0,
    "%fusion.1 = f32[8] fusion(%a)": 0.10,
    "%fusion.2 = f32[8] fusion(%a)": 0.20,
    "%ragged-dot-none.1 = bf16[8] custom-call(%a)": 0.30,
    "%fusion.3 = f32[8] fusion(%a)": 0.04,
    "%short_conv_fwd.4 = bf16[8] custom-call(%a)": 0.05,
    "%causal_attention_dq.2 = bf16[8] custom-call(%a)": 0.40,
    "%fusion.5 = f32[8] fusion(%a)": 0.06,
    "%fusion.6 = f32[8] fusion(%a)": 0.07,
    "%fusion.7 = f32[8] fusion(%a)": 0.08,
    "%copy.8 = f32[8] copy(%a)": 0.02,
    "%fusion.99 = f32[8] fusion(%a)": 0.01,
}
LEAVES = sum(SECONDS.values()) - 1.0
CONTEXT = {"trace": {"op_seconds": SECONDS}, "trace_steps": 2}


@pytest.fixture
def tabled(monkeypatch):
    """The default registry answers for `worker_train_step` with TABLE."""
    registry = programs.default_program_registry()
    monkeypatch.setattr(
        registry, "scope_table",
        lambda name: TABLE if name == "worker_train_step" else None,
    )


def spec_of(metric: str) -> dict:
    cell = manifest.resolve_cell(manifest.load_manifest(), CELLS[-1])
    return manifest.load_layer_metric(cell, metric)


@pytest.mark.parametrize("metric, want_ms", [
    ("update_ms_per_step", 35.0),
    ("remat_rebuild_ms_per_step", 20.0),
    ("moe_walk_ms_per_step", 150.0),          # not `experts`, not the loop
    ("attn_proj_ms_per_step", 30.0),          # less the attention kernel
    ("dense_ffn_ms_per_step", 40.0),
    ("conv_proj_ms_per_step", 20.0),          # less the conv kernel
])
def test_the_files_rules_on_a_hand_made_trace(tabled, metric, want_ms):
    spec = spec_of(metric)
    assert manifest.import_by_name("readers", spec["reader"]) is scope_ops
    assert scope_ops.read(spec["params"], CONTEXT) == pytest.approx(want_ms)


def test_the_two_shares(tabled, capsys):
    unattributed = scope_ops.read(
        spec_of("scope_unattributed_share")["params"], CONTEXT
    )
    # the copy under no catalogue scope and the fusion in no table
    assert unattributed == pytest.approx(100.0 * 0.03 / LEAVES)
    said = capsys.readouterr().err
    assert "dispatch|forward 50.00" in said and "fusion.3 20.00" in said
    mixed = scope_ops.read(spec_of("scope_mixed_share")["params"], CONTEXT)
    assert mixed == pytest.approx(100.0 * 0.04 / LEAVES)


def test_a_rule_that_keeps_nothing_says_nothing(tabled):
    params = spec_of("arena_lookup_ms_per_step")["params"]
    assert scope_ops.read(params, CONTEXT) is None


def test_no_trace_no_steps_no_table(tabled, monkeypatch):
    params = spec_of("update_ms_per_step")["params"]
    assert scope_ops.read(params, {"trace": None}) is None
    assert scope_ops.read(params, {**CONTEXT, "trace_steps": 0}) is None
    assert scope_ops.read({**params, "program": "never_ran"}, CONTEXT) is None
    with pytest.raises(ValueError, match="unknown stat"):
        scope_ops.read({**params, "stat": "median"}, CONTEXT)


def test_a_program_older_than_the_table_reads_as_nothing(monkeypatch):
    """The parent commit: no `scope_table`, no `device_ms_by_scope`."""
    monkeypatch.delattr(profiler, "device_ms_by_scope")
    for metric in METRICS:
        assert scope_ops.read(spec_of(metric)["params"], CONTEXT) is None
    monkeypatch.undo()
    monkeypatch.delattr(programs.ProgramRegistry, "scope_table")
    params = spec_of("update_ms_per_step")["params"]
    assert scope_ops.read(params, CONTEXT) is None


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_each_file_and_its_entry(metric):
    spec = spec_of(metric)
    entry = {
        m["name"]: m for m in manifest.load_manifest()["per_layer"]
    }[metric]
    assert entry["workloads"] == METRICS[metric]
    assert set(entry["workloads"]) <= set(CELLS)
    assert entry["source"] == "device_trace"
    assert entry["moves"] == spec["moves"] == "train_examples_per_s"
    assert entry["unit"] == spec["unit"] and entry["layer"] == spec["layer"]
    assert entry["better"] == "lower"
    params = spec["params"]
    assert spec["reader"] == "scope_ops"
    assert params["program"] == "worker_train_step"
    assert set(params.get("scopes", [])) <= set(profiler.DEVICE_SCOPES)
    assert params.get("phase") in (None,) + programs.PHASES
    for pattern in params.get("exclude_ops", []):
        re.compile(pattern)


def test_excluded_kernels_are_the_core_metrics_kernels():
    """`attn_proj` and `conv_proj` leave out exactly what `mla_core` /
    `gqa_core` / `window_core` and `short_conv` count."""
    attn = [re.compile(p) for p in
            spec_of("attn_proj_ms_per_step")["params"]["exclude_ops"]]
    conv = [re.compile(p) for p in
            spec_of("conv_proj_ms_per_step")["params"]["exclude_ops"]]
    for name in ("causal_attention_fwd.6", "causal_attention_dkv",
                 "window_attention_dq.12", "short_conv_bwd.3",
                 "short_conv_fwd"):
        text = f"%{name} = bf16[8] custom-call(%a)"
        counted = []
        for metric in ("mla_core_ms_per_step", "gqa_core_ms_per_step",
                       "window_core_ms_per_step", "short_conv_ms_per_step"):
            include = spec_of(metric)["params"]["include"]
            counted.append(any(re.search(p, text) for p in include))
        assert any(counted)
        ours = conv if name.startswith("short") else attn
        assert any(p.search(text) for p in ours), name
    assert not any(
        p.search("%fusion.5 = bf16[8] fusion(%causal_attention_fwd.6)")
        for p in attn + conv
    )
