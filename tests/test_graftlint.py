"""graftlint (scripts/graftlint/): the unified static-analysis suite.

Per rule: a positive fixture, a suppressed fixture, and an allowlisted
fixture.  Framework: finding format, suppression validation, rule
selection, syntax errors, text/JSON CLI output.  Acceptance demos (the
ISSUE's exit-1 criteria): deleting a fault-point row from
docs/ROBUSTNESS.md, adding a naked `time.time()` to master/policy.py,
and adding an unlocked write to a lock-guarded attribute each produce a
`path:line: RULE-ID` finding.  Finally the tier-1 gate: the whole repo
is clean under `python -m scripts.graftlint`.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from scripts.graftlint import core  # noqa: E402
from scripts.graftlint.core import Project, check_source  # noqa: E402
from scripts.graftlint import (  # noqa: E402
    rules_boundary,
    rules_clock,
    rules_donation,
    rules_drift,
    rules_ledger,
    rules_locks,
    rules_metrics,
    rules_programs,
    rules_quant,
    rules_retries,
)

ALL_IDS = {
    "GL-BOUNDARY", "GL-CLOCK", "GL-DONATE", "GL-DRIFT",
    "GL-LEDGER", "GL-LOCK", "GL-METRIC", "GL-PROGRAM", "GL-QUANT",
    "GL-RETRY",
}


def _ids(findings):
    return [f.rule for f in findings]


# ---- framework ----------------------------------------------------------


def test_registry_has_all_ten_rules():
    assert set(core.all_rules()) == ALL_IDS


def test_finding_format_is_path_line_rule_message():
    f = core.Finding("pkg/mod.py", 12, "GL-RETRY", "no")
    assert f.format() == "pkg/mod.py:12: GL-RETRY no"


def test_syntax_error_is_a_finding_not_a_crash():
    found = check_source("def broken(:\n", "elasticdl_tpu/x.py")
    assert _ids(found) == [core.SYNTAX_ID]


def test_unknown_suppression_token_is_a_finding():
    found = check_source(
        "x = 1  # graftlint: disable=GL-NOPE\n", "elasticdl_tpu/x.py"
    )
    assert _ids(found) == [core.SUPPRESS_ID]
    assert "GL-NOPE" in found[0].message


def test_known_suppression_token_is_not_a_finding():
    found = check_source(
        "x = 1  # graftlint: disable=GL-RETRY\n", "elasticdl_tpu/x.py"
    )
    assert not found


def test_unknown_rule_id_in_select_is_a_usage_error():
    with pytest.raises(SystemExit):
        core.run_project(Project(REPO, []), select=["GL-BOGUS"])


# ---- GL-RETRY -----------------------------------------------------------

NAKED_RETRY = (
    "import time\n"
    "while True:\n"
    "    try:\n"
    "        do_rpc()\n"
    "    except Exception:\n"
    "        time.sleep(2)\n"
)


def test_retry_positive():
    found = check_source(NAKED_RETRY, "elasticdl_tpu/worker/x.py",
                         [rules_retries.RetryRule()])
    assert _ids(found) == ["GL-RETRY"]
    assert found[0].line == 6


def test_retry_suppressed():
    src = NAKED_RETRY.replace(
        "time.sleep(2)", "time.sleep(2)  # graftlint: disable=GL-RETRY"
    )
    assert not check_source(src, "elasticdl_tpu/worker/x.py",
                            [rules_retries.RetryRule()])


def test_retry_allowlisted_module():
    rule = rules_retries.RetryRule(
        allowlist=frozenset({"elasticdl_tpu/worker/x.py"})
    )
    assert not check_source(NAKED_RETRY, "elasticdl_tpu/worker/x.py",
                            [rule])


def test_retry_router_fanout_positive():
    src = (
        "class FooRouter:\n"
        "    def predict(self, req):\n"
        "        return self._pick().predict(req)\n"
    )
    found = check_source(src, "elasticdl_tpu/proto/x.py",
                         [rules_retries.RetryRule()])
    assert _ids(found) == ["GL-RETRY"]


# ---- GL-LEDGER ----------------------------------------------------------

FIRE_AND_FORGET_ARM = (
    "def offer(tm, window):\n"
    "    tm.arm_window(window.name, window.records, 4,\n"
    "                  window_id=window.window_id)\n"
)


def test_ledger_bare_arm_is_flagged():
    found = check_source(FIRE_AND_FORGET_ARM, "elasticdl_tpu/online/x.py",
                         [rules_ledger.LedgerRule()])
    assert _ids(found) == ["GL-LEDGER"]
    assert found[0].line == 2
    assert "arm_window" in found[0].message


def test_ledger_bare_release_is_flagged():
    src = "def done(tm, wid):\n    tm.release_window(wid)\n"
    found = check_source(src, "elasticdl_tpu/online/x.py",
                         [rules_ledger.LedgerRule()])
    assert _ids(found) == ["GL-LEDGER"]
    assert "release_window" in found[0].message


def test_ledger_consumed_ack_passes():
    src = (
        "def offer(tm, reader, window):\n"
        "    n = tm.arm_window(window.name, window.records, 4)\n"
        "    if n and not reader.release_window(window.name):\n"
        "        raise RuntimeError('unacked release')\n"
        "    return n\n"
    )
    assert not check_source(src, "elasticdl_tpu/online/x.py",
                            [rules_ledger.LedgerRule()])


def test_ledger_suppressed():
    src = FIRE_AND_FORGET_ARM.replace(
        "tm.arm_window(window.name, window.records, 4,",
        "tm.arm_window(window.name, window.records, 4,"
        "  # graftlint: disable=GL-LEDGER",
    )
    assert not check_source(src, "elasticdl_tpu/online/x.py",
                            [rules_ledger.LedgerRule()])


# ---- GL-BOUNDARY --------------------------------------------------------

DEVICE_PUT = "import jax\nx = jax.device_put(batch)\n"


def test_boundary_positive_on_host_plane():
    found = check_source(DEVICE_PUT, "elasticdl_tpu/data/x.py",
                         [rules_boundary.BoundaryRule()])
    assert _ids(found) == ["GL-BOUNDARY"]


def test_boundary_not_scoped_outside_host_plane():
    assert not check_source(DEVICE_PUT, "elasticdl_tpu/worker/trainer.py",
                            [rules_boundary.BoundaryRule()])


def test_boundary_suppressed():
    src = (
        "import jax\n"
        "x = jax.device_put(b)  # graftlint: disable=GL-BOUNDARY\n"
    )
    assert not check_source(src, "elasticdl_tpu/data/x.py",
                            [rules_boundary.BoundaryRule()])


def test_boundary_allowlisted_file():
    rule = rules_boundary.BoundaryRule(
        allowlist=frozenset({"elasticdl_tpu/data/x.py"})
    )
    assert not check_source(DEVICE_PUT, "elasticdl_tpu/data/x.py", [rule])


def test_boundary_covers_store_package():
    # the tiered store's host tier runs on producer/worker threads, so
    # device APIs there are findings exactly like the data plane
    src = "import jax\nrows = jax.device_get(table)\n"
    found = check_source(src, "elasticdl_tpu/store/host_tier.py",
                         [rules_boundary.BoundaryRule()])
    assert _ids(found) == ["GL-BOUNDARY"]


def test_boundary_store_staging_seam_allowlisted():
    # store/device.py is the one sanctioned seam (registration allowlist)
    src = "import jax\nrows = jax.device_get(table)\n"
    rule = rules_boundary.BoundaryRule(
        allowlist=frozenset({"elasticdl_tpu/store/device.py"})
    )
    assert not check_source(src, "elasticdl_tpu/store/device.py", [rule])
    # but the same source anywhere else under store/ still fires
    assert check_source(src, "elasticdl_tpu/store/tiered.py", [rule])


# ---- GL-METRIC ----------------------------------------------------------


def test_metric_bad_name_positive():
    found = check_source(
        "registry.counter('frobnicator_x_total', 'h')\n",
        "elasticdl_tpu/worker/x.py", [rules_metrics.MetricRule()],
    )
    assert _ids(found) == ["GL-METRIC"]


def test_metric_only_scoped_to_elasticdl_tpu():
    assert not check_source(
        "registry.counter('frobnicator_x_total', 'h')\n",
        "scripts/whatever.py", [rules_metrics.MetricRule()],
    )


def test_metric_suppressed():
    src = (
        "registry.counter('frobnicator_x_total', 'h')"
        "  # graftlint: disable=GL-METRIC\n"
    )
    assert not check_source(src, "elasticdl_tpu/worker/x.py",
                            [rules_metrics.MetricRule()])


def test_metric_shadow_counter_allowlisted():
    rel = "elasticdl_tpu/serving/batcher.py"  # INSTRUMENTED member
    src = "class B:\n    def reset(self):\n        self.x_count = 0\n"
    assert check_source(src, rel, [rules_metrics.MetricRule()])
    rule = rules_metrics.MetricRule(
        shadow_allowlist=frozenset({(rel, "x_count")})
    )
    assert not check_source(src, rel, [rule])


def test_metric_stringly_event_positive():
    found = check_source(
        "events.emit('task_reported', task_id=1)\n",
        "elasticdl_tpu/worker/x.py", [rules_metrics.MetricRule()],
    )
    assert _ids(found) == ["GL-METRIC"]


# ---- GL-DONATE ----------------------------------------------------------

DONATING = "jit_step = jax.jit(step, donate_argnums=(0,))\n"


def test_donate_positive_asarray_over_state():
    src = DONATING + "snap = np.asarray(state.params)\n"
    found = check_source(src, "elasticdl_tpu/worker/x.py",
                         [rules_donation.DonationRule()])
    assert _ids(found) == ["GL-DONATE"]
    assert "host_snapshot" in found[0].message


def test_donate_positive_tree_mapped_asarray():
    src = DONATING + "snap = jax.tree.map(np.asarray, state)\n"
    assert check_source(src, "elasticdl_tpu/worker/x.py",
                        [rules_donation.DonationRule()])


def test_donate_requires_donating_module():
    # same aliasing, but no donate_argnums anywhere: not flagged
    src = "snap = np.asarray(state.params)\n"
    assert not check_source(src, "elasticdl_tpu/worker/x.py",
                            [rules_donation.DonationRule()])


def test_donate_suppressed():
    src = DONATING + (
        "snap = np.asarray(state.params)"
        "  # graftlint: disable=GL-DONATE\n"
    )
    assert not check_source(src, "elasticdl_tpu/worker/x.py",
                            [rules_donation.DonationRule()])


def test_donate_allowlisted_identifier():
    # the allowlist keys on the state token the finding names ('params')
    rule = rules_donation.DonationRule(
        allowlist=frozenset({("elasticdl_tpu/worker/x.py", "params")})
    )
    src = DONATING + "snap = np.asarray(state.params)\n"
    assert not check_source(src, "elasticdl_tpu/worker/x.py", [rule])


# ---- GL-CLOCK -----------------------------------------------------------

CLOCK_MODULE = (
    "import time\n"
    "def loop(clock=time.time):\n"
    "    t0 = clock()\n"
)


def test_clock_positive_naked_read():
    src = CLOCK_MODULE + "def helper():\n    return time.time()\n"
    found = check_source(src, "elasticdl_tpu/master/x.py",
                         [rules_clock.ClockRule()])
    assert _ids(found) == ["GL-CLOCK"]


def test_clock_default_factory_reference_is_exempt():
    # the declaration itself (and a lambda default) is the injection
    # point, not a bypass
    src = (
        "import time\n"
        "def loop(clock=lambda: time.time()):\n"
        "    t0 = clock()\n"
    )
    assert not check_source(src, "elasticdl_tpu/master/x.py",
                            [rules_clock.ClockRule()])


def test_clock_only_fires_in_clock_declaring_modules():
    src = "import time\ndef helper():\n    return time.time()\n"
    assert not check_source(src, "elasticdl_tpu/master/x.py",
                            [rules_clock.ClockRule()])


def test_clock_suppressed():
    src = CLOCK_MODULE + (
        "def helper():\n"
        "    return time.time()  # graftlint: disable=GL-CLOCK\n"
    )
    assert not check_source(src, "elasticdl_tpu/master/x.py",
                            [rules_clock.ClockRule()])


def test_clock_allowlisted_function():
    rule = rules_clock.ClockRule(
        allowlist=frozenset({("elasticdl_tpu/master/x.py", "helper")})
    )
    src = CLOCK_MODULE + "def helper():\n    return time.time()\n"
    assert not check_source(src, "elasticdl_tpu/master/x.py", [rule])


# ---- GL-LOCK ------------------------------------------------------------

LOCKED_CLASS = (
    "import threading\n"
    "class Box:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self._n = 0\n"
    "    def bump(self):\n"
    "        with self._lock:\n"
    "            self._n += 1\n"
)


def test_lock_positive_unlocked_read():
    src = LOCKED_CLASS + "    def peek(self):\n        return self._n\n"
    found = check_source(src, "elasticdl_tpu/master/x.py",
                         [rules_locks.LockRule()])
    assert _ids(found) == ["GL-LOCK"]
    assert "Box._n" in found[0].message


def test_lock_init_writes_do_not_count():
    # construction-time writes never make an attr "guarded"
    assert not check_source(LOCKED_CLASS, "elasticdl_tpu/master/x.py",
                            [rules_locks.LockRule()])


def test_lock_locked_suffix_convention():
    src = LOCKED_CLASS + (
        "    def _drain_locked(self):\n"
        "        self._n = 0\n"
    )
    assert not check_source(src, "elasticdl_tpu/master/x.py",
                            [rules_locks.LockRule()])


def test_lock_private_helper_fixpoint():
    # _flush is only ever called under the lock, so its bare write is
    # effectively locked (the ModelOwner._maybe_checkpoint shape)
    src = LOCKED_CLASS + (
        "    def drain(self):\n"
        "        with self._lock:\n"
        "            self._flush()\n"
        "    def _flush(self):\n"
        "        self._n = 0\n"
    )
    assert not check_source(src, "elasticdl_tpu/master/x.py",
                            [rules_locks.LockRule()])


def test_lock_suppressed():
    src = LOCKED_CLASS + (
        "    def peek(self):\n"
        "        return self._n  # graftlint: disable=GL-LOCK\n"
    )
    assert not check_source(src, "elasticdl_tpu/master/x.py",
                            [rules_locks.LockRule()])


def test_lock_allowlisted_class_attr():
    rule = rules_locks.LockRule(
        allowlist={("Box", "_n"): "GIL-atomic telemetry read"}
    )
    src = LOCKED_CLASS + "    def peek(self):\n        return self._n\n"
    assert not check_source(src, "elasticdl_tpu/master/x.py", [rule])


# ---- GL-DRIFT -----------------------------------------------------------


def _drift_project(doc_overrides=None):
    # the zoo modules declare the gauges of what their layers sow
    return core.build_project(
        REPO, ["elasticdl_tpu", "model_zoo"], doc_overrides=doc_overrides
    )


def test_drift_clean_on_real_tree():
    project = _drift_project()
    found = list(rules_drift.DriftRule().check_project(project))
    assert found == []


def test_drift_detects_deleted_fault_point_row():
    # acceptance demo: drop the `pod.watch` row from the runbook table
    with open(os.path.join(REPO, "docs", "ROBUSTNESS.md")) as fh:
        text = fh.read()
    lines = [l for l in text.splitlines() if "`pod.watch`" not in l]
    project = _drift_project(
        doc_overrides={"docs/ROBUSTNESS.md": "\n".join(lines)}
    )
    found = list(rules_drift.DriftRule().check_project(project))
    assert any(
        f.rule == "GL-DRIFT" and "pod.watch" in f.message
        and f.path == "elasticdl_tpu/common/faults.py"
        for f in found
    ), found


def test_drift_detects_stale_doc_metric_and_event():
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md")) as fh:
        text = fh.read()
    text = text.replace(
        "| `worker_train_steps_total` | counter | minibatch steps |",
        "| `worker_vanished_total` | counter | gone |",
    ).replace("| `task_claimed` |", "| `task_grabbed` |")
    project = _drift_project(
        doc_overrides={"docs/OBSERVABILITY.md": text}
    )
    messages = [
        f.message
        for f in rules_drift.DriftRule().check_project(project)
    ]
    # stale doc rows flagged at the doc, missing code entries at the code
    assert any("worker_vanished_total" in m for m in messages)
    assert any("worker_train_steps_total" in m for m in messages)
    assert any("task_grabbed" in m for m in messages)
    assert any("task_claimed" in m for m in messages)


def test_drift_flags_abbreviated_catalogue_rows():
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md")) as fh:
        text = fh.read()
    text = text.replace(
        "| `master_tasks_failed_total` | counter | tasks reported failed |",
        "| `_failed_total` | counter | tasks reported failed |",
    )
    project = _drift_project(
        doc_overrides={"docs/OBSERVABILITY.md": text}
    )
    found = list(rules_drift.DriftRule().check_project(project))
    assert any("abbreviated" in f.message for f in found), found


def test_drift_detects_slo_vocabulary_drift():
    # rename a row in the SLO table: the stale doc name flags at the doc
    # line, the now-undocumented SLO_* constant flags at common/slo.py
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md")) as fh:
        text = fh.read()
    text = text.replace("| `fleet_skew` | gauge |", "| `fleet_skue` | gauge |")
    project = _drift_project(
        doc_overrides={"docs/OBSERVABILITY.md": text}
    )
    found = list(rules_drift.DriftRule().check_project(project))
    assert any(
        "fleet_skue" in f.message and f.path == "docs/OBSERVABILITY.md"
        for f in found
    ), found
    assert any(
        "fleet_skew" in f.message
        and f.path == "elasticdl_tpu/common/slo.py"
        for f in found
    ), found


def test_drift_flags_missing_slo_table():
    # docs without any `| slo |` table: one finding, not silence — the
    # vocabulary contract needs the table to exist at all
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md")) as fh:
        text = fh.read()
    text = text.replace("| slo | kind | objective | evidence series |",
                        "| objective | kind | evidence series |")
    project = _drift_project(
        doc_overrides={"docs/OBSERVABILITY.md": text}
    )
    found = list(rules_drift.DriftRule().check_project(project))
    assert any("no SLO table" in f.message for f in found), found


def test_drift_skipped_on_partial_scan():
    # scanning one file must not compare the full docs against an
    # almost-empty code inventory
    project = core.build_project(
        REPO, [os.path.join("elasticdl_tpu", "worker", "worker.py")]
    )
    assert not list(rules_drift.DriftRule().check_project(project))


# ---- GL-QUANT -----------------------------------------------------------


def test_quant_positive_binop_on_plane_key():
    src = "deq = planes['q8'] * 0.01\n"
    found = check_source(src, "elasticdl_tpu/serving/x.py",
                         [rules_quant.QuantRule()])
    assert _ids(found) == ["GL-QUANT"]
    assert "dequantize_rows" in found[0].message


def test_quant_positive_astype_and_compare():
    src = (
        "a = q8.astype(jnp.float32)\n"
        "hot = q8_plane > 0\n"
    )
    found = check_source(src, "elasticdl_tpu/worker/x.py",
                         [rules_quant.QuantRule()])
    assert _ids(found) == ["GL-QUANT", "GL-QUANT"]


def test_quant_arena_module_is_exempt():
    # the one module allowed to do plane math
    src = "deq = planes['q8'] * scale\n"
    assert not check_source(src, "elasticdl_tpu/layers/arena.py",
                            [rules_quant.QuantRule()])


def test_quant_metadata_access_is_not_consumption():
    # checkpoint code compares plane shapes/dtypes legitimately
    src = (
        "ok = planes['q8'].shape[0] == rows\n"
        "bad_dtype = planes['q8'].dtype != jnp.int8\n"
    )
    assert not check_source(src, "elasticdl_tpu/common/x.py",
                            [rules_quant.QuantRule()])


def test_quant_suppressed():
    src = "deq = q8 * 0.01  # graftlint: disable=GL-QUANT\n"
    assert not check_source(src, "elasticdl_tpu/worker/x.py",
                            [rules_quant.QuantRule()])


def test_quant_allowlisted_token():
    rule = rules_quant.QuantRule(
        allowlist=frozenset({("elasticdl_tpu/worker/x.py", "q8")})
    )
    src = "deq = q8 * 0.01\n"
    assert not check_source(src, "elasticdl_tpu/worker/x.py", [rule])


def test_quant_store_device_seam_is_exempt():
    # ISSUE 18: the device gather/scatter seam addresses raw planes
    # (slot indexing inside dequantize call arguments) — exempt by
    # module, like the arena itself
    src = "out = dequantize_rows(planes['q8'][idx], scales[idx]) + c\n"
    assert "elasticdl_tpu/store/device.py" \
        in rules_quant.STORE_ALLOWED_MODULES
    assert not check_source(src, "elasticdl_tpu/store/device.py",
                            [rules_quant.QuantRule()])


def test_quant_other_store_modules_still_covered():
    # the exemption is per-module, not for store/ wholesale: the same
    # source in tiered.py (or any new store module) still fires
    src = "out = dequantize_rows(planes['q8'][idx], scales[idx]) + c\n"
    found = check_source(src, "elasticdl_tpu/store/tiered.py",
                         [rules_quant.QuantRule()])
    assert _ids(found) == ["GL-QUANT"]


# ---- GL-PROGRAM ---------------------------------------------------------

NAKED_JIT = "import jax\nstep = jax.jit(fn, donate_argnums=(0,))\n"


def test_program_positive_direct_jit():
    found = check_source(NAKED_JIT, "elasticdl_tpu/worker/x.py",
                         [rules_programs.ProgramsRule()])
    assert _ids(found) == ["GL-PROGRAM"]
    assert "registered_jit" in found[0].message


def test_program_positive_jit_decorator_and_alias():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x\n"
        "sneaky = jax.jit\n"
    )
    found = check_source(src, "elasticdl_tpu/store/x.py",
                         [rules_programs.ProgramsRule()])
    assert _ids(found) == ["GL-PROGRAM", "GL-PROGRAM"]


def test_program_positive_from_import_and_argful_lower():
    src = (
        "from jax import jit\n"
        "cost = step.lower(state, batch).compile().cost_analysis()\n"
    )
    found = check_source(src, "elasticdl_tpu/worker/x.py",
                         [rules_programs.ProgramsRule()])
    assert _ids(found) == ["GL-PROGRAM", "GL-PROGRAM"]
    assert any("aot_compile" in f.message for f in found)


def test_program_zero_arg_lower_is_str_lower():
    # `name.lower()` is string casing, not AOT lowering
    src = "key = program_name.lower()\n"
    assert not check_source(src, "elasticdl_tpu/worker/x.py",
                            [rules_programs.ProgramsRule()])


def test_program_registry_module_is_allowlisted():
    assert "elasticdl_tpu/common/programs.py" \
        in rules_programs.DEFAULT_ALLOWLIST
    assert not check_source(
        NAKED_JIT, "elasticdl_tpu/common/programs.py",
        [rules_programs.ProgramsRule()],
    )


def test_program_scoped_to_elasticdl_tpu():
    # model_zoo / scripts are free to jit directly (bench and zoo
    # models are not serving/training entry points)
    assert not check_source(NAKED_JIT, "model_zoo/deepfm/x.py",
                            [rules_programs.ProgramsRule()])


def test_program_suppressed():
    src = NAKED_JIT.replace(
        "jax.jit(fn, donate_argnums=(0,))",
        "jax.jit(fn)  # graftlint: disable=GL-PROGRAM",
    )
    assert not check_source(src, "elasticdl_tpu/worker/x.py",
                            [rules_programs.ProgramsRule()])


# ---- acceptance demos (ISSUE exit-1 criteria) ---------------------------


def test_acceptance_naked_time_in_policy_module():
    # adding a naked time.time() to master/policy.py fails the gate
    with open(
        os.path.join(REPO, "elasticdl_tpu", "master", "policy.py")
    ) as fh:
        src = fh.read()
    src += "\ndef _sneaky_deadline():\n    return time.time() + 5\n"
    found = check_source(src, "elasticdl_tpu/master/policy.py",
                         [rules_clock.ClockRule()])
    assert _ids(found) == ["GL-CLOCK"]
    line = found[0].line
    assert src.splitlines()[line - 1].strip() == "return time.time() + 5"


def test_acceptance_unlocked_write_to_guarded_attr():
    # adding an unlocked write to a lock-guarded attribute fails the gate
    src = LOCKED_CLASS + (
        "    def reset(self):\n"
        "        self._n = 0\n"
    )
    found = check_source(src, "elasticdl_tpu/master/x.py",
                         [rules_locks.LockRule()])
    assert [(f.rule, f.line) for f in found] == [("GL-LOCK", 10)]


# ---- CLI ----------------------------------------------------------------


def test_cli_clean_exit_and_violation_exit(tmp_path):
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    proc = subprocess.run(
        [sys.executable, "-m", "scripts.graftlint", str(clean)],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr

    dirty = tmp_path / "dirty.py"
    dirty.write_text(NAKED_RETRY)
    proc = subprocess.run(
        [sys.executable, "-m", "scripts.graftlint", "--select",
         "GL-RETRY", str(dirty)],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1
    # findings are `path:line: RULE-ID message`
    assert f"{dirty}:6: GL-RETRY" in proc.stdout


def test_cli_json_output(tmp_path):
    dirty = tmp_path / "dirty.py"
    dirty.write_text(NAKED_RETRY)
    proc = subprocess.run(
        [sys.executable, "-m", "scripts.graftlint", "--select",
         "GL-RETRY", "--json", str(dirty)],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    payload = json.loads(proc.stdout)
    assert payload["count"] == 1
    assert payload["findings"][0]["rule"] == "GL-RETRY"
    assert payload["findings"][0]["line"] == 6


def test_cli_list_rules():
    proc = subprocess.run(
        [sys.executable, "-m", "scripts.graftlint", "--list-rules"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0
    for rule_id in ALL_IDS:
        assert rule_id in proc.stdout


# ---- the tier-1 gate ----------------------------------------------------


def test_whole_repo_is_clean():
    proc = subprocess.run(
        [sys.executable, "-m", "scripts.graftlint"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, (
        f"graftlint findings:\n{proc.stdout}{proc.stderr}"
    )


def test_serving_scale_literal_vocab_clean():
    src = (
        "from elasticdl_tpu.common import events\n"
        "events.emit(events.SERVING_SCALE, action='scale_up',\n"
        "            reason='burn_rate', tick=3)\n"
    )
    assert not check_source(src, "elasticdl_tpu/master/x.py",
                            [rules_metrics.MetricRule()])


def test_serving_scale_missing_field_positive():
    src = (
        "from elasticdl_tpu.common import events\n"
        "events.emit(events.SERVING_SCALE, action='scale_up', tick=3)\n"
    )
    found = check_source(src, "elasticdl_tpu/master/x.py",
                         [rules_metrics.MetricRule()])
    assert _ids(found) == ["GL-METRIC"]
    assert "must carry reason=" in found[0].message


def test_serving_scale_computed_value_positive():
    src = (
        "from elasticdl_tpu.common import events\n"
        "events.emit(events.SERVING_SCALE, action=chosen,\n"
        "            reason='burn_rate')\n"
    )
    found = check_source(src, "elasticdl_tpu/master/x.py",
                         [rules_metrics.MetricRule()])
    assert _ids(found) == ["GL-METRIC"]
    assert "string literal" in found[0].message


def test_serving_scale_out_of_vocabulary_positive():
    src = (
        "from elasticdl_tpu.common import events\n"
        "events.emit(events.SERVING_SCALE, action='scale_up',\n"
        "            reason='vibes')\n"
    )
    found = check_source(src, "elasticdl_tpu/master/x.py",
                         [rules_metrics.MetricRule()])
    assert _ids(found) == ["GL-METRIC"]
    assert "not in the closed vocabulary" in found[0].message


def test_serving_scale_suppressed():
    src = (
        "from elasticdl_tpu.common import events\n"
        "events.emit(events.SERVING_SCALE, action='scale_up')"
        "  # graftlint: disable=GL-METRIC\n"
    )
    assert not check_source(src, "elasticdl_tpu/master/x.py",
                            [rules_metrics.MetricRule()])
