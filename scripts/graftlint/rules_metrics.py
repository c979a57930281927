"""GL-METRIC: literal `subsystem_name_unit` metric names, no shadow
counters, closed span-event and policy-decision vocabularies.

Migrated from scripts/check_metric_names.py (now a shim).  Four
patterns over elasticdl_tpu/ and model_zoo/ (METRIC_ROOTS):

1. **Name discipline.**  Every metric-creation call
   (`*.counter(...)`, `*.gauge(...)`, `*.gauge_fn(...)`,
   `*.histogram(...)`) must pass its name as a STRING LITERAL that
   satisfies `common.metrics.validate_metric_name` — a known subsystem
   prefix and an allowed unit suffix (the units vocabulary lives in
   `common/metrics.py` `ALLOWED_UNIT_SUFFIXES`; the validator is
   imported, so the lint can never drift from the runtime rules).
   Literal-only matters: a computed name defeats both this lint and the
   docs/OBSERVABILITY.md catalogue that GL-DRIFT cross-checks.

2. **No shadow counters.**  In modules already converted to the unified
   registry (INSTRUMENTED below), a fresh `self.<x> = 0` where `<x>`
   looks like a counter, or a `collections.Counter()` construction, is
   flagged — those are exactly the private tallies the registry
   replaced.  Legitimate non-metric state is allowlisted per
   (module, attribute).

3. **Span-event vocabulary.**  `events.emit(...)` must name its event
   via an `events.<CONSTANT>` attribute, never a string literal — the
   constants in common/events.py are the single source of truth the
   trace exporter (client/trace.py) and docs/OBSERVABILITY.md key on.

4. **Policy-decision fields.**  Every `emit(events.POLICY_DECISION,
   ...)` must carry `action=`/`reason=` string literals drawn from the
   closed POLICY_ACTIONS / POLICY_REASONS vocabularies.  The same
   contract covers `emit(events.SERVING_SCALE, ...)` against
   SERVING_SCALE_ACTIONS / SERVING_SCALE_REASONS — the serving
   autoscaler's decisions are dashboards' evidence exactly like the
   trainer policy's.

5. **Request-span fields.**  Every `emit(events.PREDICT_SPAN, ...)`
   must carry a `request_id=` kwarg (a span an operator cannot
   correlate by request id is forensic noise), its `reason=` must be a
   string literal from SPAN_REASONS, and a `phase=` kwarg, if present,
   must be a string literal from SPAN_PHASES — the same closed sets
   the `serving_request_phase_seconds{phase}` histogram and
   docs/OBSERVABILITY.md draw from.

6. **Window-lineage fields.**  Every `emit(events.WINDOW_SPAN, ...)`
   must carry a `window_id=` kwarg (a lineage stamp the join cannot key
   by window is unattributable), a `phase=` string literal from
   WINDOW_PHASES, and a `reason=`, if present, that is a string literal
   from WINDOW_REASONS — the closed sets the
   `master_window_phase_seconds{phase}` histogram, common/lineage.py's
   join, and docs/OBSERVABILITY.md "Window lineage" draw from.  The
   train-path mirror of pattern 5.
"""

from __future__ import annotations

import ast
import re
import sys
from typing import Dict, FrozenSet, Optional, Tuple

from scripts.graftlint.core import (
    REPO,
    Finding,
    ParsedFile,
    Rule,
    register,
)

if REPO not in sys.path:  # the shared validators live in the runtime
    sys.path.insert(0, REPO)

from elasticdl_tpu.common.events import (  # noqa: E402
    POLICY_ACTIONS,
    POLICY_REASONS,
    SERVING_SCALE_ACTIONS,
    SERVING_SCALE_REASONS,
    SPAN_PHASES,
    SPAN_REASONS,
    WINDOW_PHASES,
    WINDOW_REASONS,
)
from elasticdl_tpu.common.metrics import validate_metric_name  # noqa: E402

RULE_ID = "GL-METRIC"

CREATION_METHODS = {"counter", "gauge", "gauge_fn", "histogram"}

# Where metrics are created: the framework, and the zoo modules that
# declare the gauge of a value their layers sow
# (elasticdl_tpu/layers/step_metrics.py).
METRIC_ROOTS = ("elasticdl_tpu/", "model_zoo/")

# Modules converted to registry-backed counters: shadow-counter rule on.
INSTRUMENTED = frozenset({
    "elasticdl_tpu/common/resilience.py",
    "elasticdl_tpu/common/faults.py",
    "elasticdl_tpu/serving/batcher.py",
    "elasticdl_tpu/serving/engine.py",
    "elasticdl_tpu/serving/reloader.py",
    "elasticdl_tpu/master/task_manager.py",
    "elasticdl_tpu/master/pod_manager.py",
    "elasticdl_tpu/master/recovery.py",
    "elasticdl_tpu/worker/worker.py",
    "elasticdl_tpu/data/wire.py",
    "elasticdl_tpu/proto/service.py",
})

_SHADOW_ATTR = re.compile(r"(_count$|_total$|count$|_seen$)")

# (module, attribute) pairs that look like counters but are not metrics.
DEFAULT_ALLOWLIST: FrozenSet[Tuple[str, str]] = frozenset({
    # sticky pad caps / last-batch sizes: shapes, not tallies
    ("elasticdl_tpu/data/wire.py", "unique_cap"),
    ("elasticdl_tpu/data/wire.py", "exc_cap"),
})

# events.py defines the vocabulary constants, so its own string
# assignments are exempt from pattern 3.
EVENTS_MODULE = "elasticdl_tpu/common/events.py"


def literal_metric_name(call: ast.Call) -> Optional[str]:
    """The metric name when passed as a literal; None otherwise.  Shared
    with GL-DRIFT's code-side catalogue extraction."""
    args = call.args
    if args and isinstance(args[0], ast.Constant) \
            and isinstance(args[0].value, str):
        return args[0].value
    for kw in call.keywords:
        if kw.arg == "name" and isinstance(kw.value, ast.Constant) \
                and isinstance(kw.value.value, str):
            return kw.value.value
    return None


def iter_metric_creations(tree: ast.AST):
    """Yield (call, method, literal_name_or_None) for every metric
    creation call in `tree`."""
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in CREATION_METHODS):
            continue
        if not (node.args or node.keywords):
            continue  # zero-arg call: not a metric creation
        yield node, node.func.attr, literal_metric_name(node)


def find_bad_metric_names(tree: ast.AST):
    """Yield (lineno, message) for creation calls with computed or
    rule-breaking names.  (Public: the check_metric_names.py shim
    re-exports this.)"""
    for node, method, name in iter_metric_creations(tree):
        if name is None:
            yield (
                node.lineno,
                f"{method}(...) metric name must be a string "
                "literal (computed names defeat this lint and the "
                "metric catalogue)",
            )
            continue
        error = validate_metric_name(name)
        if error:
            yield (node.lineno, f"metric {name!r}: {error}")


def find_stringly_events(tree: ast.AST):
    """Yield (lineno, message) for `emit("...")` calls that bypass the
    common/events.py constant vocabulary."""
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "emit"
                and node.args):
            continue
        first = node.args[0]
        if isinstance(first, ast.Constant) and isinstance(first.value, str):
            yield (
                node.lineno,
                f"emit({first.value!r}, ...): pass an events.<CONSTANT> "
                "from common/events.py, not a string literal — the "
                "vocabulary is what the trace exporter and "
                "docs/OBSERVABILITY.md key on",
            )


def find_unlabeled_policy_decisions(tree: ast.AST):
    """Yield (lineno, message) for `emit(events.POLICY_DECISION, ...)`
    calls missing `action=`/`reason=` string literals from the closed
    vocabularies in common/events.py."""
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "emit"
                and node.args):
            continue
        first = node.args[0]
        if not (isinstance(first, ast.Attribute)
                and first.attr == "POLICY_DECISION"):
            continue
        kwargs = {kw.arg: kw.value for kw in node.keywords if kw.arg}
        for field, vocab in (
            ("action", POLICY_ACTIONS),
            ("reason", POLICY_REASONS),
        ):
            value = kwargs.get(field)
            if value is None:
                yield (
                    node.lineno,
                    "emit(events.POLICY_DECISION, ...) must carry "
                    f"{field}= — a decision without it cannot be "
                    "grepped off the event stream",
                )
            elif not (isinstance(value, ast.Constant)
                      and isinstance(value.value, str)):
                yield (
                    node.lineno,
                    f"emit(events.POLICY_DECISION, ...): {field}= must "
                    "be a string literal from the closed vocabulary in "
                    "common/events.py, not a computed value",
                )
            elif value.value not in vocab:
                yield (
                    node.lineno,
                    f"emit(events.POLICY_DECISION, ...): "
                    f"{field}={value.value!r} is not in the closed "
                    f"vocabulary {sorted(vocab)}",
                )


def find_unlabeled_serving_scales(tree: ast.AST):
    """Yield (lineno, message) for `emit(events.SERVING_SCALE, ...)`
    calls missing `action=`/`reason=` string literals from the closed
    SERVING_SCALE_ACTIONS / SERVING_SCALE_REASONS vocabularies in
    common/events.py — the serving-autoscaler mirror of
    find_unlabeled_policy_decisions."""
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "emit"
                and node.args):
            continue
        first = node.args[0]
        if not (isinstance(first, ast.Attribute)
                and first.attr == "SERVING_SCALE"):
            continue
        kwargs = {kw.arg: kw.value for kw in node.keywords if kw.arg}
        for field, vocab in (
            ("action", SERVING_SCALE_ACTIONS),
            ("reason", SERVING_SCALE_REASONS),
        ):
            value = kwargs.get(field)
            if value is None:
                yield (
                    node.lineno,
                    "emit(events.SERVING_SCALE, ...) must carry "
                    f"{field}= — a scale decision without it cannot "
                    "be grepped off the event stream",
                )
            elif not (isinstance(value, ast.Constant)
                      and isinstance(value.value, str)):
                yield (
                    node.lineno,
                    f"emit(events.SERVING_SCALE, ...): {field}= must "
                    "be a string literal from the closed vocabulary in "
                    "common/events.py, not a computed value",
                )
            elif value.value not in vocab:
                yield (
                    node.lineno,
                    f"emit(events.SERVING_SCALE, ...): "
                    f"{field}={value.value!r} is not in the closed "
                    f"vocabulary {sorted(vocab)}",
                )


def find_untraced_predict_spans(tree: ast.AST):
    """Yield (lineno, message) for `emit(events.PREDICT_SPAN, ...)`
    calls missing `request_id=`, or whose `reason=`/`phase=` fields are
    computed or outside the closed SPAN_REASONS / SPAN_PHASES
    vocabularies in common/events.py."""
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "emit"
                and node.args):
            continue
        first = node.args[0]
        if not (isinstance(first, ast.Attribute)
                and first.attr == "PREDICT_SPAN"):
            continue
        kwargs = {kw.arg: kw.value for kw in node.keywords if kw.arg}
        if "request_id" not in kwargs:
            yield (
                node.lineno,
                "emit(events.PREDICT_SPAN, ...) must carry "
                "request_id= — a span an operator cannot correlate by "
                "request id is forensic noise",
            )
        for field, vocab, required in (
            ("reason", SPAN_REASONS, True),
            ("phase", SPAN_PHASES, False),
        ):
            value = kwargs.get(field)
            if value is None:
                if required:
                    yield (
                        node.lineno,
                        "emit(events.PREDICT_SPAN, ...) must carry "
                        f"{field}= so always-capture outcomes "
                        "(error/shed/failover) are greppable off the "
                        "event stream",
                    )
            elif not (isinstance(value, ast.Constant)
                      and isinstance(value.value, str)):
                yield (
                    node.lineno,
                    f"emit(events.PREDICT_SPAN, ...): {field}= must be "
                    "a string literal from the closed vocabulary in "
                    "common/events.py, not a computed value",
                )
            elif value.value not in vocab:
                yield (
                    node.lineno,
                    f"emit(events.PREDICT_SPAN, ...): "
                    f"{field}={value.value!r} is not in the closed "
                    f"vocabulary {sorted(vocab)}",
                )


def find_untraced_window_spans(tree: ast.AST):
    """Yield (lineno, message) for `emit(events.WINDOW_SPAN, ...)`
    calls missing `window_id=`, missing a `phase=` string literal from
    WINDOW_PHASES, or whose `reason=`, if present, is computed or
    outside WINDOW_REASONS — the train-path mirror of
    find_untraced_predict_spans."""
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "emit"
                and node.args):
            continue
        first = node.args[0]
        if not (isinstance(first, ast.Attribute)
                and first.attr == "WINDOW_SPAN"):
            continue
        kwargs = {kw.arg: kw.value for kw in node.keywords if kw.arg}
        if "window_id" not in kwargs:
            yield (
                node.lineno,
                "emit(events.WINDOW_SPAN, ...) must carry window_id= — "
                "a lineage stamp the freshness join cannot key by "
                "window is unattributable",
            )
        for field, vocab, required in (
            ("phase", WINDOW_PHASES, True),
            ("reason", WINDOW_REASONS, False),
        ):
            value = kwargs.get(field)
            if value is None:
                if required:
                    yield (
                        node.lineno,
                        "emit(events.WINDOW_SPAN, ...) must carry "
                        f"{field}= so the staleness decomposition can "
                        "charge the stamp to a lineage phase",
                    )
            elif not (isinstance(value, ast.Constant)
                      and isinstance(value.value, str)):
                yield (
                    node.lineno,
                    f"emit(events.WINDOW_SPAN, ...): {field}= must be "
                    "a string literal from the closed vocabulary in "
                    "common/events.py, not a computed value",
                )
            elif value.value not in vocab:
                yield (
                    node.lineno,
                    f"emit(events.WINDOW_SPAN, ...): "
                    f"{field}={value.value!r} is not in the closed "
                    f"vocabulary {sorted(vocab)}",
                )


def find_shadow_counters(tree: ast.AST):
    """Yield (lineno, message, attr_or_None) for private tallies:
    `self.x = 0` counter-shaped attrs and collections.Counter
    constructions."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            value_is_zero = (
                isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, int)
                and not isinstance(node.value.value, bool)
                and node.value.value == 0
            )
            if not value_is_zero:
                continue
            for target in node.targets:
                if (isinstance(target, ast.Attribute)
                        and isinstance(target.value, ast.Name)
                        and target.value.id == "self"
                        and _SHADOW_ATTR.search(target.attr)):
                    yield (
                        node.lineno,
                        f"self.{target.attr} = 0 looks like a private "
                        "counter — register it on the metrics registry "
                        "instead (common/metrics.py)",
                        target.attr,
                    )
        elif isinstance(node, ast.Call):
            func = node.func
            if (isinstance(func, ast.Attribute)
                    and func.attr == "Counter"
                    and isinstance(func.value, ast.Name)
                    and func.value.id == "collections"):
                yield (
                    node.lineno,
                    "collections.Counter() in an instrumented module — "
                    "use a labeled registry counter instead",
                    None,
                )


class MetricRule(Rule):
    id = RULE_ID
    title = "metric/event naming discipline (literal names, closed vocabularies)"
    rationale = (
        "the metric catalogue and span-event vocabulary are what docs, "
        "dashboards and the trace exporter key on; computed or drifting "
        "names silently fall off every consumer"
    )

    def __init__(
        self,
        shadow_allowlist: FrozenSet[Tuple[str, str]] = DEFAULT_ALLOWLIST,
    ):
        self.shadow_allowlist = frozenset(shadow_allowlist)

    def applies(self, pf: ParsedFile) -> bool:
        return pf.rel.startswith(METRIC_ROOTS)

    def check(self, pf: ParsedFile):
        for lineno, message in find_bad_metric_names(pf.tree):
            yield Finding(pf.rel, lineno, self.id, message)
        if pf.rel != EVENTS_MODULE:
            for lineno, message in find_stringly_events(pf.tree):
                yield Finding(pf.rel, lineno, self.id, message)
        for lineno, message in find_unlabeled_policy_decisions(pf.tree):
            yield Finding(pf.rel, lineno, self.id, message)
        for lineno, message in find_unlabeled_serving_scales(pf.tree):
            yield Finding(pf.rel, lineno, self.id, message)
        for lineno, message in find_untraced_predict_spans(pf.tree):
            yield Finding(pf.rel, lineno, self.id, message)
        for lineno, message in find_untraced_window_spans(pf.tree):
            yield Finding(pf.rel, lineno, self.id, message)
        if pf.rel in INSTRUMENTED:
            for lineno, message, attr in find_shadow_counters(pf.tree):
                if attr is not None \
                        and (pf.rel, attr) in self.shadow_allowlist:
                    continue
                yield Finding(pf.rel, lineno, self.id, message)


register(MetricRule())


def collect_metric_names(tree: ast.AST) -> Dict[str, Tuple[int, str]]:
    """{literal metric name: (lineno, kind)} for one module — the
    code-side inventory GL-DRIFT checks the docs catalogue against."""
    out: Dict[str, Tuple[int, str]] = {}
    for node, method, name in iter_metric_creations(tree):
        if name is not None and name not in out:
            out[name] = (node.lineno, method)
    return out
