"""GL-DRIFT: docs and code describe the same system — checked both ways.

Three contracts, each a closed inventory on the code side and a
markdown table on the docs side:

1. **Fault points.**  The injection-point table in docs/ROBUSTNESS.md
   (the table whose header starts `| Point`) vs the `POINT_*` string
   constants in `elasticdl_tpu/common/faults.py`.  A point the chaos
   harness can fire but the runbook does not list is an operator
   surprise; a documented point the code no longer defines is a stale
   runbook.
2. **Metric catalogue.**  The tables in docs/OBSERVABILITY.md whose
   first header cell is `metric` vs every literal metric-creation name
   in `elasticdl_tpu/` and `model_zoo/` (the same extraction GL-METRIC
   validates).
   Label suffixes (`{...}`) are stripped; a documented histogram also
   covers its derived `_bucket`/`_count`/`_sum`/quantile series.
   Abbreviated rows (`` `_failed_total` `` shorthand) are themselves
   findings: a catalogue you cannot grep a full metric name in is not a
   catalogue.
3. **Span events.**  The table whose first header cell is `event` vs
   the UPPERCASE string constants in `elasticdl_tpu/common/events.py`
   (the VOCABULARY members; `ENV_*` wires are not events).
4. **SLO vocabulary.**  The table in docs/OBSERVABILITY.md whose first
   header cell is `slo` vs the `SLO_*` string constants in
   `elasticdl_tpu/common/slo.py` (the SLO_NAMES members).  An SLO the
   evaluator judges but the runbook does not explain leaves the
   on-call reading a breach alert with no objective; a documented SLO
   the code dropped is a promise nobody measures.

Doc-side findings anchor at the doc line; code-side findings anchor at
the defining assignment / creation call, so `path:line: GL-DRIFT ...`
always points at the thing to fix.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, FrozenSet, Iterable, List, Optional, Tuple

from scripts.graftlint.core import Finding, Project, Rule, register
from scripts.graftlint.rules_metrics import (
    METRIC_ROOTS,
    iter_metric_creations,
)

RULE_ID = "GL-DRIFT"

FAULTS_MODULE = "elasticdl_tpu/common/faults.py"
EVENTS_MODULE = "elasticdl_tpu/common/events.py"
SLO_MODULE = "elasticdl_tpu/common/slo.py"
ROBUSTNESS_DOC = "docs/ROBUSTNESS.md"
OBSERVABILITY_DOC = "docs/OBSERVABILITY.md"

# A documented histogram base name covers the derived series Prometheus
# renders for it.
HISTOGRAM_DERIVED = ("_bucket", "_count", "_sum", "_p50", "_p90", "_p99")

_BACKTICK_RE = re.compile(r"`([^`]+)`")
_LABELS_RE = re.compile(r"\{[^}]*\}")
_DIVIDER_RE = re.compile(r"^\|[\s\-:|]+\|$")


def iter_tables(text: str):
    """Yield (header_cells, [(lineno, first_cell), ...]) for every
    markdown pipe table in `text`.  Linenos are 1-based."""
    lines = text.splitlines()
    i = 0
    while i < len(lines):
        line = lines[i].strip()
        if (line.startswith("|") and i + 1 < len(lines)
                and _DIVIDER_RE.match(lines[i + 1].strip())):
            header = [c.strip() for c in line.strip("|").split("|")]
            rows: List[Tuple[int, str]] = []
            j = i + 2
            while j < len(lines) and lines[j].strip().startswith("|"):
                first = lines[j].strip().strip("|").split("|")[0].strip()
                rows.append((j + 1, first))
                j += 1
            yield header, rows
            i = j
        else:
            i += 1


def _first_header(header: List[str]) -> str:
    return header[0].lower() if header else ""


def doc_fault_points(text: str) -> Optional[Dict[str, int]]:
    """{point: doc line} from the injection-point table, or None when
    the table is missing."""
    for header, rows in iter_tables(text):
        if not _first_header(header).startswith("point"):
            continue
        out: Dict[str, int] = {}
        for lineno, cell in rows:
            for token in _BACKTICK_RE.findall(cell):
                out.setdefault(token, lineno)
        return out
    return None


def doc_metric_catalogue(
    text: str,
) -> Tuple[Optional[Dict[str, int]], List[Tuple[int, str]]]:
    """({full metric name: doc line} or None when no catalogue table
    exists, [(doc line, token)] abbreviated rows)."""
    found_any = False
    out: Dict[str, int] = {}
    abbreviated: List[Tuple[int, str]] = []
    for header, rows in iter_tables(text):
        if _first_header(header) != "metric":
            continue
        found_any = True
        for lineno, cell in rows:
            for token in _BACKTICK_RE.findall(cell):
                name = _LABELS_RE.sub("", token).strip()
                if not name:
                    continue
                if name.startswith("_"):
                    abbreviated.append((lineno, token))
                else:
                    out.setdefault(name, lineno)
    return (out if found_any else None), abbreviated


def doc_span_events(text: str) -> Optional[Dict[str, int]]:
    """{event name: doc line} from the span-event table, or None when
    the table is missing."""
    for header, rows in iter_tables(text):
        if _first_header(header) != "event":
            continue
        out: Dict[str, int] = {}
        for lineno, cell in rows:
            for token in _BACKTICK_RE.findall(cell):
                out.setdefault(token, lineno)
        return out
    return None


def doc_slo_vocabulary(text: str) -> Optional[Dict[str, int]]:
    """{slo name: doc line} from the SLO table, or None when the table
    is missing."""
    for header, rows in iter_tables(text):
        if _first_header(header) != "slo":
            continue
        out: Dict[str, int] = {}
        for lineno, cell in rows:
            for token in _BACKTICK_RE.findall(cell):
                out.setdefault(token, lineno)
        return out
    return None


def _string_constants(
    tree: ast.AST, name_filter,
) -> Dict[str, int]:
    """{assigned string value: lineno} for module-level
    `NAME = "literal"` assignments whose NAME passes `name_filter`."""
    out: Dict[str, int] = {}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)):
            continue
        for target in node.targets:
            if isinstance(target, ast.Name) and name_filter(target.id):
                out.setdefault(node.value.value, node.lineno)
    return out


def code_fault_points(project: Project) -> Optional[Dict[str, int]]:
    pf = project.file(FAULTS_MODULE)
    if pf is None or pf.tree is None:
        return None
    return _string_constants(
        pf.tree, lambda name: name.startswith("POINT_")
    )


def code_span_events(project: Project) -> Optional[Dict[str, int]]:
    pf = project.file(EVENTS_MODULE)
    if pf is None or pf.tree is None:
        return None
    return _string_constants(
        pf.tree,
        lambda name: name.isupper() and not name.startswith("ENV_"),
    )


def code_slo_names(project: Project) -> Optional[Dict[str, int]]:
    pf = project.file(SLO_MODULE)
    if pf is None or pf.tree is None:
        return None
    # STATE_*/KINDS deliberately sit outside the SLO_ prefix: only the
    # closed SLO-name vocabulary is a doc contract.
    return _string_constants(
        pf.tree, lambda name: name.startswith("SLO_")
    )


def code_metrics(project: Project) -> Dict[str, Tuple[str, int, str]]:
    """{metric name: (rel, lineno, kind)} over every module under
    METRIC_ROOTS in the project."""
    out: Dict[str, Tuple[str, int, str]] = {}
    for pf in project.files:
        if not pf.rel.startswith(METRIC_ROOTS) or pf.tree is None:
            continue
        for node, method, name in iter_metric_creations(pf.tree):
            if name is not None and name not in out:
                out[name] = (pf.rel, node.lineno, method)
    return out


def _doc_covers_metric(
    name: str, kind: str, documented: Dict[str, int]
) -> bool:
    if name in documented:
        return True
    if kind == "histogram":
        return any(
            name + suffix in documented for suffix in HISTOGRAM_DERIVED
        )
    return False


def _code_has_metric(
    doc_name: str, inventory: Dict[str, Tuple[str, int, str]]
) -> bool:
    if doc_name in inventory:
        return True
    for suffix in HISTOGRAM_DERIVED:
        if doc_name.endswith(suffix):
            base = doc_name[: -len(suffix)]
            entry = inventory.get(base)
            if entry is not None and entry[2] == "histogram":
                return True
    return False


class DriftRule(Rule):
    id = RULE_ID
    title = "docs↔code drift (fault points, metric catalogue, span events)"
    rationale = (
        "the runbook tables are the operator interface; an inventory "
        "the docs and code disagree on fails exactly when someone is "
        "debugging an incident from the docs"
    )

    def __init__(
        self,
        allow_undocumented_metrics: FrozenSet[str] = frozenset(),
    ):
        # Metric names exempt from the must-be-catalogued direction
        # (e.g. test-only fixtures); each addition needs a justification.
        self.allow_undocumented_metrics = frozenset(
            allow_undocumented_metrics
        )

    def check_project(self, project: Project) -> Iterable[Finding]:
        yield from self._check_faults(project)
        yield from self._check_metrics_and_events(project)
        yield from self._check_slos(project)

    # ---- fault points ---------------------------------------------------

    def _check_faults(self, project: Project) -> Iterable[Finding]:
        points = code_fault_points(project)
        if points is None:
            return  # faults.py outside the scanned set: nothing to check
        text = project.read_doc(ROBUSTNESS_DOC)
        if text is None:
            yield Finding(
                ROBUSTNESS_DOC, 1, self.id,
                f"{ROBUSTNESS_DOC} is missing, so the "
                f"{len(points)} injection points in common/faults.py "
                "are undocumented",
            )
            return
        documented = doc_fault_points(text)
        if documented is None:
            yield Finding(
                ROBUSTNESS_DOC, 1, self.id,
                "no injection-point table (header `| Point |`) found — "
                "the fault-point runbook is gone",
            )
            return
        for point, lineno in sorted(documented.items()):
            if point not in points:
                yield Finding(
                    ROBUSTNESS_DOC, lineno, self.id,
                    f"documents injection point {point!r} that "
                    "common/faults.py does not define",
                )
        for point, lineno in sorted(points.items()):
            if point not in documented:
                yield Finding(
                    FAULTS_MODULE, lineno, self.id,
                    f"injection point {point!r} is missing from the "
                    f"fault-point table in {ROBUSTNESS_DOC}",
                )

    # ---- metric catalogue + span events ---------------------------------

    def _check_metrics_and_events(
        self, project: Project
    ) -> Iterable[Finding]:
        events = code_span_events(project)
        if events is None:
            # Partial scan (a file or subtree): the code-side inventory
            # would be incomplete, so every doc row would false-positive.
            return
        inventory = code_metrics(project)
        text = project.read_doc(OBSERVABILITY_DOC)
        if text is None:
            yield Finding(
                OBSERVABILITY_DOC, 1, self.id,
                f"{OBSERVABILITY_DOC} is missing, so the metric "
                "catalogue and span-event vocabulary are undocumented",
            )
            return

        documented, abbreviated = doc_metric_catalogue(text)
        for lineno, token in abbreviated:
            yield Finding(
                OBSERVABILITY_DOC, lineno, self.id,
                f"abbreviated catalogue entry `{token}` — write the "
                "full metric name so the catalogue is greppable and "
                "machine-checkable",
            )
        if documented is None:
            yield Finding(
                OBSERVABILITY_DOC, 1, self.id,
                "no metric-catalogue table (first header cell "
                "`metric`) found",
            )
        else:
            for name, lineno in sorted(documented.items()):
                if not _code_has_metric(name, inventory):
                    yield Finding(
                        OBSERVABILITY_DOC, lineno, self.id,
                        f"catalogues metric {name!r} that no "
                        "elasticdl_tpu/ or model_zoo/ module creates",
                    )
            for name, (rel, lineno, kind) in sorted(inventory.items()):
                if name in self.allow_undocumented_metrics:
                    continue
                if not _doc_covers_metric(name, kind, documented):
                    yield Finding(
                        rel, lineno, self.id,
                        f"metric {name!r} ({kind}) is missing from the "
                        f"catalogue in {OBSERVABILITY_DOC}",
                    )

        doc_events = doc_span_events(text)
        if doc_events is None:
            yield Finding(
                OBSERVABILITY_DOC, 1, self.id,
                "no span-event table (first header cell `event`) "
                "found — the event vocabulary in common/events.py is "
                "undocumented",
            )
            return
        for name, lineno in sorted(doc_events.items()):
            if name not in events:
                yield Finding(
                    OBSERVABILITY_DOC, lineno, self.id,
                    f"documents span event {name!r} that "
                    "common/events.py does not define",
                )
        for name, lineno in sorted(events.items()):
            if name not in doc_events:
                yield Finding(
                    EVENTS_MODULE, lineno, self.id,
                    f"span event {name!r} is missing from the "
                    f"span-event table in {OBSERVABILITY_DOC}",
                )

    # ---- SLO vocabulary -------------------------------------------------

    def _check_slos(self, project: Project) -> Iterable[Finding]:
        slos = code_slo_names(project)
        if slos is None:
            return  # slo.py outside the scanned set: nothing to check
        text = project.read_doc(OBSERVABILITY_DOC)
        if text is None:
            # _check_metrics_and_events already reported the missing doc
            return
        documented = doc_slo_vocabulary(text)
        if documented is None:
            yield Finding(
                OBSERVABILITY_DOC, 1, self.id,
                "no SLO table (first header cell `slo`) found — the "
                "SLO vocabulary in common/slo.py is undocumented",
            )
            return
        for name, lineno in sorted(documented.items()):
            if name not in slos:
                yield Finding(
                    OBSERVABILITY_DOC, lineno, self.id,
                    f"documents SLO {name!r} that common/slo.py does "
                    "not define",
                )
        for name, lineno in sorted(slos.items()):
            if name not in documented:
                yield Finding(
                    SLO_MODULE, lineno, self.id,
                    f"SLO {name!r} is missing from the SLO table in "
                    f"{OBSERVABILITY_DOC}",
                )


register(DriftRule())
