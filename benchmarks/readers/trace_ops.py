"""Numbers from the reduced device trace (benchmarks/trace_reduce.py).

`ops_ms_per_step` sums the device seconds of the operations whose HLO
text matches any `include` pattern and no `exclude` pattern.  Patterns
are regular expressions in which `{key}` stands for the configuration's
value, so a rule is written once for every table or model size.
`line` picks the operations line (`ops`, the default) or the start-to-done
spans of asynchronous operations (`async`, else `ops` where none is).
"""

import re


def _per_step(seconds: float, context: dict) -> float:
    return 1e3 * seconds / context["trace_steps"]


def read(params: dict, context: dict):
    trace = context.get("trace")
    if trace is None:
        return None
    stat = params["stat"]
    if stat == "idle_share_pct":
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    if not context.get("trace_steps"):
        return None
    if stat == "busy_ms_per_step":
        return _per_step(trace["busy_s"], context)
    if stat != "ops_ms_per_step":
        raise ValueError(f"trace_ops: unknown stat {stat!r}")
    config = context["cell"].config
    include = [re.compile(p.format(**config)) for p in params["include"]]
    exclude = [
        re.compile(p.format(**config)) for p in params.get("exclude", [])
    ]
    table = trace["op_seconds"]
    if params.get("line") == "async" and trace.get("async_op_seconds"):
        matched = {
            name: s for name, s in trace["async_op_seconds"].items()
            if any(p.search(name) for p in include)
        }
        if matched:
            table = trace["async_op_seconds"]
    total, found = 0.0, False
    for name, seconds in table.items():
        if any(p.search(name) for p in include) and not any(
            p.search(name) for p in exclude
        ):
            total += seconds
            found = True
    return _per_step(total, context) if found else None
