"""Device time by the program's own scopes: the reduced trace's
per-operation seconds (`context["trace"]["op_seconds"]`, one entry an
instruction of the compiled step) joined to the program's scope table
(`elasticdl_tpu/common/programs.py: ProgramRegistry.scope_table`) by
`elasticdl_tpu/common/profiler.py: device_ms_by_scope`.  LEAVES only: a
`while` is its body's operations, which the trace names one by one, so
nothing is counted twice.  The rule is the metric file's `params`:

    program      the registered program's name (`worker_train_step`)
    stat         ms_per_step            the kept leaves' time a traced step
                 unattributed_share_pct leaves under no catalogue scope, or
                                        in no table, over all leaves
                 mixed_share_pct        fusions whose fused instructions lie
                                        in two catalogue scopes (each is
                                        charged whole to the scope of its
                                        own metadata), over all leaves
    scopes       `profiler.DEVICE_SCOPES` entries; a leaf is kept when its
                 INNERMOST entry is one of them, so `combine` keeps what
                 the walk's loops do outside `dispatch` and `experts`
    phase        forward | backward | rebuild (JAX's remat only); absent =
                 every phase
    exclude_ops  regular expressions on the operation's HLO text

The table is built here, on the first read, after the window and the
check: seconds, not in `setup_s`.  A program that keeps no table (one
older than it) reads as nothing, and so does a rule that keeps no leaf.
"""

import sys


def table_and_reduction(program: str):
    """(scope table, device_ms_by_scope) or None where the program has
    neither."""
    try:
        from elasticdl_tpu.common import profiler, programs
    except ImportError:
        return None
    reduce = getattr(profiler, "device_ms_by_scope", None)
    registry = programs.default_program_registry()
    if reduce is None or not hasattr(registry, "scope_table"):
        return None
    table = registry.scope_table(program)
    return None if table is None else (table, reduce)


def say_breakdown(whole: dict, steps: int) -> None:
    """The step by scope and phase, and the largest mixed fusions, once a
    run, on stderr (the result line keeps the ten metrics only)."""
    total = sum(whole["by_scope"].values()) + whole["unjoined"]
    rows = sorted(whole["by_scope"].items(), key=lambda kv: -kv[1])
    print(
        f"scopes: leaves {1e3 * total / steps:.2f} ms a step, unjoined "
        f"{100.0 * whole['unjoined'] / total:.3f}%; ms a step by scope "
        "and phase: " + "; ".join(
            f"{scope or 'none'}|{phase or 'unknown'} "
            f"{1e3 * seconds / steps:.2f}"
            for (scope, phase), seconds in rows
        ) + "; largest mixed fusions: " + "; ".join(
            f"{name} {1e3 * seconds / steps:.2f}" for name, seconds in
            sorted(whole["mixed_ops"].items(), key=lambda kv: -kv[1])[:3]
        ),
        file=sys.stderr, flush=True,
    )


def read(params: dict, context: dict):
    trace, steps = context.get("trace"), context.get("trace_steps")
    if trace is None or not steps:
        return None
    found = table_and_reduction(params["program"])
    if found is None:
        return None
    table, reduce = found
    op_seconds = trace["op_seconds"]
    stat = params["stat"]
    if stat == "ms_per_step":
        kept = reduce(
            op_seconds, table, scopes=params.get("scopes"),
            phase=params.get("phase"),
            exclude_ops=params.get("exclude_ops"),
        )["by_scope"]
        return 1e3 * sum(kept.values()) / steps if kept else None
    whole = reduce(op_seconds, table)
    total = sum(whole["by_scope"].values()) + whole["unjoined"]
    if not total:
        return None
    if stat == "unattributed_share_pct":
        say_breakdown(whole, steps)
        outside = whole["unjoined"] + sum(
            seconds for (scope, _), seconds in whole["by_scope"].items()
            if not scope
        )
        return 100.0 * outside / total
    if stat == "mixed_share_pct":
        return 100.0 * whole["mixed"] / total
    raise ValueError(f"scope_ops: unknown stat {stat!r}")
