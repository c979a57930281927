"""`elasticdl programs`: the XLA program observatory from a /varz endpoint.

Every role's telemetry server republishes its process-wide
ProgramRegistry (common/programs.py) summary under the "programs" varz
key: per-program compile counts, distinct aval signatures vs declared
budget, recompile storms, compile-time quantiles, the compiles' seconds
by stage (trace, lowering, XLA or cache load) with the persistent
cache's hits and misses, and the XLA cost
model (flops / bytes per execution) joined with live step rate into
MFU and bandwidth attribution.  Like `elasticdl top` this is a pure
HTTP client; `render_programs` is also callable directly on a summary
dict so in-process tests render the exact bytes the CLI prints.
"""

from __future__ import annotations

import json
import sys

from elasticdl_tpu.client.top import fetch_varz


def _eng(value: float) -> str:
    """Compact engineering notation for flops/bytes columns."""
    value = float(value or 0.0)
    if value <= 0:
        return "-"
    for unit, scale in (("T", 1e12), ("G", 1e9), ("M", 1e6), ("K", 1e3)):
        if value >= scale:
            return f"{value / scale:.2f}{unit}"
    return f"{value:.0f}"


def render_programs(summary: dict) -> str:
    """One report frame from a ProgramRegistry.summary() dict: headline
    totals, then a row per named program."""
    lines = [
        "elasticdl programs — {n} programs, {c} compiles, "
        "{s} signatures, {st} storms".format(
            n=summary.get("programs", 0),
            c=summary.get("compiles_total", 0),
            s=summary.get("signatures_total", 0),
            st=summary.get("storms_total", 0),
        ),
        "program".ljust(24) + "compiles".rjust(9) + "sigs".rjust(6)
        + "budget".rjust(7) + "storms".rjust(7) + "c_p50".rjust(9)
        + "c_p99".rjust(9) + "trace".rjust(9) + "lower".rjust(9)
        + "xla".rjust(9) + "cache h/m".rjust(10) + "flops/x".rjust(9)
        + "bytes/x".rjust(9),
    ]
    ledger = summary.get("ledger", {})
    for name in sorted(ledger):
        rec = ledger[name]
        budget = rec.get("budget")
        lines.append(
            str(name).ljust(24)
            + str(rec.get("compiles", 0)).rjust(9)
            + str(rec.get("signatures", 0)).rjust(6)
            + (str(budget) if budget is not None else "-").rjust(7)
            + str(rec.get("storms", 0)).rjust(7)
            + "{:.3f}s".format(
                rec.get("compile_seconds_p50", 0.0)
            ).rjust(9)
            + "{:.3f}s".format(
                rec.get("compile_seconds_p99", 0.0)
            ).rjust(9)
            # the compiles' seconds by stage, summed (xla: XLA's compile
            # or the persistent cache's load), and that cache's answers
            + "".join(
                "{:.3f}s".format(rec.get(stage + "_seconds", 0.0)).rjust(9)
                for stage in ("trace", "lower", "xla")
            )
            + "{}/{}".format(
                rec.get("cache_hits", 0), rec.get("cache_misses", 0)
            ).rjust(10)
            + _eng(rec.get("flops_per_execution", 0.0)).rjust(9)
            + _eng(rec.get("bytes_per_execution", 0.0)).rjust(9)
        )
        avals = rec.get("avals", "")
        if avals:
            lines.append("  " + avals)
    if not ledger:
        lines.append("(no programs registered — has the role jitted "
                     "anything yet?)")
    return "\n".join(lines)


def programs(args) -> int:
    """Fetch a role's /varz and render the program observatory."""
    try:
        varz = fetch_varz(args.varz_addr)
    except Exception as exc:
        print(
            f"elasticdl programs: cannot scrape {args.varz_addr}: {exc}",
            file=sys.stderr,
        )
        return 1
    payload = varz.get("programs")
    if not payload:
        print(
            "elasticdl programs: endpoint exposes no \"programs\" varz "
            "key (pre-observatory build?)",
            file=sys.stderr,
        )
        return 1
    if getattr(args, "json", False):
        print(json.dumps(payload, indent=2, sort_keys=True))
    else:
        print(render_programs(payload))
    return 0
