"""A kernel family's share of its roofline in the SmallThinker decoder:
the least time the chip could take for that work in one train step (the
larger of operations over the peak FLOP/s and bytes over the peak bytes/s,
both from shapes: benchmarks/flops_smallthinker.py) over the device time
the trace gives the kernels (`trace_ops`' rule: the operations whose HLO
text matches `include` and no `exclude`).

    work     window_core   the band layers' kernels at 28 heads of 128
                           over 4 K/V heads, the band counted (min(t + 1,
                           window) keys a query)
             gqa_core      the full layers' kernels, the causal half
                           counted
             moe_experts   the grouped products of the ReGLU experts
                           (three an expert) over the rows ACTUALLY routed
                           here (`worker_moe_routed_here_ratio`, mean over
                           the layers, from the registry)
    bound    flops | bytes | (absent) the larger of the two

The time and the work must cover the same operations: a share over 100%
says they do not.  A trace without the part's kernels, or a program
without the counter, reads as nothing.
"""

from benchmarks import flops_smallthinker
from benchmarks.readers import registry_gauge, trace_ops

BANDED = {"window_core": True, "gqa_core": False}


def work_of(name: str, cell):
    """(operations, bytes) of one train step, or None."""
    if name == "moe_experts":
        shares = registry_gauge.children("worker_moe_routed_here_ratio")
        if shares is None:
            return None
        here = sum(shares) / len(shares)
        return (
            flops_smallthinker.moe_experts_train_flops_per_step(
                cell.config, cell.traffic, here
            ),
            flops_smallthinker.moe_experts_train_bytes_per_step(
                cell.config, cell.traffic, here
            ),
        )
    if name not in BANDED:
        raise ValueError(f"roofline_smallthinker: unknown work {name!r}")
    return (
        flops_smallthinker.core_train_flops_per_step(
            cell.config, cell.traffic, BANDED[name]
        ),
        flops_smallthinker.core_train_bytes_per_step(
            cell.config, cell.traffic, BANDED[name]
        ),
    )


def read(params: dict, context: dict):
    ms = trace_ops.read(
        {**params, "stat": "ops_ms_per_step"}, context
    )
    if not ms:
        return None
    work = work_of(params["work"], context["cell"])
    if work is None:
        return None
    peaks = context["peaks"]
    least = {
        "flops": work[0] / peaks["bf16_flops_per_s"],
        "bytes": work[1] / peaks["hbm_bytes_per_s"],
    }
    bound = params.get("bound")
    seconds = least[bound] if bound else max(least.values())
    return 100.0 * seconds / (ms * 1e-3)
