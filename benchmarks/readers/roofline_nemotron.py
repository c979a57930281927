"""A kernel family's share of its roofline in the Nemotron-H decoder: the
least time the chip could take for that work in one train step (the larger
of operations over the peak FLOP/s and bytes over the peak bytes/s, both
from shapes: benchmarks/flops_nemotron.py) over the device time the trace
gives the kernels (`trace_ops`' rule: the operations whose HLO text
matches `include` and no `exclude`).

    work     ssd_core      the state-space scan of every Mamba-2 layer at
                           8 groups: the recurrence's operations and its
                           least traffic, forward and backward
             short_conv    the biased SiLU conv over x | B | C (6,144
                           columns) of every Mamba-2 layer
             gqa_core      the attention layer's kernels at 32 heads of
                           128 over 2 K/V heads, the causal half counted
             moe_experts   the grouped products of the non-gated experts
                           (two an expert) over the rows ACTUALLY routed
                           here (`worker_moe_routed_here_ratio`, mean over
                           the layers, from the registry)
    bound    flops | bytes | (absent) the larger of the two

The time and the work must cover the same operations: a share over 100%
says they do not.  The time includes what remat runs again (a Mamba-2
block's second `ssd_fwd` and conv forward) and the work does not.  A trace
without the part's kernels, or a program without the counter, reads as
nothing.
"""

from benchmarks import flops_nemotron
from benchmarks.readers import registry_gauge, trace_ops

WORK = {
    "ssd_core": (
        flops_nemotron.ssd_core_train_flops_per_step,
        flops_nemotron.ssd_core_train_bytes_per_step,
    ),
    "short_conv": (
        flops_nemotron.short_conv_train_flops_per_step,
        flops_nemotron.short_conv_train_bytes_per_step,
    ),
    "gqa_core": (
        flops_nemotron.gqa_core_train_flops_per_step,
        flops_nemotron.gqa_core_train_bytes_per_step,
    ),
}


def work_of(name: str, cell):
    """(operations, bytes) of one train step, or None."""
    if name == "moe_experts":
        shares = registry_gauge.children("worker_moe_routed_here_ratio")
        if shares is None:
            return None
        here = sum(shares) / len(shares)
        return (
            flops_nemotron.moe_experts_train_flops_per_step(
                cell.config, cell.traffic, here
            ),
            flops_nemotron.moe_experts_train_bytes_per_step(
                cell.config, cell.traffic, here
            ),
        )
    if name not in WORK:
        raise ValueError(f"roofline_nemotron: unknown work {name!r}")
    flops, bytes_ = WORK[name]
    return (
        flops(cell.config, cell.traffic), bytes_(cell.config, cell.traffic)
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
