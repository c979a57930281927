"""A kernel family's share of its roofline in the Qwen3-Next decoder: the
least time the chip could take for that work in one train step (the larger
of operations over the peak FLOP/s and bytes over the peak bytes/s, both
from shapes: benchmarks/flops_qwen3_next.py) over the device time the
trace gives the kernels (`trace_ops`' rule: the operations whose HLO text
matches `include` and no `exclude`).

    work     gdn_core      the scalar-decay delta rule of every GDN layer:
                           the chunked form's operations at the op's chunk
                           and its least traffic (q and k once a KEY
                           head), forward and backward
             short_conv    the SiLU conv over q | k | v (8,192 columns) of
                           every GDN layer
             gqa_core      the attention layer's kernels at 16 heads of
                           256 over 2 K/V heads, the causal half counted
             moe_experts   the grouped products of the gated experts
                           (three an expert) over the rows ACTUALLY routed
                           here (`worker_moe_routed_here_ratio`, mean over
                           the layers, from the registry)
    bound    flops | bytes | (absent) the larger of the two

The time and the work must cover the same operations: a share over 100%
says they do not.  The time includes what remat runs again (a GDN block's
second `gdn_chunk_fwd` and conv forward) and the work does not.  A trace
without the part's kernels, or a program without the counter, reads as
nothing.
"""

from benchmarks import flops_qwen3_next
from benchmarks.readers import registry_gauge, trace_ops

WORK = {
    "gdn_core": (
        flops_qwen3_next.gdn_core_train_flops_per_step,
        flops_qwen3_next.gdn_core_train_bytes_per_step,
    ),
    "short_conv": (
        flops_qwen3_next.short_conv_train_flops_per_step,
        flops_qwen3_next.short_conv_train_bytes_per_step,
    ),
    "gqa_core": (
        flops_qwen3_next.gqa_core_train_flops_per_step,
        flops_qwen3_next.gqa_core_train_bytes_per_step,
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
            flops_qwen3_next.moe_experts_train_flops_per_step(
                cell.config, cell.traffic, here
            ),
            flops_qwen3_next.moe_experts_train_bytes_per_step(
                cell.config, cell.traffic, here
            ),
        )
    if name not in WORK:
        raise ValueError(f"roofline_qwen3_next: unknown work {name!r}")
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
