"""A kernel family's share of its roofline in the Olmo-Hybrid decoder: the
least time the chip could take for that work in one train step (the larger
of operations over the peak FLOP/s and bytes over the peak bytes/s, both
from shapes at the PUBLISHED head widths: benchmarks/flops_olmo_hybrid.py)
over the device time the trace gives the kernels (`trace_ops`' rule: the
operations whose HLO text matches `include` and no `exclude`).

    work     gdn_core      the scalar-decay delta rule of every delta-rule
                           layer at heads of 96 | 192: the chunked form's
                           operations at the op's chunk and its least
                           traffic, forward and backward (the kernels may
                           run on heads padded to 128 | 256: their time is
                           the padded work's, the work here is not)
             short_conv    the SiLU conv over the held q | k | v columns
                           of every delta-rule layer
             gqa_core      the attention layer's kernels at the held heads
                           of 128, the causal half counted
    bound    flops | bytes | (absent) the larger of the two

The time and the work must cover the same operations: a share over 100%
says they do not.  The time includes what remat runs again (a delta-rule
block's second `gdn_chunk_fwd` and conv forward) and the work does not.
A trace without the part's kernels reads as nothing.
"""

from benchmarks import flops_olmo_hybrid
from benchmarks.readers import trace_ops

WORK = {
    "gdn_core": (
        flops_olmo_hybrid.gdn_core_train_flops_per_step,
        flops_olmo_hybrid.gdn_core_train_bytes_per_step,
    ),
    "short_conv": (
        flops_olmo_hybrid.short_conv_train_flops_per_step,
        flops_olmo_hybrid.short_conv_train_bytes_per_step,
    ),
    "gqa_core": (
        flops_olmo_hybrid.gqa_core_train_flops_per_step,
        flops_olmo_hybrid.gqa_core_train_bytes_per_step,
    ),
}


def work_of(name: str, cell):
    """(operations, bytes) of one train step."""
    if name not in WORK:
        raise ValueError(f"roofline_olmo_hybrid: unknown work {name!r}")
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
    peaks = context["peaks"]
    least = {
        "flops": work[0] / peaks["bf16_flops_per_s"],
        "bytes": work[1] / peaks["hbm_bytes_per_s"],
    }
    bound = params.get("bound")
    seconds = least[bound] if bound else max(least.values())
    return 100.0 * seconds / (ms * 1e-3)
