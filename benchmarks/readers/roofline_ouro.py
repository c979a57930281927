"""A kernel family's share of its roofline in the Ouro decoder: the least
time the chip could take for that work in one train step (the larger of
operations over the peak FLOP/s and bytes over the peak bytes/s, both from
shapes: benchmarks/flops_ouro.py) over the device time the trace gives the
kernels (`trace_ops`' rule: the operations whose HLO text matches
`include` and no `exclude`).

    work     gqa_core   the streaming attention kernels at 16 heads of 128
                        over 16 K/V heads (a group of ONE), the causal
                        half counted, R x N applications a step
    bound    flops | bytes | (absent) the larger of the two

The time and the work must cover the same operations: a share over 100%
says they do not.  A trace without the kernels reads as nothing.
"""

from benchmarks import flops_ouro
from benchmarks.readers import trace_ops

WORK = {
    "gqa_core": (
        flops_ouro.core_train_flops_per_step,
        flops_ouro.core_train_bytes_per_step,
    ),
}


def work_of(name: str, cell):
    """(operations, bytes) of one train step."""
    if name not in WORK:
        raise ValueError(f"roofline_ouro: unknown work {name!r}")
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
