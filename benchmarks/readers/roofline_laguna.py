"""An attention core's share of its roofline in the Laguna decoder: the
least time the chip could take for the cores of one KIND of layer in one
train step (the larger of operations over the peak FLOP/s and bytes over
the peak bytes/s, both from shapes: benchmarks/flops_laguna.py) over the
device time the trace gives those kernels (`trace_ops`' rule: the
operations whose HLO text matches `include` and no `exclude`).

    work     window_core   the `sliding_attention` layers' kernels, the
                           band counted (min(t + 1, window) keys a query)
             gqa_core      the `full_attention` layers' kernels over
                           grouped K/V, the causal half counted
    bound    flops | bytes | (absent) the larger of the two

The time and the work must cover the same operations: a share over 100%
says they do not.  A trace without the part's kernels (a program that
names none so) reads as nothing.
"""

from benchmarks import flops_laguna
from benchmarks.readers import trace_ops

KINDS = {
    "window_core": flops_laguna.WINDOW,
    "gqa_core": flops_laguna.FULL,
}


def work_of(name: str, cell):
    """(operations, bytes) of one train step."""
    if name not in KINDS:
        raise ValueError(f"roofline_laguna: unknown work {name!r}")
    return (
        flops_laguna.core_train_flops_per_step(
            cell.config, cell.traffic, KINDS[name]
        ),
        flops_laguna.core_train_bytes_per_step(
            cell.config, cell.traffic, KINDS[name]
        ),
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
