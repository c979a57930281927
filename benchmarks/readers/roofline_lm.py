"""A part's share of its roofline: the least time the chip could take
for the part's work in one train step (the larger of operations over the
peak FLOP/s and bytes over the peak bytes/s, both from shapes:
benchmarks/flops_lm.py) over the device time the trace gives the part
(`trace_ops`' rule: the operations whose HLO text matches `include` and
no `exclude`).

    work     mla_core      attention cores, the causal half counted
             moe_experts   the grouped products over the rows ACTUALLY
                           routed here (`worker_moe_routed_here_ratio`,
                           mean over the layers, from the registry)
    bound    flops | bytes | (absent) the larger of the two

The time and the work must cover the same operations: a share over 100%
says they do not.  A trace without the part's operations, or a program
without the counter, reads as nothing.
"""

from benchmarks import flops_lm
from benchmarks.readers import registry_gauge, trace_ops


def work_of(name: str, cell):
    """(operations, bytes) of one train step, or None."""
    if name == "mla_core":
        return (
            flops_lm.mla_core_train_flops_per_step(cell.config, cell.traffic),
            flops_lm.mla_core_train_bytes_per_step(cell.config, cell.traffic),
        )
    if name == "moe_experts":
        shares = registry_gauge.children("worker_moe_routed_here_ratio")
        if shares is None:
            return None
        here = sum(shares) / len(shares)
        return (
            flops_lm.moe_experts_train_flops_per_step(
                cell.config, cell.traffic, here
            ),
            flops_lm.moe_experts_train_bytes_per_step(
                cell.config, cell.traffic, here
            ),
        )
    raise ValueError(f"roofline_lm: unknown work {name!r}")


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
