"""`trace_ops`, with the cell's traffic in reach of a rule: patterns may
name, beside the configuration's keys, what one train step of THIS cell
handles,

    {tokens}   minibatch_size x seq_len
    {slots}    tokens x the configuration's `num_experts_per_tok` (the
               rows of a routed layer's worst-case dispatch buffer)

so that an operation is matched by a shape the batch gives it
(`s32[65536]`), as `scatter_ms_per_step` matches one the table gives it.
"""

import dataclasses

from benchmarks.readers import trace_ops


def with_traffic(cell):
    tokens = cell.traffic["minibatch_size"] * cell.traffic["seq_len"]
    return dataclasses.replace(cell, config={
        **cell.config, "tokens": tokens,
        "slots": tokens * cell.config.get("num_experts_per_tok", 1),
    })


def read(params: dict, context: dict):
    if context.get("trace") is None:
        return None
    return trace_ops.read(
        params, {**context, "cell": with_traffic(context["cell"])}
    )
