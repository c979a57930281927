"""Model FLOP/s utilisation of the Ouro decoder's train step: operations a
step needs forward and backward by shapes (benchmarks/flops_ouro.py: R x N
block applications' projections, causal-half cores and gated MLPs, R
passes of the untied head over the whole vocabulary, the gate's logits,
remat not counted) over the device's time for a traced step (the trace's
window, idle included, over its steps) times chips times the device's
peak (benchmarks/peaks.json), as `smallthinker_flops.py` takes the time.
A run without a trace reads as nothing.
"""

from benchmarks import flops_ouro


def read(params: dict, context: dict):
    trace, steps = context.get("trace"), context.get("trace_steps")
    if trace is None or not steps:
        return None
    cell = context["cell"]
    per_step = flops_ouro.tokens_per_step(cell.traffic) * (
        flops_ouro.train_flops_per_token(
            cell.config, cell.traffic["seq_len"]
        )
    )
    peak = context["peaks"]["bf16_flops_per_s"] * context["chips"]
    return 100.0 * per_step * steps / (trace["window_s"] * peak)
