"""Model FLOP/s utilisation of the Olmo-Hybrid decoder's train step:
operations a step needs forward and backward by shapes
(benchmarks/flops_olmo_hybrid.py: the delta-rule layers' projections and
chunked delta rule at heads of 96 | 192, the attention layer's projections
and the causal half of its core, every layer's MLP, the untied head over
the vocabulary slice, remat not counted) over the device's time for a
traced step (the trace's window, idle included, over its steps) times
chips times the device's peak (benchmarks/peaks.json), as `ouro_flops.py`
takes the time.  A run without a trace reads as nothing.
"""

from benchmarks import flops_olmo_hybrid


def read(params: dict, context: dict):
    trace, steps = context.get("trace"), context.get("trace_steps")
    if trace is None or not steps:
        return None
    cell = context["cell"]
    per_step = flops_olmo_hybrid.tokens_per_step(cell.traffic) * (
        flops_olmo_hybrid.train_flops_per_token(
            cell.config, cell.traffic["seq_len"]
        )
    )
    peak = context["peaks"]["bf16_flops_per_s"] * context["chips"]
    return 100.0 * per_step * steps / (trace["window_s"] * peak)
