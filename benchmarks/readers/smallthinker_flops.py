"""Model FLOP/s utilisation of the SmallThinker decoder's train step:
operations a step needs forward and backward by shapes
(benchmarks/flops_smallthinker.py: the band of a band layer, the causal
half of a full one, every layer's projections, router and held ReGLU
experts, the untied head over the vocabulary slice, remat not counted)
over the device's time for a traced step (the trace's window, idle
included, over its steps) times chips times the device's peak
(benchmarks/peaks.json).

The held experts' products are counted over the rows ACTUALLY routed here
(`worker_moe_routed_here_ratio`, mean over the layers, from the registry,
as `roofline_smallthinker.py` counts them), not at balanced load: the
routers of the cut model drift onto the held experts as the job runs.  The
gauge is of the run's last task, which is the traced one, so the time is
the trace's and not the window's rate: both sides of the share are the
same steps.  A run without a trace, or a program without the counter,
reads as nothing.
"""

from benchmarks import flops_smallthinker
from benchmarks.readers import registry_gauge


def read(params: dict, context: dict):
    trace, steps = context.get("trace"), context.get("trace_steps")
    shares = registry_gauge.children("worker_moe_routed_here_ratio")
    if trace is None or not steps or shares is None:
        return None
    cell = context["cell"]
    seq_len = cell.traffic["seq_len"]
    per_step = flops_smallthinker.tokens_per_step(cell.traffic) * (
        flops_smallthinker.train_flops_per_token(
            cell.config, seq_len, sum(shares) / len(shares)
        )
    )
    peak = context["peaks"]["bf16_flops_per_s"] * context["chips"]
    return 100.0 * per_step * steps / (trace["window_s"] * peak)
