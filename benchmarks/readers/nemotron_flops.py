"""Model FLOP/s utilisation of the Nemotron-H decoder's train step:
operations a step needs forward and backward by shapes
(benchmarks/flops_nemotron.py: the Mamba-2 layers' projections and
recurrence, the causal half of the attention core, the routed layers'
router, shared expert and held experts, the untied head over the
vocabulary slice, remat not counted) over the device's time for a traced
step (the trace's window, idle included, over its steps) times chips
times the device's peak (benchmarks/peaks.json).

The held experts' products are counted over the rows ACTUALLY routed here
(`worker_moe_routed_here_ratio`, mean over the layers, from the registry,
as `roofline_nemotron.py` counts them), not at balanced load: in this
cell the routers move the top-6 slots onto the held experts all through
the run (0.0625 of them at the seeded weights, 0.6-0.77 eight tasks
later), and a count at balanced load leaves out a third of the work done.
The gauge is of the run's last task, which is the traced one, so the time
is the trace's and not the window's rate: both sides of the share are
the same steps.  A run without a trace, or a program without the counter,
reads as nothing.
"""

from benchmarks import flops_nemotron
from benchmarks.readers import registry_gauge


def read(params: dict, context: dict):
    trace, steps = context.get("trace"), context.get("trace_steps")
    shares = registry_gauge.children("worker_moe_routed_here_ratio")
    if trace is None or not steps or shares is None:
        return None
    cell = context["cell"]
    seq_len = cell.traffic["seq_len"]
    per_step = flops_nemotron.tokens_per_step(cell.traffic) * (
        flops_nemotron.train_flops_per_token(
            cell.config, seq_len, sum(shares) / len(shares)
        )
    )
    peak = context["peaks"]["bf16_flops_per_s"] * context["chips"]
    return 100.0 * per_step * steps / (trace["window_s"] * peak)
