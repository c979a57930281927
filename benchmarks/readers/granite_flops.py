"""Model FLOP/s utilisation of the granite hybrid decoder's train step:
operations a token needs forward and backward by shapes
(benchmarks/flops_granite.py: the Mamba-2 layers' projections and
recurrence, the causal half of the attention core, the gated MLP of every
layer, the tied head over the vocabulary slice, remat not counted) times
the window's tokens a second, over chips times the device's peak
(benchmarks/peaks.json)."""

from benchmarks import flops_granite


def read(params: dict, context: dict):
    rate = context.get("train_examples_per_s")
    if rate is None:
        return None
    cell = context["cell"]
    seq_len = cell.traffic["seq_len"]
    per_token = flops_granite.train_flops_per_token(cell.config, seq_len)
    peak = context["peaks"]["bf16_flops_per_s"] * context["chips"]
    return 100.0 * per_token * seq_len * rate / peak
