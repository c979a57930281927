"""Model FLOP/s utilisation: operations the forward and backward passes
need per example (benchmarks/flops.py, from shapes) times the window's
examples/s, over chips times the device's peak (benchmarks/peaks.json)."""

from benchmarks import flops


def read(params: dict, context: dict):
    rate = context.get("train_examples_per_s")
    if rate is None:
        return None
    cell = context["cell"]
    per_example = flops.TRAIN_FLOPS[cell.config["model"]](
        cell.config, cell.traffic
    )
    peak = context["peaks"]["bf16_flops_per_s"] * context["chips"]
    return 100.0 * per_example * rate / peak
