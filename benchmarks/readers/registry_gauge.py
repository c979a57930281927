"""A labelled gauge of the program's metrics registry
(`elasticdl_tpu/common/metrics.py`), reduced over its label values.

    metric   the family's name (`worker_moe_routed_here_ratio`, ...)
    stat     max | mean of the family's children

The program sets these once a task from what the last step sowed
(`worker/worker.py`), so the reading is of the run's last task.  A
program that has no such family, or never set it, reads as nothing.
"""


def children(metric: str):
    """[value] of every labelled child of `metric`, or None."""
    try:
        from elasticdl_tpu.common import metrics as metrics_lib

        families = {
            f.name: f for f in metrics_lib.default_registry().families()
        }
        values = list(families[metric].child_values().values())
    except (ImportError, KeyError, AttributeError):
        return None
    return values or None


def read(params: dict, context: dict):
    values = children(params["metric"])
    if values is None:
        return None
    if params["stat"] == "max":
        return max(values)
    if params["stat"] == "mean":
        return sum(values) / len(values)
    raise ValueError(f"registry_gauge: unknown stat {params['stat']!r}")
