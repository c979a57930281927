"""Numbers from the task-boundary stamps the train driver took."""

from benchmarks import stats


def read(params: dict, context: dict):
    if "stamps" not in context:
        return None
    if params["stat"] == "gap_mean_ms":
        gaps = context["gaps"]
        return 1e3 * sum(gaps) / len(gaps) if gaps else None
    if params["stat"] == "median_rate":
        if len(context["stamps"]) < 3:   # only the dropped first task
            return None
        return stats.median_task_rate(context["stamps"])
    raise ValueError(f"task_stamps: unknown stat {params['stat']!r}")
