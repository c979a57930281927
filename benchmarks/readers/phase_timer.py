"""Host phases of the worker loop, from `common/profiler.PhaseTimer`
totals taken when the window opened and closed (host clock)."""


def read(params: dict, context: dict):
    phases = context.get("phases")
    if phases is None or params["phase"] not in phases:
        return None
    seconds = phases[params["phase"]]
    if params["per"] == "window_share_pct":
        return 100.0 * seconds / context["window_s"]
    if params["per"] == "us_per_example":
        return 1e6 * seconds / context["examples"]
    raise ValueError(f"phase_timer: unknown per {params['per']!r}")
