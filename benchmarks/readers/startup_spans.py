"""Set-up by phase, from the program's own start-up record.

The program keeps its start-up and every compile's stages as spans of the
one `PhaseTimer` the loop's spans lie in (`common/profiler.py:
STARTUP_PHASES`, `COMPILE_PHASES`), on `time.perf_counter()`, the clock of
the driver's task stamps, and outside the ring's turnover:

    boot           the process's start (the OS's stamp) -> the CLI's entry:
                   the interpreter, the imports, and in this benchmark the
                   backend's start and the data written from the seed
    job_setup      the entry -> the model's owner is built
    worker_setup   -> the loop thread's first `get_task`
    init_state,    where the state is made, lazily from the first batch:
    restore        inside the first task, on the loop thread
    compile_trace, one compile's stages, children of the span the compiling
    compile_lower, thread was in; `attrs["program"]` names the registered
    compile_xla    program (`(unregistered)`: none), `attrs["cache"]` on
                   `compile_xla` is the persistent cache's `hit`, `miss`
                   or `off`

Set-up is the `boot` span's start to the window's opening
(`context["stamps"][0][0]`); only what BEGAN before the opening is read,
and a span that straddles it is cut there.  The rule is the metric file's
`params`:

    stat   span_s      summed length of the spans named in `spans`; with
                       `program`, of those whose `attrs["program"]` it is
                       (every compile of it added)
           loop_run_s  the loop thread's time from its first `from` span
                       (`get_task`) to the opening, less what the spans
                       named in `less` cover of it (each second once, also
                       where a compile lies inside `init_state`): the
                       warm-up tasks as run
           cache_hit_share_pct   hits over hits and misses of the `span`
                       records (`compile_xla`); 0 where no compile asked
                       the cache
           unattributed_share_pct   share of set-up in which the main path
                       lay in no span: the `main_path` spans up to the
                       loop's first `from` span, every span of the loop's
                       thread after

`span_s` over the three compile stages of `(unregistered)` is what the
program's counter `worker_unregistered_compile_seconds_total` read when the
window opened: the same call feeds both, and the counter itself has moved
on by the time a reader runs (the check after the window compiles
eagerly).  The six `_s` metrics do not quite add up to set-up: what
remains is the compiles of OTHER programs inside the first task (and the
little `setup_unattributed_share` counts).

A program that keeps no such record (one older than the record: no `boot`
span) reads as nothing.
"""

from benchmarks.readers import program_spans


def _clipped(spans, lo: float, hi: float) -> list:
    """[(start, end)] of `spans` within [lo, hi], the empty ones dropped."""
    cut = [(max(s.start, lo), min(s.end, hi)) for s in spans]
    return [(a, b) for a, b in cut if b > a]


def _covered_s(regions) -> float:
    """Seconds that `regions` cover, each second once."""
    covered, edge = 0.0, float("-inf")
    for start, end in sorted(regions):
        start = max(start, edge)
        if end > start:
            covered += end - start
            edge = end
    return covered


def read(params: dict, context: dict):
    stamps = context.get("stamps")
    spans = program_spans.ring()
    if not stamps or not spans:
        return None
    opened = stamps[0][0]
    boots = [s for s in spans if s.name == "boot"]
    if not boots:
        return None
    started = boots[0].start
    before = [s for s in spans if s.start < opened]
    stat = params["stat"]
    if stat == "span_s":
        kept = [
            s for s in before if s.name in params["spans"] and (
                "program" not in params
                or (s.attrs or {}).get("program") == params["program"]
            )
        ]
        return sum(b - a for a, b in _clipped(kept, started, opened))
    if stat == "cache_hit_share_pct":
        answers = [
            (s.attrs or {}).get("cache") for s in before
            if s.name == params["span"]
        ]
        hits, misses = answers.count("hit"), answers.count("miss")
        return 100.0 * hits / (hits + misses) if hits + misses else 0.0
    first = min(
        (s for s in before if s.name == params["from"]),
        key=lambda s: s.start, default=None,
    )
    if first is None:
        return None
    loop = [s for s in before if s.thread == first.thread]
    if stat == "loop_run_s":
        less = [s for s in loop if s.name in params["less"]]
        return (opened - first.start) - _covered_s(
            _clipped(less, first.start, opened)
        )
    if stat == "unattributed_share_pct":
        main = [s for s in before if s.name in params["main_path"]]
        covered = _covered_s(
            _clipped(main, started, first.start)
            + _clipped(loop, first.start, opened)
        )
        return 100.0 * (1.0 - covered / (opened - started))
    raise ValueError(f"startup_spans: unknown stat {stat!r}")
