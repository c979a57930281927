"""Program observatory: a process-wide registry of compiled XLA programs.

Every jitted entry point in the system registers here — either by
wrapping the function with :func:`registered_jit` (the normal path) or
by reporting an already-compiled executable via
:func:`register_compiled` (bench / ad-hoc AOT).  The registry records,
per named program and per distinct aval signature:

- compile wall seconds (``worker_program_compile_seconds{program}``
  histogram, injectable clock so tests replay deterministically);
- compile / retrace counts and the distinct-signature count;
- XLA's own cost model (``cost_analysis()`` flops + bytes accessed).

Joining per-program cost against the step-rate telemetry the worker
already publishes (``bind_step_rate``) turns the static ledger into
live ``worker_program_bytes_per_sec`` / ``worker_mfu_ratio`` /
``worker_hbm_utilization_ratio`` gauges: the memory-wall numbers the
perf roadmap is navigated by, visible on /varz while training runs
instead of once per bench round.

Retrace detection closes the loop: a program whose distinct-signature
count exceeds its declared budget (serving-engine buckets declare
theirs) within ``storm_window_s`` emits a ``recompile_storm`` span
event and fires the ``on_storm`` hook — wired by the FlightRecorder to
capture an incident bundle with a ``programs.json`` ledger section.

Dispatch contract of :class:`RegisteredProgram`: every call goes
through the plain ``jax.jit`` callable — byte-identical semantics to
the unregistered code (donation, sharding resolution, multi-process
SPMD, the virtual-mesh CPU backend).  Compiles are OBSERVED, not
re-routed: a trace-time hook inside the wrapped function marks the
dispatches that traced, and the wrapper's clock around that dispatch
is the compile wall time.  (An earlier AOT-dispatch design — call the
``lower().compile()`` executable directly — died in testing:
``Compiled.__call__`` hard-aborts the process on the virtual-mesh
remesh path and cannot compile multi-process CPU programs at all.)

AOT executables still exist, but only where they existed before this
layer: explicit :meth:`RegisteredProgram.aot_compile` (the prewarm
path) and :meth:`RegisteredProgram.cost_for` (the bench path) build
one per signature, cache it, record its compile, and harvest
``cost_analysis()`` into the ledger — never dispatching it.
"""

from __future__ import annotations

import hashlib
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from elasticdl_tpu.common import events
from elasticdl_tpu.common import metrics as metrics_lib

# How long a compile-seconds sample list is kept per program (for the
# ledger's p50/p99; the histogram metric keeps the full distribution).
_COMPILE_SAMPLES_KEPT = 256

# Signature digests shown in events/ledgers are content hashes of the
# aval signature, NOT Python hash() — byte-stable across processes.
_DIGEST_CHARS = 12


# Datasheet peaks keyed by the exact `device_kind` string the chip
# reports.  One row per kind that has actually been read off a device:
#   "TPU v5 lite" — Google Cloud documentation, "TPU v5e": 197 TFLOP/s
#   bf16, 819 GB/s HBM per chip.
_DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def device_peaks() -> Optional[dict]:
    """Datasheet peak numbers for MFU / bandwidth rooflines of the live
    telemetry.  The CPU platform has no peaks (None:
    the ratio gauges read 0.0); an accelerator whose `device_kind` is
    not in the table is an error, never a guessed row."""
    import jax

    device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    peaks = _DEVICE_PEAKS.get(device.device_kind)
    if peaks is None:
        raise ValueError(
            f"no peaks row for device_kind {device.device_kind!r} "
            f"(platform {device.platform!r}); add it to "
            "programs._DEVICE_PEAKS with its source"
        )
    return peaks


def cost_analysis_dict(compiled) -> dict:
    """flops / bytes-accessed from XLA's own cost model."""
    return dict(compiled.cost_analysis() or {})


def _flops_bytes(cost: dict) -> Tuple[float, float]:
    return (
        float(cost.get("flops", 0.0) or 0.0),
        float(cost.get("bytes accessed", 0.0) or 0.0),
    )


def _quantile(sorted_values: List[float], q: float) -> float:
    if not sorted_values:
        return 0.0
    idx = min(int(q * len(sorted_values)), len(sorted_values) - 1)
    return sorted_values[idx]


def _sharding_key(x) -> Tuple[str, Tuple[int, ...]]:
    s = getattr(x, "sharding", None)
    if s is None:
        return ("", ())
    try:
        ids = tuple(sorted(d.id for d in s.device_set))
    except Exception:
        ids = ()
    return (str(s), ids)


def _leaf_key(x):
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        weak = bool(getattr(getattr(x, "aval", None), "weak_type", False))
        return (tuple(shape), str(dtype), weak, _sharding_key(x))
    return ("py", type(x).__name__)


def signature_of(args) -> tuple:
    """Hashable aval signature of a positional-args tuple: pytree
    structure + per-leaf (shape, dtype, weak_type, sharding).  Two calls
    with the same signature reuse one compiled executable; a new
    signature is a retrace."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (str(treedef), tuple(_leaf_key(leaf) for leaf in leaves))


def signature_digest(sig: tuple) -> str:
    return hashlib.sha1(repr(sig).encode()).hexdigest()[:_DIGEST_CHARS]


def describe_avals(args, limit: int = 8) -> str:
    """Human-readable aval summary ("float32[65536,26], int32[64]")."""
    import jax
    import numpy as np

    leaves = jax.tree_util.tree_leaves(args)
    parts = []
    for leaf in leaves[:limit]:
        dtype = getattr(leaf, "dtype", None)
        if dtype is not None:
            dims = ",".join(str(d) for d in getattr(leaf, "shape", ()))
            parts.append(f"{np.dtype(dtype).name}[{dims}]")
        else:
            parts.append(type(leaf).__name__)
    if len(leaves) > limit:
        parts.append(f"...+{len(leaves) - limit}")
    return ", ".join(parts)


def _has_tracers(args) -> bool:
    import jax

    return any(
        isinstance(leaf, jax.core.Tracer)
        for leaf in jax.tree_util.tree_leaves(args)
    )


def _new_record() -> dict:
    return {
        "signatures": {},
        "compiles": 0,
        "compile_seconds": [],
        "storms": 0,
        "budget": None,
        "latest": None,
    }


class ProgramRegistry:
    """Process-wide ledger of named compiled programs.

    Thread-safe; compiles themselves run outside the lock (they take
    seconds-to-minutes).  The injectable ``clock`` times compiles and
    stamps signature first-seen times for storm detection, so the storm
    tests replay deterministically under a fake clock."""

    def __init__(
        self,
        clock: Callable[[], float] = time.monotonic,
        metrics: Optional[metrics_lib.MetricsRegistry] = None,
        storm_window_s: float = 60.0,
        on_storm: Optional[Callable[[dict], None]] = None,
    ):
        self.clock = clock
        self.storm_window_s = float(storm_window_s)
        self._lock = threading.Lock()
        self._programs: Dict[str, dict] = {}
        self._rates: Dict[str, Tuple[Callable[[], float], int]] = {}
        self._on_storm = on_storm
        reg = metrics or metrics_lib.default_registry()
        self._compile_hist = reg.histogram(
            "worker_program_compile_seconds",
            "XLA compile wall seconds per registered program",
            min_value=1e-3, max_value=900.0, labelnames=("program",),
        )
        self._compiles_total = reg.counter(
            "worker_program_compiles_total",
            "XLA compiles (first compile + every retrace) per program",
            labelnames=("program",),
        )
        self._signatures_gauge = reg.gauge(
            "worker_program_signatures_count",
            "distinct aval signatures seen per registered program",
            labelnames=("program",),
        )
        self._storms_total = reg.counter(
            "worker_program_storms_total",
            "recompile storms (signature budget blown within the window)",
            labelnames=("program",),
        )
        reg.gauge_fn(
            "worker_program_bytes_per_sec",
            lambda: self.live()["bytes_per_sec"],
            "cost-model bytes/s across rate-bound programs (cost x rate)",
        )
        reg.gauge_fn(
            "worker_mfu_ratio",
            lambda: self.live()["mfu"],
            "cost-model flops/s over the device datasheet peak (0 off-TPU)",
        )
        reg.gauge_fn(
            "worker_hbm_utilization_ratio",
            lambda: self.live()["hbm_utilization"],
            "cost-model bytes/s over the device HBM roof (0 off-TPU)",
        )

    # -- recording ----------------------------------------------------

    def declare(self, name: str, budget: Optional[int] = None) -> None:
        """Ensure a program record exists; optionally (re)declare its
        signature budget (latest declaration wins)."""
        with self._lock:
            rec = self._programs.setdefault(name, _new_record())
            if budget is not None:
                rec["budget"] = int(budget)

    def set_on_storm(self, hook: Optional[Callable[[dict], None]]) -> None:
        with self._lock:
            self._on_storm = hook

    def note_compile(
        self,
        name: str,
        signature: str,
        seconds: float,
        cost: Optional[dict] = None,
        avals: str = "",
    ) -> None:
        """Record one compile of `name` for aval-signature digest
        `signature`.  Called by RegisteredProgram after every AOT
        compile and by register_compiled for external executables."""
        flops, bytes_ = _flops_bytes(cost or {})
        with self._lock:
            rec = self._programs.setdefault(name, _new_record())
            sig = rec["signatures"].setdefault(
                signature,
                {"compiles": 0, "seconds": 0.0, "flops": 0.0,
                 "bytes": 0.0, "avals": ""},
            )
            sig["compiles"] += 1
            sig["seconds"] = round(sig["seconds"] + seconds, 6)
            if cost:
                # dispatch-path compiles carry no cost model (only the
                # AOT cost/prewarm queries do) — never zero a known cost
                sig["flops"] = flops
                sig["bytes"] = bytes_
            if avals:
                sig["avals"] = avals
            rec["compiles"] += 1
            rec["compile_seconds"].append(round(seconds, 6))
            del rec["compile_seconds"][:-_COMPILE_SAMPLES_KEPT]
            rec["latest"] = signature
            n_sigs = len(rec["signatures"])
        self._compile_hist.labels(program=name).record(max(seconds, 1e-9))
        self._compiles_total.labels(program=name).inc()
        self._signatures_gauge.labels(program=name).set(n_sigs)
        events.emit(
            events.PROGRAM_COMPILED,
            program=name,
            signature=signature,
            seconds=round(seconds, 4),
            flops=flops,
            bytes=bytes_,
            signatures=n_sigs,
        )

    def note_storm(self, name: str, signatures: int, budget: int) -> None:
        """A program blew its signature budget within the window: bump
        the ledger, emit the closed-vocab event, fire the hook (the
        FlightRecorder's immediate pend+flush)."""
        with self._lock:
            rec = self._programs.setdefault(name, _new_record())
            rec["storms"] += 1
            hook = self._on_storm
        record = {
            "program": name,
            "signatures": int(signatures),
            "budget": int(budget),
        }
        self._storms_total.labels(program=name).inc()
        events.emit(events.RECOMPILE_STORM, **record)
        if hook is not None:
            try:
                hook(dict(record))
            except Exception:
                pass

    def bind_step_rate(
        self,
        name: str,
        rate_fn: Callable[[], float],
        steps_per_execution: int = 1,
    ) -> None:
        """Join a program's per-execution cost against a live step rate
        (optimizer steps/sec).  `steps_per_execution` scales fused
        programs whose one execution advances K steps."""
        with self._lock:
            self._rates[name] = (rate_fn, max(int(steps_per_execution), 1))

    # -- views --------------------------------------------------------

    def live(self) -> dict:
        """Live cost x rate attribution across rate-bound programs."""
        with self._lock:
            bound = list(self._rates.items())
            latest: Dict[str, dict] = {}
            for name, _ in bound:
                rec = self._programs.get(name)
                if rec and rec["latest"] is not None:
                    latest[name] = dict(rec["signatures"][rec["latest"]])
        flops_rate = bytes_rate = 0.0
        for name, (rate_fn, spe) in bound:
            cost = latest.get(name)
            if not cost:
                continue
            try:
                rate = float(rate_fn() or 0.0)
            except Exception:
                rate = 0.0
            flops_rate += cost["flops"] * rate / spe
            bytes_rate += cost["bytes"] * rate / spe
        # no rate-bound program means this process runs none (the
        # master): asking for peaks would initialise a backend there and
        # take the chips its workers need
        peaks = device_peaks() if bound else None
        return {
            "flops_per_sec": flops_rate,
            "bytes_per_sec": bytes_rate,
            "mfu": flops_rate / peaks["bf16_flops"] if peaks else 0.0,
            "hbm_utilization": (
                bytes_rate / peaks["hbm_bytes_per_s"] if peaks else 0.0
            ),
        }

    def ledger(self) -> dict:
        """Per-program ledger: compiles, signatures, budget, storms,
        compile-time quantiles, latest-signature cost."""
        with self._lock:
            names = sorted(self._programs)
            records = {name: self._programs[name] for name in names}
            out = {}
            for name in names:
                rec = records[name]
                times = sorted(rec["compile_seconds"])
                latest = (
                    rec["signatures"][rec["latest"]]
                    if rec["latest"] is not None else {}
                )
                out[name] = {
                    "compiles": rec["compiles"],
                    "signatures": len(rec["signatures"]),
                    "budget": rec["budget"],
                    "storms": rec["storms"],
                    "compile_seconds_total": round(sum(times), 6),
                    "compile_seconds_p50": _quantile(times, 0.5),
                    "compile_seconds_p99": _quantile(times, 0.99),
                    "flops_per_execution": latest.get("flops", 0.0),
                    "bytes_per_execution": latest.get("bytes", 0.0),
                    "avals": latest.get("avals", ""),
                }
        return out

    def summary(self) -> dict:
        """The /varz "programs" payload: headline totals + live rates +
        the full ledger (what `elasticdl programs` renders)."""
        led = self.ledger()
        live = self.live()
        return {
            "programs": len(led),
            "compiles_total": sum(p["compiles"] for p in led.values()),
            "signatures_total": sum(p["signatures"] for p in led.values()),
            "storms_total": sum(p["storms"] for p in led.values()),
            "mfu": round(live["mfu"], 6),
            "bytes_per_sec": round(live["bytes_per_sec"], 1),
            "hbm_utilization": round(live["hbm_utilization"], 6),
            "ledger": led,
        }

    def forensics(self) -> dict:
        """The incident-bundle `programs.json` section.  Ledger minus
        live rates and compile wall-time quantiles — both mix in
        wall-clock state, and bundles must be byte-identical across
        same-seed runs (the flight-recorder discipline)."""
        led = self.ledger()
        return {"ledger": {
            name: {
                k: v for k, v in rec.items()
                if not k.startswith("compile_seconds")
            }
            for name, rec in led.items()
        }}


class RegisteredProgram:
    """A jitted callable whose compiles are observed and reported to
    the ProgramRegistry.

    Dispatch is the plain jitted function — unchanged semantics.  The
    wrapped body calls a trace-time hook; a dispatch during which the
    hook fired is a compile, and the wrapper's clock around that
    dispatch is the recorded compile wall time (trace + XLA compile;
    execution is dispatched asynchronously).  Calls under an outer
    trace (tracer arguments) inline without activating the hook slot,
    so nested tracing is not miscounted as a compile.

    Thread-safe: the hook slot is thread-local (jit traces on the
    dispatching thread), and ledger/storm state is lock-guarded.  Under
    concurrent first-calls jax's own jit cache serializes the compile;
    whichever dispatches observe a trace record it."""

    def __init__(
        self,
        name: str,
        fn: Callable,
        registry: ProgramRegistry,
        signature_budget: Optional[int] = None,
        **jit_kwargs,
    ):
        import jax

        self.name = name
        self._registry = registry
        self._budget = signature_budget
        self._tls = threading.local()

        def _observed(*a, **k):
            # trace-time side effect: runs once per trace, never on the
            # executed hot path (the serving engine's compile counter
            # uses the same pattern)
            cell = getattr(self._tls, "cell", None)
            if cell is not None:
                cell.append(1)
            return fn(*a, **k)

        self._jitted = jax.jit(_observed, **jit_kwargs)
        self._lock = threading.Lock()
        self._aot: Dict[tuple, Any] = {}
        self._sig_times: List[float] = []
        self._seen: Dict[tuple, bool] = {}
        self._stormed = False
        registry.declare(name, signature_budget)

    @property
    def signature_count(self) -> int:
        with self._lock:
            return len(self._seen)

    def __call__(self, *args, **kwargs):
        if kwargs or _has_tracers(args):
            # under an outer trace (fused timing loops) or a kwargs
            # call: dispatch without arming the hook slot — an inline
            # nested trace is not an XLA compile
            return self._jitted(*args, **kwargs)
        sig = signature_of(args)
        avals = describe_avals(args)
        clock = self._registry.clock
        tls = self._tls
        prev = getattr(tls, "cell", None)
        cell: List[int] = []
        tls.cell = cell
        start = clock()
        try:
            out = self._jitted(*args)
        finally:
            tls.cell = prev
        if cell:
            self._record(sig, max(clock() - start, 0.0), avals, cost=None)
        return out

    def aot_compile(self, *args):
        """Build (once per signature) the AOT executable — the prewarm
        path (accepts ShapeDtypeStructs like .lower()) — recording the
        compile and harvesting its cost model into the ledger.  The
        executable is cached and returned but never dispatched; the
        call path benefits via the persistent XLA compile cache."""
        return self._aot_for(args)

    def cost_for(self, *args) -> dict:
        """cost_analysis() dict for this signature,
        AOT-compiling (once, recorded) if no executable is cached —
        the bench path, and the source of the ledger's flops/bytes."""
        compiled = self._aot_for(args)
        if compiled is None:
            return {}
        return cost_analysis_dict(compiled)

    def _aot_for(self, args):
        sig = signature_of(args)
        with self._lock:
            if sig in self._aot:
                return self._aot[sig]
        clock = self._registry.clock
        start = clock()
        try:
            compiled = self._jitted.lower(*args).compile()
        except Exception:
            # multi-process backends cannot AOT-compile; cost queries
            # degrade to {} rather than breaking the caller
            compiled = None
        seconds = max(clock() - start, 0.0)
        with self._lock:
            self._aot[sig] = compiled
        if compiled is not None:
            self._record(
                sig, seconds, describe_avals(args),
                cost=cost_analysis_dict(compiled),
            )
        return compiled

    def _record(self, sig, seconds, avals, cost) -> None:
        clock = self._registry.clock
        now = clock()
        with self._lock:
            new_sig = sig not in self._seen
            if new_sig:
                self._seen[sig] = True
                self._sig_times.append(now)
            window = self._registry.storm_window_s
            recent = [t for t in self._sig_times if now - t <= window]
            storm = (
                new_sig
                and self._budget is not None
                and len(recent) > self._budget
                and not self._stormed
            )
            if storm:
                self._stormed = True
            churn = len(self._sig_times)
        self._registry.note_compile(
            self.name, signature_digest(sig), seconds,
            cost=cost, avals=avals,
        )
        if storm:
            self._registry.note_storm(self.name, churn, self._budget)


_DEFAULT_LOCK = threading.Lock()
_default: Optional[ProgramRegistry] = None


def default_program_registry() -> ProgramRegistry:
    global _default
    with _DEFAULT_LOCK:
        if _default is None:
            _default = ProgramRegistry()
        return _default


def registered_jit(
    name: str,
    fn: Callable,
    registry: Optional[ProgramRegistry] = None,
    signature_budget: Optional[int] = None,
    **jit_kwargs,
) -> RegisteredProgram:
    """The normal registration path: wrap `fn` as a named registered
    program.  Extra kwargs (donate_argnums, out_shardings, ...) pass
    through to jax.jit unchanged."""
    return RegisteredProgram(
        name,
        fn,
        registry or default_program_registry(),
        signature_budget=signature_budget,
        **jit_kwargs,
    )


def register_compiled(
    name: str,
    compiled: Any,
    seconds: float = 0.0,
    registry: Optional[ProgramRegistry] = None,
    signature: str = "external",
    avals: str = "",
):
    """Report an executable compiled outside registered_jit (explicit
    lowered.compile() flows).  Returns the executable unchanged."""
    reg = registry or default_program_registry()
    reg.note_compile(
        name, signature, seconds,
        cost=cost_analysis_dict(compiled), avals=avals,
    )
    return compiled
