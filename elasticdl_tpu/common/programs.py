"""Program observatory: a process-wide registry of compiled XLA programs.

Every jitted entry point in the system registers here — either by
wrapping the function with :func:`registered_jit` (the normal path) or
by reporting an already-compiled executable via
:func:`register_compiled` (bench / ad-hoc AOT).  The registry records,
per named program and per distinct aval signature:

- the wall seconds of each dispatch that compiled
  (``worker_program_compile_seconds{program}`` histogram, injectable
  clock so tests replay deterministically): trace + lowering + XLA's
  compile or the persistent cache's load, added up;
- that sum's parts, by stage, as jax's own monitoring events give them
  ("Compile stages" below): trace, lowering, XLA or cache load, and the
  persistent cache's hits and misses;
- compile / retrace counts and the distinct-signature count;
- XLA's own cost model (``cost_analysis()`` flops + bytes accessed).

It also keeps, for the latest observed compile of each program, the
abstract arguments it was compiled for, and builds ON REQUEST the
program's scope table (:meth:`ProgramRegistry.scope_table`): every
instruction a device trace can name, with the ``jax.named_scope`` path,
the phase (forward / backward / remat's rebuilt forward) and the opcode
its compiled text gives it.  ``profiler.device_ms_by_scope`` joins a
trace's per-operation seconds to it.

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
import re
import threading
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

from elasticdl_tpu.common import events
from elasticdl_tpu.common import metrics as metrics_lib
from elasticdl_tpu.common import profiler
from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger(__name__)

# How long a compile-seconds sample list is kept per program (for the
# ledger's p50/p99; the histogram metric keeps the full distribution).
_COMPILE_SAMPLES_KEPT = 256

# Signature digests shown in events/ledgers are content hashes of the
# aval signature, NOT Python hash() — byte-stable across processes.
_DIGEST_CHARS = 12

# ---- the scope table ------------------------------------------------------
#
# The compiled text gives every instruction the name stack it was traced
# under (`metadata={op_name="..."}`), e.g.
#
#   jit(step)/jvp(M)/layer_1/glm/mla/proj/q/dot_general            forward
#   jit(step)/transpose(jvp(M))/jvp(M)/checkpoint/layer_1/
#       glm/dense_ffn/up/dot_general                                backward
#   jit(step)/transpose(jvp(M))/jvp(M)/checkpoint/
#       rematted_computation/layer_0/glm/dense_ffn/tanh             rebuild
#   jit(step)/jvp(M)/glm/head_ce/while/body/closed_call/dot_general in a loop
#   jit(step)/train/optimizer/sub
#
# and a device trace names every operation by its instruction, so the
# instruction's name joins the two.  The markers below are what JAX
# 0.9 writes (`jax/_src/ad_checkpoint.py`, `interpreters/ad.py`);
# `tests/test_scope_table.py` finds each in a step compiled by the
# installed JAX, so a JAX that renames one fails a test and no metric
# silently reads 0.

REBUILD_MARKER = "rematted_computation"   # remat's second forward
BACKWARD_MARKER = "transpose("            # the transposed (backward) pass
CHECKPOINT_MARKER = "checkpoint"          # a remat block, either pass
PHASES = ("forward", "backward", "rebuild")
# an instruction whose device time is its children's, which the trace
# names one by one: counting it too counts them twice
CONTAINER_OPCODES = frozenset(("while", "conditional", "call"))

# path components that are structure, not scopes (module names such as
# `layer_1` are scopes: a rule may ask for one layer)
_STRUCTURAL = frozenset((
    CHECKPOINT_MARKER, REBUILD_MARKER, "while", "body", "cond",
    "closed_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "custom_jvp_call",
))
_BRANCH = re.compile(r"branch_\d+_fun")
_JIT_COMPONENT = re.compile(r"(?:^|/)p?jit\([^()]*\)")
_WRAPPER_OPEN = re.compile(r"\w+\(")      # jvp( transpose( vmap( ...

_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%(\S+) = (.*)$")
_OPCODE = re.compile(r"^.*? ([\w-]+)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"calls=%([^\s,)}]+)")
_TO_APPLY = re.compile(r"to_apply=%([^\s,)}]+)")
# the computations of a `while`, a `call`, a `conditional`
_CALLEES = re.compile(
    r"(?:body|condition|to_apply|true_computation|false_computation)="
    r"%([^\s,)}]+)"
)
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_NAMES = re.compile(r"%([^\s,(){}]+)")


class ScopeRow(NamedTuple):
    """One instruction a device trace can name.  `scope` is its
    `op_name` path without `jit(...)`, the transform wrappers, the
    structural components and the final primitive; `entry` the innermost
    `profiler.DEVICE_SCOPES` entry on that path ("" = none); `fused`,
    for a fusion, the catalogue entries of its fused instructions (more
    than one: the fusion spans scopes and is charged whole to the scope
    of its own metadata)."""

    opcode: str
    computation: str
    container: bool
    scope: str
    phase: str
    entry: str
    fused: Tuple[str, ...]


def split_op_name(op_name: str) -> Tuple[str, str]:
    """(scope, phase) of one `op_name`; ("", "") for a name JAX did not
    write (the phase of such an instruction is not known).  Instructions
    XLA merged carry several names joined by `;`: the first stands for
    all."""
    first = op_name.split(";", 1)[0]
    if not first.startswith(("jit(", "pjit(")):
        return "", ""    # a parameter's path or a compiler-made name
    if REBUILD_MARKER in first:
        phase = "rebuild"
    elif BACKWARD_MARKER in first:
        phase = "backward"
    else:
        phase = "forward"
    path = _WRAPPER_OPEN.sub("", _JIT_COMPONENT.sub("", first))
    parts = [c for c in path.replace(")", "").split("/") if c][:-1]
    return "/".join(
        c for c in parts
        if c not in _STRUCTURAL and not _BRANCH.fullmatch(c)
    ), phase


def parse_scope_table(hlo_text: str) -> Dict[str, ScopeRow]:
    """{instruction name: ScopeRow} of a compiled module's text, for the
    instructions a trace can name: those of the entry, loop, branch and
    called computations.  A fused computation's (and a reducer's)
    instructions never run on their own; they only give their fusion
    its `fused` entries."""
    lines = hlo_text.split("\n")
    inner = set()   # computations whose instructions no trace names
    for line in lines:
        if "calls=%" in line:
            inner.update(_CALLS.findall(line))
        elif "to_apply=%" in line and " call(" not in line:
            inner.update(_TO_APPLY.findall(line))
    split_cache: Dict[str, Tuple[str, str, str]] = {}

    def located(line):
        found = _OP_NAME.search(line)
        if found is None:
            return "", "", ""
        op_name = found.group(1)
        if op_name not in split_cache:
            scope, phase = split_op_name(op_name)
            split_cache[op_name] = (
                scope, phase, profiler.catalogue_scope(scope)
            )
        return split_cache[op_name]

    table: Dict[str, ScopeRow] = {}
    # inner computation -> the (entry, phase) pairs of its instructions
    located_in: Dict[str, set] = {}
    called_by: Dict[str, str] = {}    # loop or branch -> its container
    first_user: Dict[str, str] = {}   # the text is in schedule order
    pathless = []
    computation, is_inner = "", False
    for line in lines:
        if not line.startswith(" "):
            header = _COMPUTATION.match(line)
            if header is not None:
                computation = header.group(1)
                is_inner = computation in inner
                if is_inner:
                    located_in[computation] = set()
            continue
        if is_inner:
            _, phase, entry = located(line)
            if entry:
                located_in[computation].add((entry, phase))
            for callee in _CALLS.findall(line):   # a fusion in a fusion
                located_in[computation] |= located_in.get(callee, set())
            continue
        instruction = _INSTRUCTION.match(line)
        if instruction is None:
            continue
        name, rest = instruction.groups()
        opcode = _OPCODE.match(rest)
        for operand in _NAMES.findall(rest, opcode.end() if opcode else 0):
            first_user.setdefault(operand, name)
        opcode = opcode.group(1) if opcode else ""
        scope, phase, entry = located(rest)
        fused: Tuple[str, ...] = ()
        if opcode == "fusion":
            inside = set().union(*(
                located_in.get(c, ()) for c in _CALLS.findall(rest)
            ))
            fused = tuple(sorted({e for e, _ in inside}))
            if not scope and len(fused) == 1:
                # no path of its own, and its instructions agree on one
                phases = {p for _, p in inside}
                scope = entry = fused[0]
                phase = phases.pop() if len(phases) == 1 else ""
        elif opcode in CONTAINER_OPCODES:
            branches = ",".join(_BRANCHES.findall(rest)).replace("%", "")
            for callee in _CALLEES.findall(rest) + branches.split(","):
                called_by[callee.strip()] = name
        if not scope:
            pathless.append(name)
        table[name] = ScopeRow(
            opcode, computation, opcode in CONTAINER_OPCODES,
            scope, phase, entry, fused,
        )
    # What the compiler made itself has no path (layout copies, zeroed
    # buffers, asynchronous halves): it is charged to its first user,
    # the operation it was made for, else to the loop or branch it lies
    # in.  Users and containers come later in the text, so the reversed
    # order has settled them, pathless ones included, before they are
    # asked.  A kernel the compiler names after a primitive takes the
    # scope this program calls that primitive under.
    for name in reversed(pathless):
        row = table[name]
        owner = table.get(first_user.get(name, ""))
        if owner is None or not owner.scope:
            owner = table.get(called_by.get(row.computation, ""))
        if owner is not None and owner.scope:
            row = row._replace(
                scope=owner.scope, phase=owner.phase, entry=owner.entry
            )
        for prefix, entry in profiler.COMPILER_NAMED_SCOPES.items():
            if name.startswith(prefix):
                row = row._replace(
                    scope=f"{row.scope}/{entry}".lstrip("/"), entry=entry
                )
        table[name] = row
    return table


# ---- compile stages -------------------------------------------------------
#
# jax (0.9) times every compile itself and hands the seconds to whoever
# listens (`jax.monitoring`): on the dispatching thread, in this order,
#
#   jaxpr_trace_duration            the Python trace, once for every jitted
#                                   function called INSIDE the traced body
#                                   (`sin`, `matmul`, ...), then for the
#                                   outermost, the one that counts, and then
#                                   for what a lowering rule traces (they END
#                                   inside the lowering's seconds)
#   jaxpr_to_mlir_module_duration   the lowering
#   compile_requests_use_cache      where a persistent cache is on, then
#   cache_hits                      on a hit, with
#   cache_retrieval_time_sec        the load's seconds
#   backend_compile_duration        XLA's compile, or that load
#
# (`/jax/compilation_cache/cache_misses` fires only where the new entry is
# WRITTEN, past the cache's size and compile-time thresholds: a request
# that no hit follows is the miss here.)  `fun_name` cannot say whose
# compile it is: every registered program's is `_observed`, the wrapper's,
# and that name is in the lowered module, so in every persistent-cache key.
# What can: the events fire on the thread that dispatches, and
# `RegisteredProgram` says on that thread which program it is dispatching
# (`_compiling.owner`).  A compile with no owner is unregistered: an eager
# `jnp` operation, a bare `jax.jit`.  `tests/test_startup_spans.py` finds
# each event fired by the installed jax, so a jax that renames one fails a
# test and no counter silently reads 0.

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
XLA_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
COMPILE_EVENTS = (
    TRACE_EVENT, LOWER_EVENT, XLA_EVENT, CACHE_REQUEST_EVENT,
    CACHE_HIT_EVENT, CACHE_RETRIEVAL_EVENT,
)
STAGES = ("trace", "lower", "xla")
#: the `program` of a compile span that belongs to no registered program
UNREGISTERED = "(unregistered)"


class _Compiling(threading.local):
    """The compile in progress on this thread, as the listeners know it."""

    owner = None        # (registry, program name) while one dispatches
    lower = None        # (start, end) of the lowering
    cache = "off"       # -> "miss" at the request -> "hit"
    retrieval_s = 0.0
    stages = None       # {stage: seconds, "cache": ...} summed for `owner`

    def __init__(self):     # once a thread, at its first use
        # [(start, end)] on perf_counter of the trace events since the
        # last compile that no later one encloses, in order
        self.traces = []

    def begin(self, owner):
        """Arm `owner` (None disarms); returns what was armed before.  A
        trace left over from before (an `eval_shape`) is not this one's."""
        previous = self.owner, self.stages
        self.owner, self.stages = owner, None
        self.traces, self.lower = [], None
        return previous

    def end(self, previous) -> Optional[dict]:
        """Back to `previous`; the stages of what compiled meanwhile."""
        stages = self.stages
        self.owner, self.stages = previous
        self.traces, self.lower = [], None
        return stages


_compiling = _Compiling()
_TRACES_KEPT = 4096
_listeners_lock = threading.Lock()
_listeners_installed = False


def _on_duration(event: str, seconds: float, **_kwargs) -> None:
    if event == TRACE_EVENT:
        end = time.perf_counter()
        traces = _compiling.traces
        # an outer trace ends after the inner ones it encloses: the newest
        while traces and traces[-1][0] >= end - seconds:
            traces.pop()
        traces.append((end - seconds, end))
        # bounded for a thread that only ever traces (`eval_shape`); what
        # a lowering traces comes after the compile's own, by the hundred
        del traces[:-_TRACES_KEPT]
    elif event == LOWER_EVENT:
        end = time.perf_counter()
        _compiling.lower = (end - seconds, end)
        _compiling.cache, _compiling.retrieval_s = "off", 0.0
    elif event == CACHE_RETRIEVAL_EVENT:
        _compiling.retrieval_s = seconds
    elif event == XLA_EVENT:
        try:
            _compile_done(time.perf_counter(), seconds)
        except Exception:   # a listener must never fail the compile
            logger.exception("compile stages: not recorded")


def _on_event(event: str, **_kwargs) -> None:
    if event == CACHE_REQUEST_EVENT:
        _compiling.cache = "miss"
    elif event == CACHE_HIT_EVENT:
        _compiling.cache = "hit"


def _compile_done(now: float, xla_s: float) -> None:
    """One compile has ended on this thread: its three stages as child
    spans of whatever span the thread is in, laid back from each event's
    stamp by its seconds, and their seconds into the owner's ledger."""
    state = _compiling
    traces, lower, cache = state.traces, state.lower, state.cache
    state.traces, state.lower, state.cache = [], None, "off"
    # the compile's own trace: the last that ended before its lowering began
    before = [t for t in traces if lower is None or t[1] <= lower[0]]
    trace = before[-1] if before else None
    registry, name = state.owner or (default_program_registry(), None)
    program = name or UNREGISTERED
    timer = profiler.process_phase_timer()
    seconds = dict.fromkeys(STAGES, 0.0)
    for stage, region in (("trace", trace), ("lower", lower)):
        if region is not None:
            seconds[stage] = region[1] - region[0]
            timer.add("compile_" + stage, seconds[stage], region[0],
                      program=program)
    seconds["xla"] = xla_s
    attrs = {"program": program, "cache": cache}
    if cache == "hit":
        attrs["retrieval_s"] = state.retrieval_s
    timer.add("compile_xla", xla_s, now - xla_s, **attrs)
    registry.note_compile_stages(name, seconds, cache)
    if name is not None:
        summed = state.stages or dict.fromkeys(STAGES, 0.0)
        for stage in STAGES:
            summed[stage] += seconds[stage]
        summed["cache"] = cache
        state.stages = summed


def install_compile_listeners() -> None:
    """Register the process's listeners on jax's compile events, once
    however often it is asked (every `RegisteredProgram` asks; a process's
    entry point asks first, so that what compiles before its first
    registered program is seen too).  They fire at compiles only."""
    global _listeners_installed
    with _listeners_lock:
        if _listeners_installed:
            return
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _listeners_installed = True


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


def abstract_arguments(args):
    """`args` with every array leaf replaced by its ShapeDtypeStruct
    (sharding and weak type kept); reads no data, so donated (deleted)
    arrays are fine."""
    import jax

    def abstract(x):
        if getattr(x, "shape", None) is None or not hasattr(x, "dtype"):
            return x
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=getattr(x, "sharding", None),
            weak_type=bool(getattr(x, "weak_type", False)),
        )

    return jax.tree_util.tree_map(abstract, args)


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
        "stage_seconds": dict.fromkeys(STAGES, 0.0),
        "cache": {"hit": 0, "miss": 0},
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
        self._on_storm = on_storm
        reg = metrics or metrics_lib.default_registry()
        self._compile_hist = reg.histogram(
            "worker_program_compile_seconds",
            "wall seconds of a dispatch that compiled, per registered "
            "program: trace + lowering + XLA compile or cache load",
            min_value=1e-3, max_value=900.0, labelnames=("program",),
        )
        self._stage_seconds_total = reg.counter(
            "worker_program_compile_stage_seconds_total",
            "seconds of a registered program's compiles by stage (trace, "
            "lower, xla: XLA's compile or the persistent cache's load)",
            labelnames=("program", "stage"),
        )
        self._cache_requests_total = reg.counter(
            "worker_program_cache_requests_total",
            "a registered program's compile requests answered by the "
            "persistent cache (hit) or compiled (miss)",
            labelnames=("program", "result"),
        )
        self._unregistered_compiles_total = reg.counter(
            "worker_unregistered_compiles_total",
            "compiles that belong to no registered program (eager "
            "operations, bare jax.jit)",
        )
        self._unregistered_seconds_total = reg.counter(
            "worker_unregistered_compile_seconds_total",
            "trace + lowering + XLA or cache load seconds of those",
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
        self._scope_builds_total = reg.counter(
            "worker_program_scope_table_builds_total",
            "scope tables built on request (a compile of its own, counted "
            "nowhere else)",
            labelnames=("program",),
        )
        # name -> (RegisteredProgram, abstract arguments) of the latest
        # observed compile, and the table built from it
        self._scope_sources: Dict[str, Tuple[Any, tuple]] = {}
        self._scope_tables: Dict[str, Dict[str, "ScopeRow"]] = {}

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
        stages: Optional[dict] = None,
    ) -> None:
        """Record one compile of `name` for aval-signature digest
        `signature`.  Called by RegisteredProgram after every AOT
        compile and by register_compiled for external executables.
        `stages` ({stage: seconds, "cache": ...}, what the listeners
        gathered during the dispatch) rides the event; the ledger has
        it already (`note_compile_stages`)."""
        flops, bytes_ = _flops_bytes(cost or {})
        stages = stages or {}
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
            **{
                stage + "_seconds": round(stages.get(stage, 0.0), 4)
                for stage in STAGES
            },
            cache=stages.get("cache", "off"),
        )

    def note_compile_stages(self, name: Optional[str], seconds: dict,
                            cache: str) -> None:
        """The stages of one compile, as the process's listeners read
        them: {stage: seconds} and the persistent cache's answer (`hit`,
        `miss`, or `off` where none was asked).  `name` None: a compile
        of no registered program, counted and kept in no record."""
        if name is None:
            self._unregistered_compiles_total.inc()
            self._unregistered_seconds_total.inc(sum(seconds.values()))
            return
        with self._lock:
            rec = self._programs.setdefault(name, _new_record())
            for stage in STAGES:
                rec["stage_seconds"][stage] += seconds[stage]
            if cache in rec["cache"]:
                rec["cache"][cache] += 1
        for stage in STAGES:
            self._stage_seconds_total.labels(
                program=name, stage=stage
            ).inc(seconds[stage])
        if cache != "off":
            self._cache_requests_total.labels(
                program=name, result=cache
            ).inc()

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

    # -- the scope table ---------------------------------------------

    def keep_compiled_for(self, program: "RegisteredProgram", args) -> None:
        """Remember what `program` was just compiled for: shape, dtype
        and sharding of every leaf, no array.  Called on an OBSERVED
        compile only; the latest one of a name is the one a trace
        shows.  The program itself (its function's closure: a model, an
        optimizer) stays alive with it until the name compiles again:
        the table is asked for after the job that ran it has ended."""
        abstract = abstract_arguments(args)
        with self._lock:
            self._scope_sources[program.name] = (program, abstract)
            self._scope_tables.pop(program.name, None)

    def scope_table(self, name: str) -> Optional[Dict[str, ScopeRow]]:
        """`parse_scope_table` of program `name` as last compiled, built
        on the first request and kept; None for a program this process
        has not compiled (or cannot compile ahead of time).  The build
        lowers and compiles from the kept abstract arguments: with the
        persistent cache warm that loads the executable that ran.  It
        takes seconds and a text of many megabytes, so never ask on the
        hot path; it is not a compile of the job (no ledger entry, no
        storm)."""
        with self._lock:
            table = self._scope_tables.get(name)
            source = self._scope_sources.get(name)
        if table is not None or source is None:
            return table
        program, abstract = source
        start = time.perf_counter()
        try:
            text = program.compiled_text(*abstract)
        except Exception:
            logger.exception("scope table of %s: cannot compile", name)
            return None
        compiled_s = time.perf_counter() - start
        table = parse_scope_table(text)
        self._scope_builds_total.labels(program=name).inc()
        logger.info(
            "scope table of %s: %d instructions from %.1f MB of text, "
            "compile or cache load %.1fs, parse %.1fs", name, len(table),
            len(text) / 1e6, compiled_s,
            time.perf_counter() - start - compiled_s,
        )
        with self._lock:
            if self._scope_sources.get(name) is source:
                self._scope_tables[name] = table
        return table

    # -- views --------------------------------------------------------

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
                    **{
                        stage + "_seconds": round(total, 6)
                        for stage, total in rec["stage_seconds"].items()
                    },
                    "cache_hits": rec["cache"]["hit"],
                    "cache_misses": rec["cache"]["miss"],
                    "flops_per_execution": latest.get("flops", 0.0),
                    "bytes_per_execution": latest.get("bytes", 0.0),
                    "avals": latest.get("avals", ""),
                }
        return out

    def summary(self) -> dict:
        """The /varz "programs" payload: headline totals + the full
        ledger (what `elasticdl programs` renders)."""
        led = self.ledger()
        return {
            "programs": len(led),
            "compiles_total": sum(p["compiles"] for p in led.values()),
            "signatures_total": sum(p["signatures"] for p in led.values()),
            "storms_total": sum(p["storms"] for p in led.values()),
            "ledger": led,
        }

    def forensics(self) -> dict:
        """The incident-bundle `programs.json` section.  Ledger minus
        what mixes in the machine's state — every wall-time field, and
        the persistent cache's hits and misses (a second run finds what
        the first wrote) — since bundles must be byte-identical across
        same-seed runs (the flight-recorder discipline)."""
        led = self.ledger()
        return {"ledger": {
            name: {
                k: v for k, v in rec.items()
                if "_seconds" not in k and not k.startswith("cache_")
            }
            for name, rec in led.items()
        }}


class RegisteredProgram:
    """A jitted callable whose compiles are observed and reported to
    the ProgramRegistry.

    Dispatch is the plain jitted function — unchanged semantics.  The
    wrapped body calls a trace-time hook; a dispatch during which the
    hook fired is a compile, and the wrapper's clock around that
    dispatch is the recorded compile wall time (trace + lowering + XLA
    compile or cache load; execution is dispatched asynchronously).
    While it dispatches, or compiles ahead of time, the process's
    compile listeners charge what compiles on this thread to its name
    (`_compiling.owner`).  Calls under an outer
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

        install_compile_listeners()
        self.name = name
        self._registry = registry
        self._owner = (registry, name)
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
        armed = _compiling.begin(self._owner)
        start = clock()
        try:
            out = self._jitted(*args)
        finally:
            tls.cell = prev
            stages = _compiling.end(armed)
        if cell:
            self._record(sig, max(clock() - start, 0.0), avals, cost=None,
                         stages=stages)
            self._registry.keep_compiled_for(self, args)
        return out

    def compiled_text(self, *args) -> str:
        """The optimized HLO text of the executable for `args` (arrays
        or ShapeDtypeStructs).  No compile of the ledger's (no count, no
        signature, no storm); its stages are this program's all the same:
        a load, where the persistent cache is warm."""
        armed = _compiling.begin(self._owner)
        try:
            return self._jitted.lower(*args).compile().as_text()
        finally:
            _compiling.end(armed)

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
        armed = _compiling.begin(self._owner)
        start = clock()
        try:
            compiled = self._jitted.lower(*args).compile()
        except Exception:
            # multi-process backends cannot AOT-compile; cost queries
            # degrade to {} rather than breaking the caller
            compiled = None
        finally:
            stages = _compiling.end(armed)
        seconds = max(clock() - start, 0.0)
        with self._lock:
            self._aot[sig] = compiled
        if compiled is not None:
            self._record(
                sig, seconds, describe_avals(args),
                cost=cost_analysis_dict(compiled), stages=stages,
            )
        return compiled

    def _record(self, sig, seconds, avals, cost, stages=None) -> None:
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
            cost=cost, avals=avals, stages=stages,
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
