"""The `train` driver: `elasticdl train --distribution_strategy Local` in
this process, read from outside.

Nothing of the program changes.  The driver records the `Worker`, the
`ModelOwner` and the `Master` the CLI builds (as `chip_smoke.py`'s
`capture_instances` does), appends one object to the zoo spec's callbacks
and reads the job through `on_task_start` / `on_task_end`.  `on_task_end`
fires after the worker's per-task loss fetch, so it is the one stamp of the
normal path at which the device has finished what the host has counted.

    warm-up   whole tasks until the train step has compiled and
              `warmup_tasks_after_compile` more tasks have ended
    window    opens at that task end, closes at the first task end past
              `--seconds`; one reading a task (benchmarks/stats.py)
    trace     with `--trace 1`, `trace_tasks` more whole tasks run under the
              profiler after the window has closed, so the rates read in the
              window are never taken with the profiler on
    check     after the job: one more step of the job's own train step,
              its loss, gradient and update against
              benchmarks/reference/<model>.py (`check_train_step`)

The job is ended from the last `on_task_end` by `Worker.drain_and_stop()`
and the master's abort hook: `Master.wait()` has no other way out while
epochs remain (listed in PERF.md, Open questions).
"""

from __future__ import annotations

import contextlib
import math
import os
import shutil
import tempfile
import time

from benchmarks import datagen, manifest, stats, trace_reduce
from benchmarks.manifest import BenchmarkError

# phases of the callback's little state machine
WARMUP, WINDOW, TRACING, DONE = "warmup", "window", "tracing", "done"


def say(message: str) -> None:
    print(f"[bench] {message}", flush=True)


@contextlib.contextmanager
def wrapped_init(cls, before=None, after=None):
    """Run `before(kwargs)` / `after(instance)` around every `cls(...)`
    made while the CLI runs; `client.main` returns only an exit code."""
    init = cls.__init__

    def recording_init(self, *args, **kwargs):
        if before is not None:
            before(kwargs)
        init(self, *args, **kwargs)
        if after is not None:
            after(self)

    cls.__init__ = recording_init
    try:
        yield
    finally:
        cls.__init__ = init


def annotated(fn, name: str):
    """`fn` inside a profiler span, so that an idle gap of the device can
    be charged to the call the host was in."""
    import jax

    def call(*args, **kwargs):
        with jax.profiler.TraceAnnotation(name):
            return fn(*args, **kwargs)

    return call


class ManualSpan:
    """A profiler span opened in one callback and closed in a later one
    of the same thread."""

    def __init__(self):
        self._open = None

    def enter(self, name: str) -> None:
        import jax

        self.exit()
        self._open = jax.profiler.TraceAnnotation(name)
        self._open.__enter__()

    def exit(self) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


def compiles_so_far() -> int:
    from elasticdl_tpu.common import programs

    ledger = programs.default_program_registry().ledger()
    return sum(record["compiles"] for record in ledger.values())


def registry_value(name: str, **labels) -> float:
    from elasticdl_tpu.common import metrics as metrics_lib

    return metrics_lib.default_registry().value(name, **labels)


def phase_totals() -> dict:
    from elasticdl_tpu.worker.worker import _phase_timer

    return {
        phase: entry["total_s"]
        for phase, entry in _phase_timer.snapshot().items()
    }


class TaskWindow:
    """The zoo callback the driver appends: stamps, window, trace."""

    def __init__(self, *, seconds, trace, traffic, trace_dir):
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.steps_per_task = (
            traffic["records_per_task"] // traffic["minibatch_size"]
        )
        self.warmup_after_compile = int(
            traffic.get("warmup_tasks_after_compile", 1)
        )
        self.trace_tasks = int(traffic.get("trace_tasks", 1))
        self.trace_dir = trace_dir
        self.worker = None
        self.master = None
        self.phase = WARMUP
        self.tasks_since_compile = 0
        self.stamps = []          # [(t_end, records)], [0] = window opens
        self.gaps = []            # seconds, on_task_end -> on_task_start
        self.losses = []          # device scalars of every window step
        self.at_open = None
        self.at_close = None
        self.traced_tasks = 0
        self.compiles_after_trace = None
        self.error = None
        self._last_end = None
        self._task_span = ManualSpan()
        self._window_span = ManualSpan()

    # ---- program hooks -------------------------------------------------

    def on_task_start(self, task) -> None:
        now = time.perf_counter()
        if self.phase == WINDOW and self._last_end is not None:
            self.gaps.append(now - self._last_end)
        if self.phase == TRACING:
            self._task_span.enter("bench:task")

    def on_task_end(self, task, records) -> None:
        now = time.perf_counter()
        try:
            self._on_task_end(now, int(records))
        except Exception as exc:   # the worker would only log it
            self.error = exc
            self._finish()

    # ---- the state machine ---------------------------------------------

    def _snapshot(self) -> dict:
        return {
            "t": time.perf_counter(),
            "compiles": compiles_so_far(),
            "phases": phase_totals(),
            "steps": registry_value("worker_train_steps_total"),
            "failed": registry_value("worker_tasks_total", result="failed"),
            "live": live_bytes(),
        }

    def _on_task_end(self, now: float, records: int) -> None:
        self._last_end = now
        if self.phase == WARMUP:
            if compiles_so_far() and registry_value(
                "worker_train_steps_total"
            ):
                self.tasks_since_compile += 1
            # the task that compiled counts as 1; one whole task more
            if self.tasks_since_compile > self.warmup_after_compile:
                self.phase = WINDOW
                self.stamps.append((now, 0))
                self.at_open = self._snapshot()
                self.at_open["t"] = now
            return
        if self.phase == WINDOW:
            self.stamps.append((now, records))
            self.losses.extend(
                self.worker.losses[-i]
                for i in range(self.steps_per_task, 0, -1)
            )
            if now - self.stamps[0][0] >= self.seconds:
                self.at_close = self._snapshot()
                self.at_close["t"] = now
                if self.trace:
                    self._start_trace()
                else:
                    self._finish()
            return
        if self.phase == TRACING:
            self._task_span.exit()
            self.traced_tasks += 1
            if self.traced_tasks >= self.trace_tasks:
                self._stop_trace()
                self._finish()

    def _start_trace(self) -> None:
        import jax

        self.phase = TRACING
        jax.profiler.start_trace(self.trace_dir)
        self._window_span.enter(trace_reduce.WINDOW_SPAN)

    def _stop_trace(self) -> None:
        import jax

        self._window_span.exit()
        self.compiles_after_trace = compiles_so_far()
        jax.profiler.stop_trace()

    def _finish(self) -> None:
        self.phase = DONE
        self._task_span.exit()
        self._window_span.exit()
        self.worker.drain_and_stop()
        self.master._on_job_abort("benchmark window closed")

    def watch(self, deadline_s: float) -> None:
        """Watchdog thread: a task that fails is re-queued for ever (the
        epochs outlast any window), and `on_task_end` never fires for it.
        End the job at the first failed task, or when warm-up outlasts
        `deadline_s`."""
        started = time.perf_counter()
        while self.phase != DONE:
            time.sleep(0.5)
            reason = None
            if registry_value("worker_tasks_total", result="failed"):
                reason = "a task failed (the worker's log has the error)"
            elif (self.phase == WARMUP
                  and time.perf_counter() - started > deadline_s):
                reason = f"warm-up took more than {deadline_s:.0f}s"
            if reason and self.phase != DONE and self.master is not None:
                self.error = BenchmarkError(reason)
                self.phase = DONE
                if self.worker is not None:
                    self.worker.drain_and_stop()
                self.master._on_job_abort(f"benchmark: {reason}")


def preflight(cell) -> dict:
    """The device as jax reports it; raises unless it is the TPU the cell
    asks for and `peaks.json` knows its kind."""
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu":
        raise BenchmarkError(
            f"platform is {device['platform']!r}, not 'tpu': the "
            "benchmark has no CPU continuation"
        )
    if device["kind"] not in manifest.load_peaks(cell):
        raise BenchmarkError(
            f"device kind {device['kind']!r} is not in benchmarks/peaks.json"
        )
    if device["count"] != cell.chips:
        raise BenchmarkError(
            f"{cell.name} asks for {cell.chips} chip(s), jax found "
            f"{device['count']}"
        )
    return device


def live_bytes() -> list:
    """`bytes_in_use` of every chip, now."""
    import jax

    return [int(d.memory_stats()["bytes_in_use"]) for d in jax.devices()]


def memory_peak_bytes(live_at_close: list) -> dict:
    """Peak device memory of the fullest chip, read when the window has
    closed and before the check runs anything of its own.

    The runtime books a running program's temporaries apart from the
    buffers the process holds: a program with 2.1 GB of temporaries moved
    `peak_bytes_reserved` by 2.1 GB and `peak_bytes_in_use` by nothing (my
    chip run, PR 23).  The two peaks need not fall together (an eager
    init can hold more buffers than a step ever sees), so they are not
    added.  What is together is known: when the window closes the train
    state and the staged batches are live (`live_at_close`, taken at that
    task end), and the train step, the job's largest program, has just
    run over exactly that state with its scratch reserved.

    `live`      buffers held at the window's last task end
    `scratch`   `peak_bytes_reserved`
    `peak`      max(`peak_bytes_in_use`, live + scratch): the device
                figure of the result line.  PERF.md section 4 holds it
                against the compiler's own `memory_analysis()` of the
                step."""
    import jax

    def of(stats, live):
        scratch = int(stats.get("peak_bytes_reserved", 0))
        return {
            "live": live, "scratch": scratch,
            "peak_live": int(stats["peak_bytes_in_use"]),
            "peak": max(int(stats["peak_bytes_in_use"]), live + scratch),
        }

    return max(
        (of(d.memory_stats(), live)
         for d, live in zip(jax.devices(), live_at_close)),
        key=lambda m: m["peak"],
    )


def model_params(config: dict) -> str:
    return config["model_params"].format(**config)


def data_spec(cell) -> dict:
    """What the records are drawn from: the data set's own shape from the
    configuration (`dataset`), the mix's parameters over it."""
    return {**cell.config.get("dataset", {}), **cell.traffic["data"]}


def run_job(cell, seed, window: TaskWindow, data_path: str) -> int:
    """The CLI, with the window hooked in.  Returns its exit code."""
    from elasticdl_tpu.client.main import main as cli_main
    from elasticdl_tpu.master.main import Master
    from elasticdl_tpu.worker.sync import ModelOwner
    from elasticdl_tpu.worker.worker import Worker

    config, traffic = cell.config, cell.traffic

    def seed_owner(kwargs):
        # the CLI has no seed flag; the owner's constructor does
        kwargs.setdefault("seed", int(seed) & 0x7FFFFFFF)

    def hook_worker(worker):
        window.worker = worker
        worker.spec.callbacks = list(worker.spec.callbacks or []) + [window]
        owner = worker.model_owner
        owner.train_batch = annotated(owner.train_batch, "bench:train_batch")
        owner.stage_batch = annotated(owner.stage_batch, "bench:stage_batch")

    def hook_master(master):
        window.master = master

    argv = [
        "train",
        "--model_zoo", os.path.join(manifest.ROOT, config["model_zoo"]),
        "--model_def", config["model_def"],
        "--model_params", model_params(config),
        "--use_bf16", "true" if config["use_bf16"] else "false",
        "--distribution_strategy", "Local",
        "--training_data", data_path,
        "--minibatch_size", str(traffic["minibatch_size"]),
        "--records_per_task", str(traffic["records_per_task"]),
        "--num_epochs", str(traffic["num_epochs"]),
    ]
    import threading

    watchdog = threading.Thread(
        target=window.watch, args=(traffic["warmup_deadline_s"],),
        daemon=True,
    )
    watchdog.start()
    try:
        with wrapped_init(ModelOwner, before=seed_owner), \
                wrapped_init(Worker, after=hook_worker), \
                wrapped_init(Master, after=hook_master):
            return cli_main(argv)
    finally:
        window.phase = DONE
        watchdog.join(timeout=5)


# The optimizer's arithmetic is f32 on both sides, so what the step wrote
# and what the closed form gives differ by rounding alone: 1e-2 of the
# move, plus the f32 rounding of the parameter (or moment) the move was
# added to (`adam_check.excess`).  Another optimizer, learning rate or
# moment decay is O(1) of the move.
OPTIMIZER_REL_L2 = 1e-2


def adam_moments(opt_state):
    """The one node of an optax state that holds Adam's `mu`, `nu` and
    `count`."""
    import jax

    nodes = [
        node for node in jax.tree.leaves(
            opt_state, is_leaf=lambda x: hasattr(x, "mu")
        ) if hasattr(node, "mu")
    ]
    if len(nodes) != 1:
        raise BenchmarkError(
            f"expected one Adam state in the optimizer, found {len(nodes)}"
        )
    return nodes[0]


def state_on_host(state, reference, features, config) -> dict:
    """Parameters and Adam moments as flat f32 host arrays, cut to what
    the batch can touch (`reference.cut`), so a 2 GB table comes as its
    touched rows.  Copies: the step donates the state's buffers."""
    import numpy as np

    moments = adam_moments(state.opt_state)

    def host(tree):
        return {
            name: np.asarray(leaf, np.float32) for name, leaf in
            reference.cut(tree["params"], features, config).items()
        }

    return {
        "params": host(state.params),
        "mu": host(moments.mu),
        "nu": host(moments.nu),
        "count": int(moments.count),
    }


# Equal parts of the check's batch that a leaf's sampling noise is read
# from.  The estimate has parts - 1 degrees of freedom: from two halves a
# one-number leaf's noise is |N(0, s)|, under s / 2 in 38% of samples;
# from eight parts it is under s / 2 in 3%.
NOISE_PARTS = 8


def sampling_noise(reference, params, features, labels, config, want):
    """{leaf: L2 standard error of `want`, the reference's full-batch
    gradient}, taking the batch's examples as independent draws: from
    the reference's gradients over `NOISE_PARTS` equal parts of the
    batch (`reference.part_grads`: the same parameters and rows)."""
    from benchmarks import adam_check

    if len(labels) % NOISE_PARTS:
        raise BenchmarkError(
            f"a batch of {len(labels)} examples does not split into "
            f"{NOISE_PARTS} equal parts"
        )
    parts = reference.part_grads(
        params, features, labels, config, NOISE_PARTS
    )
    return {
        name: adam_check.standard_error(parts[name], want[name])
        for name in want
    }


def stated_type(config):
    """The type below float32 that the configuration states its tower
    computes in; None where it states float32."""
    return "bfloat16" if config["use_bf16"] else None


def stated_twin(reference, params, features, labels, config):
    """{leaf: the gradient of the reference's own twin with its tower in
    the stated type, on the same parameters and batch}: the yardstick of
    `leaf_shares` and `cosine_floor`.  None where the configuration
    states float32, or its reference has no twin (no `STATED_RATIO`)."""
    import numpy as np

    kind = stated_type(config)
    if kind is None or not hasattr(reference, "STATED_RATIO"):
        return None
    _, twin = reference.loss_and_grads(
        params, features, labels, config, tower=kind
    )
    return {k: np.asarray(v, np.float32) for k, v in twin.items()}


def leaf_shares(reference, params, features, labels, config, want, got,
                twin=None):
    """{leaf: |got - want| as a share of what the leaf is allowed; at
    most 1 passes}, `want` the reference's gradient.

    Where the configuration states float32, or its reference has no twin
    in the stated type: the leaf is allowed the reference file's
    `LEAF_REL_L2` of |want|.

    Where it states bfloat16 (`use_bf16`) and the reference can compute
    in it (`STATED_RATIO`; `loss_and_grads(..., tower=)`; `part_grads`),
    the error is split (`adam_check.along_across`) and each part held to
    what the stated type itself makes of it: the reference's twin in
    bfloat16 on the same parameters and batch (`twin`, from
    `stated_twin`; computed here where it is not handed over), against
    the reference.

    across `want`  a wrong shape: at most `STATED_RATIO` times the twin's,
                   or that many roundings of |want| where the twin's has
                   cancelled.  This is what tells bfloat16 from the type
                   below, whatever |want| is.
    along `want`   a wrong scale, ONE number a leaf: at most `STATED_RATIO`
                   times the twin's (or roundings), or `LEAF_REL_L2` of
                   |want| or of the leaf's sampling noise, whichever is
                   largest.  A leaf is a mean over the batch's examples,
                   and early in training the whole gradient swings
                   through zero from step to step (a bias under
                   coin-flip labels is mean(p - y)): |want| falls 25-60x
                   while the error in mean(p) that the stated type makes
                   stays, so as a share of |want| alone it passes any
                   limit at a crossing.  One signed number can cancel in
                   the twin and not in the step, hence the share of the
                   norm or of the noise beside it.
    (PERF.md section 6, PR 26.)"""
    import re

    import jax.numpy as jnp
    import numpy as np

    from benchmarks import adam_check

    norm = np.linalg.norm
    rel = {
        name: next(
            tol for pattern, tol in reference.LEAF_REL_L2
            if re.search(pattern, name)
        ) for name in want
    }
    if twin is None:
        twin = stated_twin(reference, params, features, labels, config)
    if twin is None:
        return {
            name: adam_check.share(
                norm(got[name] - want[name]),
                rel[name] * float(norm(want[name])),
            ) for name in want
        }
    ratio = reference.STATED_RATIO
    noise = sampling_noise(reference, params, features, labels, config, want)
    shares = {}
    for name in want:
        size = float(norm(want[name]))
        rounding = float(jnp.finfo(stated_type(config)).eps) / 2 * size
        along, across = adam_check.along_across(
            got[name] - want[name], want[name]
        )
        twin_along, twin_across = adam_check.along_across(
            twin[name] - want[name], want[name]
        )
        shares[name] = max(
            adam_check.share(across, ratio * max(twin_across, rounding)),
            adam_check.share(along, max(
                ratio * max(abs(twin_along), rounding),
                rel[name] * max(size, noise[name]),
            )),
        )
    return shares


def cosine_floor(reference, config, want, twin) -> tuple:
    """(the least cosine of all leaves as one vector against `want` that
    passes, the twin's own cosine or None).

    Where there is a twin (`stated_twin`): the angle may be `STATED_RATIO`
    times the angle the stated type itself makes on the same parameters
    and batch (`adam_check.cosine_floor`), the leaves' across-part read
    on the whole gradient.  The whole gradient's norm swings 20x and
    more from step to step while the stated type's error stays, so the
    twin's angle swings with it and a constant is passed at a crossing;
    away from one the twin reads 0.9998 and allows 0.9982, where the
    type below reads 0.997-0.999.  It is also the one term that holds
    the leaves' scales to EACH OTHER: `leaf_shares` lets each leaf alone
    be a tenth of its norm off along itself.  Where there is none: the
    reference file's `GRAD_COSINE_MIN`.  (PERF.md section 6, PR 28.)"""
    import jax.numpy as jnp

    from benchmarks import adam_check

    if twin is None:
        return reference.GRAD_COSINE_MIN, None
    twin_cosine = adam_check.cosine(twin, want)
    return adam_check.cosine_floor(
        twin_cosine, reference.STATED_RATIO,
        float(jnp.finfo(stated_type(config)).eps),
    ), twin_cosine


def check_gradient(reference, params, features, labels, config, want,
                   got) -> dict:
    """The step's gradient `got` against the reference's `want`: every
    leaf inside its bound (`leaf_shares`) and all leaves as one vector at
    no wider an angle than allowed (`cosine_floor`), both against ONE
    computation of the stated type's twin."""
    from benchmarks import adam_check

    twin = stated_twin(reference, params, features, labels, config)
    shares = leaf_shares(
        reference, params, features, labels, config, want, got, twin=twin
    )
    cosine = adam_check.cosine(got, want)
    floor, twin_cosine = cosine_floor(reference, config, want, twin)
    return {
        "shares": shares, "cosine": cosine, "cosine_floor": floor,
        "twin_cosine": twin_cosine,
        "ok": bool(
            all(v <= 1.0 for v in shares.values()) and cosine >= floor
        ),
    }


def optimizer_excess(before, after, got, h) -> dict:
    """{leaf: how far the second moment and the parameter update the step
    wrote lie from optax's closed form over the gradient `got`, as a share
    of what rounding allows (`adam_check.excess`); at most 1 passes}."""
    import numpy as np

    from benchmarks import adam_check

    return {
        name: max(
            adam_check.excess(
                after["nu"][name] - np.float32(h["b2"]) * before["nu"][name],
                np.float32(1.0 - h["b2"]) * np.square(got[name]),
                OPTIMIZER_REL_L2, before["nu"][name],
            ),
            adam_check.excess(
                after["params"][name] - before["params"][name],
                adam_check.expected_delta(
                    before["params"][name], after["mu"][name],
                    after["nu"][name], after["count"], h,
                ),
                OPTIMIZER_REL_L2, before["params"][name],
            ),
        ) for name in got
    }


def check_train_step(cell, window, first_records) -> dict:
    """One more step of the job's OWN train step (the program the window
    timed, on the job's mesh, from the state the window left), on the
    first `minibatch_size` records, held to the plain f32 reference:

    loss       the step's against the reference's on the same parameters
    gradient   read back from Adam's first moment (benchmarks/
               adam_check.py), leaf by leaf against the reference's:
               |step's - reference's| as a share of what the leaf is
               allowed (`leaf_shares`: multiples of the error the
               stated type itself makes in the leaf, across and along
               the reference's gradient; the reference file's
               `LEAF_REL_L2` of |reference's| for float32), and all
               leaves as one vector by their cosine (`cosine_floor`:
               the angle in multiples of the stated type's own; the
               reference file's `GRAD_COSINE_MIN` for float32)
    optimizer  the second moment and the parameter update the step
               wrote, against optax's closed form from that gradient

    Outside every window, after the result's stamps are taken."""
    import numpy as np

    from benchmarks import adam_check

    config, traffic = cell.config, cell.traffic
    reference = manifest.import_by_name("reference", config["reference"])
    owner = window.worker.model_owner
    data = data_spec(cell)
    batch = datagen.RECORD_PARSERS[data["format"]](
        first_records[:traffic["minibatch_size"]], data
    )
    features, labels = batch["features"], batch["labels"]
    before = state_on_host(owner.state, reference, features, config)
    loss = float(owner.train_batch(batch))
    after = state_on_host(owner.state, reference, features, config)
    h = adam_check.hyper(config)
    want_loss, want = reference.loss_and_grads(
        before["params"], features, labels, config
    )
    want = {k: np.asarray(v, np.float32) for k, v in want.items()}
    got = {
        k: adam_check.recovered_gradient(before["mu"][k], after["mu"][k],
                                         h["b1"])
        for k in want
    }
    errors = {k: adam_check.rel_l2(got[k], want[k]) for k in want}
    gradient = check_gradient(
        reference, before["params"], features, labels, config, want, got
    )
    shares, cosine = gradient["shares"], gradient["cosine"]
    floor, twin_cosine = gradient["cosine_floor"], gradient["twin_cosine"]
    optimizer = optimizer_excess(before, after, got, h)
    loss_error = abs(loss - float(want_loss))
    ranked = sorted(shares, key=lambda n: -shares[n])
    over = [n for n in ranked if not shares[n] <= 1.0]
    ok = (
        math.isfinite(loss)
        and loss_error <= reference.LOSS_ATOL
        and gradient["ok"]
        and max(optimizer.values()) <= 1.0
        and after["count"] == before["count"] + 1
    )
    say(
        f"check: the train step's loss {loss:.6f} reference "
        f"{float(want_loss):.6f} (|diff| {loss_error:.2e}, allowed "
        f"{reference.LOSS_ATOL:.0e}) on {len(labels)} examples at step "
        f"{after['count']}; gradient from Adam's first moment over "
        f"{len(want)} leaves: relative L2 worst {max(errors.values()):.2e} "
        f"({max(errors, key=errors.get)}), median "
        f"{sorted(errors.values())[len(errors) // 2]:.2e}; of what a leaf "
        f"is allowed worst {shares[ranked[0]]:.2e} ({ranked[0]}), median "
        f"{sorted(shares.values())[len(shares) // 2]:.2e} (at most 1), "
        f"{len(over)} over; cosine {cosine:.6f}, 1 - cosine "
        f"{1 - cosine:.2e} (at most {1 - floor:.2e}"
        + ("" if twin_cosine is None else
           f": {reference.STATED_RATIO:g} times the angle of the "
           f"{stated_type(config)} twin, whose 1 - cosine is "
           f"{1 - twin_cosine:.2e}")
        + f"); optimizer arithmetic worst "
        f"{max(optimizer.values()):.2e} of what rounding allows: "
        f"{'ok' if ok else 'FAILED'}"
    )
    if not ok:
        norm = np.linalg.norm
        say("check: worst leaves, share of its bound, error / |reference| "
            "(|step|, |reference|), optimizer: " + ", ".join(
                f"{n}={shares[n]:.2e}, {errors[n]:.2e} ({norm(got[n]):.2e}, "
                f"{norm(want[n]):.2e}), {optimizer[n]:.1e}"
                for n in (over or ranked)[:12]
            ))
    return {**gradient, "ok": ok, "step": after["count"], "loss": loss,
            "want_loss": float(want_loss), "loss_error": loss_error,
            "errors": errors, "optimizer": optimizer}


def dead_parameters(state) -> tuple:
    """(leaves checked, names of the dead): parameter leaves whose Adam
    first moment is exactly zero after the window, so the REAL train
    step never gave them a gradient."""
    import jax
    import numpy as np

    moments = [
        node.mu for node in jax.tree.leaves(
            state.opt_state, is_leaf=lambda x: hasattr(x, "mu")
        ) if hasattr(node, "mu")
    ]
    checked, dead = 0, []
    for mu in moments:
        for path, leaf in jax.tree_util.tree_leaves_with_path(mu):
            checked += 1
            if not float(np.asarray(jax.numpy.max(jax.numpy.abs(leaf)))):
                dead.append(jax.tree_util.keystr(path))
    return checked, dead


def run(cell, seed: int, seconds: float, trace: bool, process_t0: float):
    """One run of one train cell; returns what `run.py` prints from."""
    # every executable persists, so that only a checkout's first run
    # compiles (jax's default skips what compiled in under a second)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    device = preflight(cell)
    import numpy as np

    from elasticdl_tpu.data import native_io

    if not native_io.available():
        raise BenchmarkError(
            "the native record scanner did not load "
            "(scripts/build_native.sh shows why)"
        )
    traffic = cell.traffic
    work = tempfile.mkdtemp(prefix="bench_train_")
    try:
        data_path = os.path.join(work, "train.tfrecord")
        t0 = time.perf_counter()
        first_records = datagen.write_task_file(
            data_path, seed, data_spec(cell),
            traffic["records_per_task"], traffic["file_tasks"],
        )
        say(
            f"data: {traffic['file_tasks']} tasks of "
            f"{traffic['records_per_task']} records written in "
            f"{time.perf_counter() - t0:.1f}s"
        )
        window = TaskWindow(
            seconds=seconds, trace=trace, traffic=traffic,
            trace_dir=os.path.join(work, "trace"),
        )
        rc = run_job(cell, seed, window, data_path)
        if window.error is not None:
            raise window.error
        if window.at_close is None:
            raise BenchmarkError(
                f"the job ended (exit {rc}) before the window closed: "
                f"{traffic['num_epochs']} epochs did not outlast it"
            )
        memory = memory_peak_bytes(window.at_close["live"])
        say(
            f"memory: {memory['peak']} bytes on the fullest chip = max("
            f"peak of live buffers {memory['peak_live']}, live at the "
            f"window's close {memory['live']} + program scratch "
            f"{memory['scratch']})"
        )
        opened, closed = window.at_open, window.at_close
        compiled_inside = closed["compiles"] - opened["compiles"]
        if trace:
            compiled_inside = max(
                compiled_inside,
                window.compiles_after_trace - opened["compiles"],
            )
        if compiled_inside:
            raise BenchmarkError(
                f"{compiled_inside} program(s) compiled inside the window"
            )
        tasks = len(window.stamps) - 1
        steps = int(closed["steps"] - opened["steps"])
        failed = int(closed["failed"] - opened["failed"])
        losses = np.asarray([float(x) for x in window.losses])
        leaves, dead = dead_parameters(window.worker.state)
        say(
            f"check: {len(dead)} of {leaves} parameter leaves never "
            f"received a gradient from the train step"
            + (f", e.g. {dead[:4]}" if dead else "")
        )
        check = check_train_step(cell, window, first_records)
        correct = bool(
            check["ok"]
            and leaves > 0 and not dead
            and failed == 0
            and steps == tasks * window.steps_per_task
            and len(losses) == steps
            and np.isfinite(losses).all()
        )
        window_s = closed["t"] - opened["t"]
        rate = stats.window_task_rate(window.stamps)
        say(
            f"window: {tasks} whole tasks, {steps} steps in "
            f"{window_s:.3f}s: {rate:.1f} examples/s"
            + (f"; median task rate past the first "
               f"{stats.median_task_rate(window.stamps):.1f}"
               if tasks > 1 else "")
            + f"; losses {losses[0]:.5f} -> {losses[-1]:.5f}; failed "
            f"tasks {failed}"
        )
        phases = {
            k: closed["phases"][k] - opened["phases"][k]
            for k in closed["phases"]
        }
        say(
            "host phases (ms a step): " + ", ".join(
                f"{k} {1e3 * v / max(steps, 1):.2f}"
                for k, v in sorted(phases.items()) if v
            ) + "; mean task gap "
            f"{1e3 * sum(window.gaps) / max(len(window.gaps), 1):.2f} ms; "
            "task rates in order: " + " ".join(
                f"{r / t:.0f}" for r, t in stats.task_readings(window.stamps)
            )
        )
        context = {
            "cell": cell,
            "stamps": window.stamps,
            "gaps": window.gaps,
            "window_s": window_s,
            "examples": sum(r for _, r in window.stamps),
            "phases": phases,
            "memory_peak_bytes": memory["peak"],
            "peaks": manifest.load_peaks(cell)[device["kind"]],
            "chips": cell.chips,
            "train_examples_per_s": rate,
        }
        device["memory_peak_bytes"] = memory["peak"]
        if trace:
            reduced = trace_reduce.reduce_trace_dir(window.trace_dir)
            context["trace"] = reduced
            context["trace_steps"] = (
                window.traced_tasks * window.steps_per_task
            )
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            say(
                f"trace: {context['trace_steps']} steps, window "
                f"{reduced['window_s']:.3f}s, busy {reduced['busy_s']:.3f}s;"
                " top operations (ms a step): " + "; ".join(
                    f"{name} {1e3 * seconds / context['trace_steps']:.2f}"
                    for name, seconds in
                    reduced["breakdown"]["device_ops"]
                )
            )
        return {
            "correct": correct,
            "attempted": tasks,
            "failed": failed,
            "end_to_end": {
                "train_examples_per_s": rate,
                "setup_s": opened["t"] - process_t0,
            },
            "device": device,
            "context": context,
        }
    finally:
        shutil.rmtree(work, ignore_errors=True)

