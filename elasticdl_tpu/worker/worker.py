"""Worker runtime: pull tasks, train/evaluate/predict, report.

Parity: reference python/worker/worker.py (SURVEY.md C7, call stack §3.3).
Differences by design: the hot loop is an XLA-compiled step on the device
mesh instead of eager ops + per-step PS RPCs — the only RPCs left are
per-*shard* get_task/report (the property that kept master load low in the
reference is preserved exactly).

Model state lives in a `ModelOwner` (worker/sync.py).  Workers sharing one
owner train ONE model — the multi-worker consistency the reference provided
via PS/Horovod; a worker given no owner builds a private one (single-worker
jobs, tests).
"""

from __future__ import annotations

import traceback
from typing import Dict, Optional

import numpy as np

from elasticdl_tpu.common import events
from elasticdl_tpu.common import metrics as metrics_lib
from elasticdl_tpu.common import profiler as profiler_lib
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.common.model_handler import ModelSpec, resolve_wire_format
from elasticdl_tpu.layers import step_metrics
from elasticdl_tpu.proto import elasticdl_pb2 as pb
from elasticdl_tpu.worker.sync import ModelOwner
from elasticdl_tpu.worker.task_data_service import TaskDataService
from elasticdl_tpu.worker.trainer import Trainer, run_device_serialized

logger = get_logger(__name__)

# Unified registry series (process-wide: one worker per process in
# cluster mode; in-process tests share them, which is what a
# cluster-wide total means anyway).  The same numbers ride task reports
# to the master as `__`-prefixed exec_counters.  Module-level so a
# Worker built without __init__ (test scaffolding) still counts.
_steps_counter = metrics_lib.default_registry().counter(
    "worker_train_steps_total", "optimizer steps completed"
)
_steps_gauge = metrics_lib.default_registry().gauge(
    "worker_steps_per_sec",
    "steps of the last task over the time between the synchronised "
    "ends of consecutive tasks (profiler.SyncedStepRate)",
)
_tasks_counter = metrics_lib.default_registry().counter(
    "worker_tasks_total",
    "tasks processed, by outcome",
    labelnames=("result",),
)
# Read by benchmarks/tests/test_granite_cell.py
# (test_the_gauge_reader_reads_what_the_worker_sets), which only a
# `benchmark` PR may edit: the sown names' table under the name it had
# here (ROADMAP.md Reach C has its removal).
_moe_gauges = step_metrics.declared()
# The process's one PhaseTimer (per-step phase attribution and the span
# ring), shared by the threaded and SPMD loops.  Module-level for the
# same __new__ reason as the counters above.
_phase_timer = profiler_lib.process_phase_timer()


def finish_train_task(step_rate, summary, steps: int, fetch, model_step):
    """The tail of a train task, the threaded loop's and the SPMD loop's.
    One fetch per TASK, not per step: forcing the loss to host every
    batch would serialize the device pipeline.  `fetch()` is that fetch
    (worker/sync.py: fetch_loss); the wait in it is the device work that
    stood behind the host (`task_sync`) and its end is the loop's one
    synchronised stamp.  What the model's layers sowed goes to the
    metrics they declared (layers/step_metrics.py) and the rest, with
    the loss, to the summary at `model_step()`."""
    with _phase_timer.phase("task_sync") as sync:
        loss_value, sown = fetch()
    step_rate.task_synced(sync.end, steps)
    _steps_gauge.set(step_rate.steps_per_sec)
    scalars = {
        "train/loss": loss_value,
        "train/steps_per_sec": step_rate.steps_per_sec,
    }
    for path, value in step_metrics.publish(sown).items():
        scalars["train/" + path] = value
    summary.scalars(scalars, step=model_step())


def invoke_callbacks(callbacks, hook: str, *args) -> None:
    """Fire one zoo-callback hook on every callback that implements it.
    Hook points (reference C14 semantics, SURVEY.md): on_task_start(task),
    on_task_end(task, records), on_job_end().  A raising callback is
    logged, never fatal — user code must not kill the task loop."""
    for cb in callbacks or ():
        fn = getattr(cb, hook, None)
        if fn is None:
            continue
        try:
            fn(*args)
        except Exception:
            logger.exception("callback %r failed in %s", cb, hook)


# ~1MB of floats per report: comfortably under gRPC's 4MB default
# message cap, few round trips per shard.
EVAL_SAMPLE_CHUNK_FLOATS = 1 << 18


def report_evaluation_with_samples(
    client, worker_id: int, model_version: int,
    metrics: Dict[str, float], num_examples: int, labels, preds,
    task_id: int = -1,
) -> None:
    """Report shard metrics PLUS the raw (label, prediction) samples so
    the master can recompute rank metrics (AUC) exactly over the merged
    validation set — per-shard AUC means are biased (VERDICT r3 weak #3).
    Samples are chunked under the gRPC message limit; continuation chunks
    set samples_only so scalars/num_examples are counted once."""
    labels = np.asarray(labels, np.float32)
    preds2 = np.asarray(preds, np.float32).reshape(len(labels), -1)
    width = preds2.shape[1]
    rows_per_chunk = max(1, EVAL_SAMPLE_CHUNK_FLOATS // (1 + width))
    first = True
    for i in range(0, len(labels), rows_per_chunk):
        j = min(i + rows_per_chunk, len(labels))
        req = pb.ReportEvaluationMetricsRequest(
            worker_id=worker_id,
            model_version=model_version,
            pred_width=width,
            samples_only=not first,
            eval_task_key=task_id + 1 if task_id >= 0 else 0,
            final_chunk=j >= len(labels),
        )
        if first:
            req.num_examples = num_examples
            for name, value in metrics.items():
                req.metrics[name] = float(value)
            first = False
        req.eval_labels.extend(labels[i:j].tolist())
        req.eval_preds.extend(preds2[i:j].ravel().tolist())
        client.report_evaluation_metrics(req)


class TransientTaskError(RuntimeError):
    """The task is fine but THIS worker can't serve it yet (e.g. a fresh
    replacement pod leasing an eval task before it has trained state).
    Reported with transient=True: the master re-queues without charging a
    retry."""


class Worker:
    # class-level defaults: tests (and recovery paths) build bare
    # instances via __new__ and set only what they exercise
    wire_format = "plain"
    compact_wire = False

    def __init__(
        self,
        worker_id: int,
        master_client,
        data_reader,
        spec: ModelSpec,
        minibatch_size: int = 64,
        mesh=None,
        use_bf16: bool = False,
        seed: int = 0,
        checkpoint_saver=None,
        checkpoint_steps: int = 0,
        elastic_manager=None,
        model_owner: Optional[ModelOwner] = None,
        tensorboard_dir: str = "",
        profile_dir: str = "",
        compact_wire: bool = False,
        wire_format: str = "",
    ):
        self.worker_id = worker_id
        self.spec = spec
        self.minibatch_size = minibatch_size
        # --wire_format / --compact_wire: ship batches in a reduced device
        # wire format when the zoo provides one (fewer H2D bytes/example);
        # the zoo's model accepts the reduced dtypes by contract.  An
        # unavailable format degrades to the next-best the zoo defines.
        self.wire_format = resolve_wire_format(
            spec, wire_format, compact_wire, logger
        )
        self.compact_wire = self.wire_format == "compact"
        self._client = master_client
        self._data_service = TaskDataService(
            master_client, data_reader, worker_id
        )
        if model_owner is not None and (
            mesh is not None
            or use_bf16
            or seed != 0
            or checkpoint_saver is not None
            or checkpoint_steps != 0
        ):
            raise ValueError(
                "mesh/use_bf16/seed/checkpoint_* are owned by the "
                "ModelOwner; configure them on the owner you pass in"
            )
        if model_owner is None:
            model_owner = ModelOwner(
                Trainer(
                    model=spec.model,
                    optimizer=spec.optimizer,
                    loss_fn=spec.loss,
                    mesh=mesh,
                    use_bf16=use_bf16,
                    param_sharding_fn=spec.param_sharding,
                ),
                seed=seed,
                checkpoint_saver=checkpoint_saver,
                checkpoint_steps=checkpoint_steps,
            )
        self._owner = model_owner
        # Phase attribution: hand the process-wide timer to the layers
        # that own each phase (trainer: h2d_stage/compute; data service:
        # get_task/read/pack; prefetch_batches gets it per-iteration for
        # data_wait/queue_full).
        self._owner.trainer.phase_timer = _phase_timer
        self._data_service.phase_timer = _phase_timer
        self._reader = data_reader
        # Bounded: device arrays, converted lazily; unbounded growth would
        # pin one device buffer per step for the job's lifetime.
        from collections import deque

        self.losses = deque(maxlen=1024)
        self._elastic = elastic_manager
        # Observability (SURVEY.md §5): step rate from the per-task
        # synchronised stamp + TensorBoard scalars (a no-op when no
        # tensorboard_dir is set).
        from elasticdl_tpu.common.summary import SummaryWriter

        self.step_rate = profiler_lib.SyncedStepRate()
        self._summary = SummaryWriter(tensorboard_dir or None)
        # --profile_dir: capture ONE task's device trace (Perfetto/XPlane,
        # TensorBoard-readable) then stop — always-on tracing would drag
        # the hot loop.
        self._profile_dir = profile_dir
        self._profiled = False

    # ---- owner passthroughs (tests and the client API read these) ------

    @property
    def state(self):
        return self._owner.state

    @property
    def trainer(self):
        return self._owner.trainer

    @property
    def model_owner(self) -> ModelOwner:
        return self._owner

    @property
    def _checkpoint_saver(self):
        return self._owner.checkpoint_saver

    # ---- loops ---------------------------------------------------------

    def drain_and_stop(self) -> None:
        """Maintenance-notice hook (thread-safe): request a stop at the
        next task boundary.  The MAIN thread does the final checkpoint
        there — saving from the watcher thread would race the training
        loop's state mutation."""
        self._stop_requested = True

    def run(self) -> bool:
        """Main loop until the master declares the job finished.  Returns
        True on clean completion."""
        # start-up ends where the loop's own spans begin
        _phase_timer.startup(None)
        while True:
            if getattr(self, "_stop_requested", False):
                logger.info(
                    "Worker %d draining at task boundary "
                    "(maintenance/preemption notice); flushing checkpoint",
                    self.worker_id,
                )
                self._owner.save_and_flush()
                return False
            task, finished = self._data_service.get_task(
                should_stop=lambda: getattr(self, "_stop_requested", False)
            )
            if finished:
                logger.info("Job finished; worker %d exiting", self.worker_id)
                if self.step_rate.steps_per_sec:
                    logger.info(
                        "worker %d: steps/sec=%.2f",
                        self.worker_id, self.step_rate.steps_per_sec,
                    )
                self._summary.close()
                invoke_callbacks(self.spec.callbacks, "on_job_end")
                return True
            if task is None:
                # woken out of the WAIT loop by should_stop: loop back so
                # the drain check at the top runs
                continue
            _phase_timer.mark(task_id=task.task_id, step=None)
            self._maybe_remesh()
            events.emit(
                events.TASK_CLAIMED,
                task_id=task.task_id,
                worker_id=self.worker_id,
                task_type=task.type,
            )
            try:
                invoke_callbacks(self.spec.callbacks, "on_task_start", task)
                records = self._process_task(task)
                events.emit(
                    events.TASK_TRAINED,
                    task_id=task.task_id,
                    worker_id=self.worker_id,
                    records=records,
                )
                _tasks_counter.labels(result="ok").inc()
                with _phase_timer.phase("report"):
                    self._data_service.report_task(
                        task,
                        records=records,
                        model_version=self._owner.step
                        if task.type == pb.TRAINING
                        else -1,
                        telemetry=self._telemetry_payload(),
                    )
                invoke_callbacks(
                    self.spec.callbacks, "on_task_end", task, records
                )
                if task.type == pb.TRAINING:
                    try:
                        self._client.report_version(
                            pb.ReportVersionRequest(
                                worker_id=self.worker_id,
                                model_version=self._owner.step,
                            )
                        )
                    except Exception:
                        pass  # advisory only; eval scheduling catches up
            except TransientTaskError as exc:
                logger.info(
                    "Task %d transiently unserviceable on worker %d: %s",
                    task.task_id, self.worker_id, exc,
                )
                _tasks_counter.labels(result="transient").inc()
                self._data_service.report_task(
                    task, err=str(exc), transient=True
                )
            except Exception as exc:  # report failure; master re-queues
                logger.error(
                    "Task %d failed on worker %d: %s",
                    task.task_id, self.worker_id, exc,
                )
                traceback.print_exc()
                # An exception with an empty str() must still read as a
                # failure on the wire (err_message=="" means success).
                err = str(exc) or type(exc).__name__
                _tasks_counter.labels(result="failed").inc()
                self._data_service.report_task(task, err=err)

    def _telemetry_payload(self) -> Dict[str, int]:
        """Telemetry piggybacked on task reports (int64 on the wire;
        rates pre-scaled to milli units)."""
        payload = {
            "steps_total": int(_steps_counter.value()),
            "steps_per_sec_milli": int(
                self.step_rate.steps_per_sec * 1000
            ),
            "model_step": int(self._owner.step),
        }
        # Cumulative per-phase milliseconds: the master diffs/normalizes
        # these in its snapshot, `elasticdl top` renders the dominant
        # phase per worker.
        for phase, ms in _phase_timer.totals_milli().items():
            payload[f"phase_{phase}_ms"] = ms
        return payload

    def _process_task(self, task: pb.Task) -> int:
        if task.type == pb.TRAINING:
            return self._train_task(task)
        if task.type == pb.EVALUATION:
            return self._evaluate_task(task)
        if task.type == pb.PREDICTION:
            return self._predict_task(task)
        if task.type == pb.SAVE_MODEL:
            self._save_model(task)
            return 0
        logger.warning("Unknown task type %s", task.type)
        return 0

    def _save_model(self, task: pb.Task):
        """Checkpoint, and export if the task's config rider asks for it
        (cluster mode: the master injects the output dir at job end)."""
        self._owner.save(force=True)
        # snapshot: another worker thread may still be training (and
        # donating the live state's buffers) while the export reads it
        export_for_task(
            self._owner.snapshot(), self.spec, task,
            sample_features=self._owner.sample_features,
        )

    def _train_task(self, task: pb.Task) -> int:
        if self._profile_dir and not self._profiled:
            self._profiled = True
            import jax as _jax

            from elasticdl_tpu.common import profiler

            with profiler.trace(self._profile_dir):
                with profiler.annotate(f"task-{task.task_id}"):
                    records = self._train_task_inner(task)
                    if self.losses:
                        _jax.block_until_ready(self.losses[-1])
            return records
        return self._train_task_inner(task)

    def _train_task_inner(self, task: pb.Task) -> int:
        from elasticdl_tpu.worker.task_data_service import prefetch_batches

        records = 0
        steps = 0
        loss = None

        # Second buffering level: batch k+1's host->device transfer is
        # issued while batch k executes (ModelOwner.stage_batch;
        # device_put is async on real backends).
        def device_stage(item):
            staged_batch, staged_real = item
            return self._owner.stage_batch(staged_batch), staged_real

        # host read/parse overlaps the device step (double buffering)
        for batch, real in prefetch_batches(
            self._data_service.batches_for_task(
                task, self.minibatch_size, self._feed,
                feed_bulk=self._feed_bulk,
            ),
            device_stage=device_stage,
            phase_timer=_phase_timer,
        ):
            records += real
            _phase_timer.mark(step=steps)
            loss = self._owner.train_batch(batch)
            _phase_timer.step_done()
            steps += 1
            self.losses.append(loss)
        _phase_timer.mark(step=None)
        if steps:
            _steps_counter.inc(steps)
            # partial flush window: the task boundary must not strand
            # accumulated phase time (the trace exporter reads these)
            _phase_timer.flush()
        if loss is not None:
            finish_train_task(
                self.step_rate, self._summary, steps,
                # serialized: a device->host fetch racing another
                # thread's step execution corrupts the CPU backend
                fetch=lambda: self._owner.fetch_loss(loss),
                model_step=lambda: self._owner.step,
            )
        return records

    def _evaluate_task(self, task: pb.Task) -> int:
        """Forward-only over the shard; metrics computed host-side on the
        un-padded slice and reported to the master for aggregation."""
        if not self._owner.has_trained_state():
            # A fresh worker (e.g. a replacement pod) with no trained state
            # and no checkpoint to restore must not report metrics from
            # randomly initialised params.  Re-queue for a worker that has
            # either.  (ADVICE r1: a configured-but-empty checkpoint dir
            # counts as *no* trained state.)
            raise TransientTaskError(
                "worker has no trained state for evaluation; re-queueing"
            )
        records = 0
        all_labels, all_preds = [], []
        eval_state, actual_version = None, None
        for batch, real in self._data_service.batches_for_task(
            task, self.minibatch_size, self._feed,
            feed_bulk=self._feed_bulk,
        ):
            if actual_version is None:
                # Eval-at-version (§3.5): score the checkpointed state at
                # the requested version when retrievable; otherwise label
                # metrics with the step actually evaluated.
                self._owner.ensure_state(batch)
                eval_state, actual_version = self._owner.state_for_eval(
                    task.model_version
                )
            preds = self._owner.predict_batch(batch, state=eval_state)
            all_labels.append(np.asarray(batch["labels"])[:real])
            all_preds.append(preds[:real])
            records += real
        if records:
            # Metrics computed once over the whole shard (not averaged per
            # batch) so rank-based metrics like AUC stay faithful.
            labels = np.concatenate(all_labels)
            preds = np.concatenate(all_preds)
            version = (
                actual_version
                if actual_version is not None and actual_version >= 0
                else self._owner.step
            )
            metrics = {
                name: float(fn(labels, preds))
                for name, fn in self.spec.eval_metrics.items()
            }
            report_evaluation_with_samples(
                self._client, self.worker_id, version,
                metrics, records, labels, preds, task_id=task.task_id,
            )
            self._summary.scalars(
                {f"eval/{k}": v for k, v in metrics.items()},
                step=version,
            )
        return records

    def _predict_task(self, task: pb.Task) -> int:
        records = 0
        # keyed by task_id and only committed on task completion: a
        # mid-task failure + re-queue must not leave partial rows that a
        # rerun would duplicate (the SPMD path keys the same way)
        self.predictions = getattr(self, "predictions", {})
        processor = self.spec.prediction_outputs_processor
        rows = []
        for batch, real in self._data_service.batches_for_task(
            task, self.minibatch_size, self._feed,
            feed_bulk=self._feed_bulk,
        ):
            preds = self._owner.predict_batch(batch)
            rows.append(preds[:real])
            records += real
        if rows:
            self.predictions[task.task_id] = np.concatenate(rows)
            if processor is not None:
                # reference C18 contract, buffered per task (ADVICE r3):
                # a mid-task failure + re-queue must not deliver partial
                # duplicate rows to the sink.  Delivery is at-least-once
                # at TASK granularity (a crash between this flush and the
                # completion report re-runs the whole task).
                for chunk in rows:
                    processor.process(chunk, self.worker_id)
        return records

    def _maybe_remesh(self):
        """Elastic cycle: if the membership epoch moved, rebuild the mesh
        and re-place (or restore) state before processing the next task."""
        if self._elastic is None:
            return
        try:
            spec = self._elastic.fetch_spec()
        except Exception as exc:
            # The spec fetch sits outside the per-task error handling; a
            # transient failure (master briefly unreachable, injected
            # rendezvous fault) must skip this remesh round, not kill the
            # worker — the next loop iteration fetches again.
            logger.warning("cluster spec fetch failed: %s; will retry", exc)
            return
        if not self._elastic.is_new_epoch(spec):
            return
        mesh = self._elastic.build_mesh(spec)
        if mesh is None:
            return
        self._owner.remesh(mesh)

    def _feed(self, records):
        return self.spec.feed(records, getattr(self._reader, "metadata", {}))

    @property
    def _feed_bulk(self):
        """Vectorized-parse closure for batches_for_task, or None when the
        zoo module has no feed_bulk (the streaming feed path then runs).
        With --wire_format (or legacy --compact_wire) and the matching
        zoo feed, batches parse straight into that device wire format."""
        if self.wire_format == "dedup":
            fn = self.spec.feed_bulk_dedup
        elif self.compact_wire:
            fn = self.spec.feed_bulk_compact
        else:
            fn = self.spec.feed_bulk
        if fn is None:
            return None
        metadata = getattr(self._reader, "metadata", {})
        return lambda buf, sizes: fn(buf, sizes, metadata)


def _task_export_config(task: pb.Task) -> dict:
    """Parse a SAVE_MODEL task's JSON config rider ({output, saved_model})."""
    if not task.extended_config:
        return {}
    import json

    try:
        return json.loads(task.extended_config)
    except ValueError:
        logger.warning(
            "Bad extended_config on task %d: %r",
            task.task_id, task.extended_config,
        )
        return {}


def export_for_task(state, spec, task: pb.Task,
                    sample_features=None) -> bool:
    """Export the model if the SAVE_MODEL task's rider names an output dir.

    Raises when an export was requested but there is no trained state —
    a silent skip would let the job report success with args.output never
    written; raising re-queues the task for a worker that has state.
    """
    config = _task_export_config(task)
    output = config.get("output", "")
    if not output:
        return False
    if state is None:
        raise RuntimeError(
            "SAVE_MODEL requested an export but this worker has no "
            "trained state; re-queueing"
        )
    from elasticdl_tpu.common.export import export_model

    export_model(
        state, spec, output,
        saved_model=bool(config.get("saved_model", False)),
        sample_features=sample_features,
    )
    logger.info("Exported model to %s", output)
    return True
