"""Multi-process SPMD worker: one global mesh, one model, one train step.

This is the cluster-mode replacement for BOTH reference topologies
(SURVEY.md §3.3 PS mode, §3.4 Horovod AllReduce): instead of N workers
training private replicas synchronized through a parameter server or an
allreduce ring, every process joins a single `jax.distributed` runtime,
the devices form one global `Mesh`, and all ranks enter the SAME jitted
collective train step per global batch — XLA emits the gradient reduction
over ICI/DCN from the shardings.  Consistency is by construction: there is
only one logical computation, so no rank can diverge.

Task flow (the part the reference's design survives intact): the master
still owns the shard queue; ranks fetch the group-synchronized assignment
for (epoch, seq) via get_spmd_task (master/spmd_assigner.py) so everyone
trains the same shard in the same order.  Each rank reads the whole shard
from shared storage and builds the full global batch host-side; only the
locally-addressable slice is transferred to devices
(mesh.make_global_batch).  Rank 0 alone reports task completion and
model versions.

Elasticity: a membership change bumps the rendezvous epoch; get_spmd_task
answers `epoch_stale`, every rank tears down and re-initialises
jax.distributed for the new topology, restores state from the latest
checkpoint (Orbax handles cross-topology resharding) and resumes at
seq=0 — the task queue re-leases whatever the old group held, so no
step-exact replay is needed (SURVEY.md §7 hard part 1).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

import jax
import numpy as np

from elasticdl_tpu.common import profiler as profiler_lib
from elasticdl_tpu.common import resilience
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.common.model_handler import ModelSpec, resolve_wire_format
from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.proto import elasticdl_pb2 as pb
from elasticdl_tpu.worker.task_data_service import TaskDataService
from elasticdl_tpu.worker.trainer import Trainer

logger = get_logger(__name__)

# Step-phase attribution and spans: the process's one PhaseTimer, the
# threaded worker's too — SPMD cluster mode runs one rank per process, so
# per-process totals are per-rank totals.  Module-level for __new__
# scaffolding.
_phase_timer = profiler_lib.process_phase_timer()


def wait_for_confirmed_epoch(
    client,
    worker_id: int,
    poll_s: float = 0.5,
    timeout_s: Optional[float] = None,
    rpc_policy: Optional[resilience.RetryPolicy] = None,
):
    """Block until this worker is a member of a SETTLED and GROUP-CONFIRMED
    epoch; returns (cluster_spec, my_worker_spec), or (None, None) on
    timeout.

    Three gates, in order:
    1. membership — I appear in the spec;
    2. settled — world_size matches the pod manager's published target
       (expected_world_size), NOT the static --num_workers flag (which
       would deadlock replacements after scale-down/budget exhaustion);
       with no published target (unmanaged rendezvous), any nonzero world
       counts as settled;
    3. confirmed — every member's MAIN thread has confirmed this exact
       epoch.  This is the anti-cascade barrier: a rank wedged in a
       collective with a dead peer cannot confirm, so nobody initializes
       a mesh containing it; its watchdog restarts it, the epoch moves,
       and the survivors re-confirm the new epoch.  Without the barrier,
       staggered deaths bump the epoch faster than replacements can boot
       and every joiner suicides on arrival (observed live in
       tests/test_elastic_cluster.py's first iterations).
    """
    import time as _time

    from elasticdl_tpu.proto import elasticdl_pb2 as pb

    if rpc_policy is None:
        rpc_policy = resilience.default_policy()
    deadline = None if timeout_s is None else _time.time() + timeout_s
    confirm = 0
    while True:
        # Each poll gets the full per-call retry budget; a master that
        # stays dead past it raises RetryBudgetExhausted out of the wait
        # (worker/main.py turns that into exit code 45).
        spec = rpc_policy.call(
            lambda: client.get_cluster_spec(
                pb.GetClusterSpecRequest(
                    worker_id=worker_id, confirm_epoch=confirm
                )
            ),
            description="get_cluster_spec",
        )
        me = next(
            (w for w in spec.workers if w.worker_id == worker_id), None
        )
        settled = me is not None and (
            spec.world_size == spec.expected_world_size
            or (spec.expected_world_size == 0 and spec.world_size > 0)
        )
        if settled and spec.all_confirmed and confirm == spec.rendezvous_id:
            return spec, me
        # (re-)confirm whatever epoch we currently observe; recorded on
        # the NEXT poll
        confirm = spec.rendezvous_id if settled else 0
        if deadline is not None and _time.time() > deadline:
            return None, None
        _time.sleep(poll_s)


class SPMDWorker:
    # class-level defaults (same rationale as Worker: bare __new__
    # construction in tests)
    wire_format = "plain"
    compact_wire = False

    """One rank of a multi-process SPMD training job."""

    def __init__(
        self,
        worker_id: int,
        master_client,
        data_reader,
        spec: ModelSpec,
        minibatch_size: int = 64,  # GLOBAL batch size
        process_id: int = 0,
        num_processes: int = 1,
        coordinator_address: str = "",
        use_bf16: bool = False,
        seed: int = 0,
        checkpoint_saver=None,
        checkpoint_saver_factory=None,
        checkpoint_steps: int = 0,
        wait_sleep_s: float = 0.2,
        initial_epoch: int = 0,
        wedge_grace_s: float = 20.0,
        output_dir: str = "",
        tensorboard_dir: str = "",
        profile_dir: str = "",
        compact_wire: bool = False,
        wire_format: str = "",
        rpc_policy: Optional[resilience.RetryPolicy] = None,
    ):
        self.worker_id = worker_id
        # One policy for every control-plane RPC this rank makes; budget
        # exhaustion propagates to worker/main.py -> exit code 45.
        self._rpc_policy = (
            rpc_policy if rpc_policy is not None
            else resilience.default_policy()
        )
        self.spec = spec
        self.minibatch_size = minibatch_size
        # --wire_format / --compact_wire (same contract as Worker), with
        # one SPMD restriction: the dedup format's padded shapes are
        # governed by each rank's OWN sticky packer caps, which can grow
        # at different steps on different ranks — a collective program
        # shape mismatch.  Degrade dedup to the compact format here.
        if (wire_format or "").strip().lower() == "dedup":
            logger.warning(
                "--wire_format=dedup is not supported under SPMD "
                "slice-local reads (per-rank dedup caps diverge); "
                "using the compact wire format instead"
            )
            wire_format = "compact"
        self.wire_format = resolve_wire_format(
            spec, wire_format, compact_wire, logger
        )
        self.compact_wire = self.wire_format == "compact"
        self.process_id = process_id
        self.num_processes = num_processes
        self._coordinator = coordinator_address
        self._client = master_client
        self._data_service = TaskDataService(
            master_client, data_reader, worker_id
        )
        self._data_service.phase_timer = _phase_timer
        self._reader = data_reader
        self._use_bf16 = use_bf16
        self._seed = seed
        self._saver = checkpoint_saver
        # Orbax construction touches the XLA backend, which must not
        # happen before jax.distributed.initialize — multi-process callers
        # pass a FACTORY and the saver is built in setup(), after init.
        self._saver_factory = checkpoint_saver_factory
        self._checkpoint_steps = checkpoint_steps
        self._wait_sleep_s = wait_sleep_s
        self._epoch = initial_epoch
        self.state = None
        self.trainer: Optional[Trainer] = None
        self.mesh = None
        self.last_loss = None
        self.remesh_count = 0
        self._preempted = False
        self._output_dir = output_dir
        self._recovery_t0: Optional[float] = None
        self._wedge_grace_s = wedge_grace_s
        self._epoch_stale_since: Optional[float] = None
        self._watchdog_started = False
        # Set while the MAIN thread is in the confirmation-barrier poll
        # loop: it is then provably live and epoch-aware, so the watchdog
        # must not shoot it for lagging the epoch.
        self._in_rendezvous_wait = False
        # Leader-only observability: ONE rank writes scalars (every rank
        # holds identical state/loss by construction).
        from elasticdl_tpu.common.summary import SummaryWriter

        self.step_rate = profiler_lib.SyncedStepRate()
        self._summary = SummaryWriter(
            tensorboard_dir if (tensorboard_dir and process_id == 0) else None
        )
        # one-shot device trace of the first training task (every rank
        # writes its own subdir — in SPMD each process only sees its
        # addressable devices)
        self._profile_dir = profile_dir
        self._profiled = False

    # ---- runtime lifecycle --------------------------------------------

    # jax.distributed.initialize's default 300s join deadline is far too
    # long for an elastic group: a rank that entered initialize with a
    # stale epoch would anchor the whole recovery cascade on it.  The
    # watchdog (started BEFORE initialize) normally restarts such a rank
    # within the grace window; this cap is the backstop.
    INIT_TIMEOUT_S = 60

    def setup(self) -> None:
        """Join the distributed runtime and build the global mesh."""
        if self.num_processes > 1 and not self._watchdog_started:
            # Must start before initialize(): a rank blocked in
            # RegisterTask against a coordinator of a newer epoch can only
            # be saved by the watchdog restarting the process.
            self._watchdog_started = True
            threading.Thread(target=self._watchdog, daemon=True).start()
        if self.num_processes > 1 and not jax.distributed.is_initialized():
            jax.distributed.initialize(
                coordinator_address=self._coordinator,
                num_processes=self.num_processes,
                process_id=self.process_id,
                initialization_timeout=self.INIT_TIMEOUT_S,
            )
        if jax.process_count() != self.num_processes:
            # e.g. TPU children each pinned to a stand-alone chip: every
            # rank would then train its own diverging replica
            raise RuntimeError(
                f"rank {self.process_id}: the {jax.default_backend()} "
                f"backend spans {jax.process_count()} process(es) but the "
                f"rendezvous world has {self.num_processes}; the ranks "
                "would not share one mesh"
            )
        if self._saver is None and self._saver_factory is not None:
            self._saver = self._saver_factory()
        self.mesh = mesh_lib.create_mesh(jax.devices())
        self.trainer = Trainer(
            model=self.spec.model,
            optimizer=self.spec.optimizer,
            loss_fn=self.spec.loss,
            mesh=self.mesh,
            use_bf16=self._use_bf16,
            param_sharding_fn=self.spec.param_sharding,
        )
        # compute / h2d-adjacent dispatch time lands in the phase timer
        self.trainer.phase_timer = _phase_timer
        logger.info(
            "SPMD rank %d/%d up: %d global devices, mesh %s",
            self.process_id, self.num_processes,
            len(jax.devices()), dict(self.mesh.shape),
        )

    def _ensure_state(self, batch, global_rows: Optional[int] = None) -> None:
        if getattr(self, "sample_features", None) is None:
            # one host row, kept for export signatures (SavedModel)
            self.sample_features = jax.tree.map(
                lambda a: np.asarray(a[:1]), batch["features"]
            )
        if self.state is not None:
            return
        features = batch["features"]
        if global_rows is not None:
            # Slice-local data path: ranks hold DIFFERENT local rows, but
            # the jitted init embeds its features as constants — every
            # rank must trace the identical program, so init from zeros
            # of the global batch shape (param init depends on shapes and
            # rng only, never on feature values).
            features = jax.tree.map(
                lambda a: np.zeros(
                    (global_rows,) + np.asarray(a).shape[1:],
                    np.asarray(a).dtype,
                ),
                features,
            )
        # start-up spans, as worker/sync.py: ModelOwner.ensure_state's
        with _phase_timer.phase("init_state"):
            self.state = self.trainer.init_state_global(
                jax.random.PRNGKey(self._seed), features
            )
        self._maybe_prewarm(batch, global_rows)
        if self._saver is not None:
            with _phase_timer.phase("restore"):
                restored = self._saver.maybe_restore(self.state)
            if restored is not None:
                self.state = restored
                logger.info(
                    "Rank %d restored checkpoint at step %d",
                    self.process_id, int(self.state.step),
                )

    def _maybe_prewarm(self, batch, global_rows) -> None:
        """Background-compile the train step for EXPECTED post-failure
        mesh sizes (world-1 and world/2 — SURVEY §7 hard part 1's
        mitigation): the executables land in the persistent compile
        cache, so a post-preemption remesh restores without paying a
        cold XLA compile.  Once, after first init; multi-process only."""
        if self.num_processes <= 1 or getattr(self, "_prewarmed", False):
            return
        self._prewarmed = True
        try:
            per = max(len(jax.devices()) // self.num_processes, 1)
            counts = sorted(
                {
                    (self.num_processes - 1) * per,
                    (self.num_processes // 2) * per,
                }
                - {0, len(jax.devices())}
            )
            if not counts or "labels" not in batch:
                # prediction-only feeds carry no labels; the train step
                # (the thing worth prewarming) is not on their path
                return
            rows = global_rows or self.minibatch_size

            def zeros_like_rows(a):
                a = np.asarray(a)
                return np.zeros((rows,) + a.shape[1:], a.dtype)

            sample = {
                "features": jax.tree.map(
                    zeros_like_rows, batch["features"]
                ),
                "labels": zeros_like_rows(batch["labels"]),
            }
            self.trainer.prewarm_for_device_counts(
                sample, counts, rng=jax.random.PRNGKey(self._seed)
            )
        except Exception:  # advisory path: never fail the task for it
            logger.exception("elastic prewarm setup skipped")

    @property
    def is_leader(self) -> bool:
        return self.process_id == 0

    # ---- wedge watchdog --------------------------------------------------
    # A dead peer does NOT fail a blocking XLA collective — the survivor
    # hangs in it forever (measured: gloo psum blocks >75s after peer
    # death; on a real TPU slice the ICI collective stalls the same way —
    # SURVEY.md §7 hard part 3).  The in-process re-rendezvous path only
    # runs BETWEEN tasks, so a rank stuck INSIDE a collective when the
    # membership epoch moves must be restarted: the watchdog polls the
    # master and, if the epoch has moved past us for longer than the grace
    # window (i.e. the main loop never reached the stale-epoch check),
    # kills the process.  The pod manager relaunches it; the replacement
    # bootstraps at the new epoch and restores from the checkpoint — the
    # restart unit is the process, exactly like a slice-host loss.

    WEDGED_EXIT_CODE = 43

    def _watchdog(self, poll_s: float = 2.0) -> None:
        while True:
            time.sleep(poll_s)
            try:
                spec = self._client.get_cluster_spec(
                    pb.GetClusterSpecRequest(worker_id=self.worker_id)
                )
            except Exception:
                continue  # master briefly unreachable
            if spec.rendezvous_id <= self._epoch or self._in_rendezvous_wait:
                self._epoch_stale_since = None
                continue
            now = time.time()
            if self._epoch_stale_since is None:
                self._epoch_stale_since = now
                continue
            if now - self._epoch_stale_since > self._wedge_grace_s:
                logger.error(
                    "Rank %d wedged: epoch moved %d -> %d but the main "
                    "loop hasn't re-rendezvoused in %.0fs (stuck in a "
                    "collective with a dead peer); restarting process",
                    self.process_id, self._epoch, spec.rendezvous_id,
                    now - self._epoch_stale_since,
                )
                os._exit(self.WEDGED_EXIT_CODE)

    # ---- main loop -----------------------------------------------------

    def drain_and_stop(self) -> None:
        """Maintenance-notice hook (thread-safe): flag-only; the main
        loop drains at its next task boundary (single-process ranks also
        flush a final checkpoint there — doing it from the watcher
        thread would race the training loop)."""
        self._preempted = True

    def run(self) -> bool:
        if self.trainer is None:
            self.setup()
        # start-up ends where the loop's own spans begin
        _phase_timer.startup(None)
        seq = 0
        while True:
            if self._preempted:
                logger.info(
                    "Rank %d stopping at task boundary (preemption/"
                    "maintenance notice); tasks re-lease and the relaunch "
                    "restores from checkpoint",
                    self.process_id,
                )
                if self.num_processes == 1 and self._saver is not None:
                    # single-process: no collective-save hazard — flush
                    # the freshest state before exiting (multi-process
                    # ranks rely on periodic checkpoints; a drain-time
                    # collective save could enter mismatched programs)
                    self._save(force=True)
                    self._saver.wait_until_finished()
                return False
            # Bounded, jittered retries replace the old fixed-sleep
            # infinite loop; exhaustion raises RetryBudgetExhausted,
            # which worker/main.py maps to exit code 45 so the pod
            # manager relaunches us (charged against the budget).
            with _phase_timer.phase("get_task"):
                resp = self._rpc_policy.call(
                    lambda: self._client.get_spmd_task(
                        pb.GetSpmdTaskRequest(
                            worker_id=self.worker_id,
                            rendezvous_id=self._epoch,
                            seq=seq,
                        )
                    ),
                    description="get_spmd_task",
                )
            if resp.job_finished:
                logger.info(
                    "Job finished; SPMD rank %d exiting", self.process_id
                )
                self._flush_predictions()
                if self.is_leader and self.step_rate.steps_per_sec:
                    logger.info(
                        "rank %d: steps/sec=%.2f",
                        self.process_id, self.step_rate.steps_per_sec,
                    )
                self._summary.close()
                from elasticdl_tpu.worker.worker import invoke_callbacks

                invoke_callbacks(self.spec.callbacks, "on_job_end")
                return True
            if resp.epoch_stale:
                logger.info(
                    "Rank %d: epoch %d stale; re-rendezvous",
                    self.process_id, self._epoch,
                )
                if not self._re_rendezvous():
                    return False
                seq = 0
                continue
            task = resp.task
            if task.task_id < 0 or task.type == pb.WAIT:
                with _phase_timer.phase("get_task"):   # the lease wait
                    time.sleep(self._wait_sleep_s)
                continue
            self._process_task(task)
            seq += 1

    def _process_task(self, task: pb.Task) -> int:
        # No per-rank failure reporting: if any rank's collective step
        # dies the whole group is wedged and recovery is the elastic
        # epoch-bump path, not a task retry.
        from elasticdl_tpu.worker.worker import invoke_callbacks

        _phase_timer.mark(task_id=task.task_id, step=None)
        invoke_callbacks(self.spec.callbacks, "on_task_start", task)
        records = 0
        if task.type == pb.TRAINING:
            records = self._train_task(task)
            if self.is_leader:
                with _phase_timer.phase("report"):
                    self._data_service.report_task(
                        task,
                        records=records,
                        model_version=int(self.state.step),
                        telemetry=self._telemetry_payload(),
                    )
                try:
                    self._client.report_version(
                        pb.ReportVersionRequest(
                            worker_id=self.worker_id,
                            model_version=int(self.state.step),
                        )
                    )
                except Exception:
                    pass
        elif task.type == pb.EVALUATION:
            if not self._has_trained_state():
                # Same guard as Worker._evaluate_task: never report metrics
                # from randomly initialised params.  The condition is
                # deterministic across ranks (state/step identical), so all
                # ranks skip together; the leader re-queues the task.  No
                # early return: on_task_end must pair with the
                # on_task_start already fired above.
                if self.is_leader:
                    self._data_service.report_task(
                        task,
                        err="no trained state for evaluation",
                        transient=True,
                    )
            else:
                records = self._evaluate_task(task)
                if self.is_leader:
                    self._data_service.report_task(task, records=records)
        elif task.type == pb.PREDICTION:
            records = self._predict_task(task)
            if self.is_leader:
                self._data_service.report_task(task, records=records)
        elif task.type == pb.SAVE_MODEL:
            self._save(force=True)
            if self.is_leader:
                from elasticdl_tpu.worker.worker import export_for_task

                # Params are replicated => fully addressable on every
                # host; the leader alone writes the export.  No trained
                # state (deterministic across ranks) => report failure so
                # the task re-queues instead of silently skipping.
                try:
                    export_for_task(
                        self.state, self.spec, task,
                        sample_features=getattr(
                            self, "sample_features", None
                        ),
                    )
                except RuntimeError as exc:
                    self._data_service.report_task(task, err=str(exc))
                else:
                    self._data_service.report_task(task, records=0)
        else:
            logger.warning("SPMD worker ignoring task type %s", task.type)
            if self.is_leader:
                self._data_service.report_task(task, records=0)
        invoke_callbacks(self.spec.callbacks, "on_task_end", task, records)
        return records

    def _telemetry_payload(self) -> dict:
        """Leader-rank telemetry piggybacked on task reports (int64 on
        the wire; rates pre-scaled to milli units) — same shape as
        Worker._telemetry_payload so the master's snapshot and
        `elasticdl top` render both worker kinds identically."""
        payload = {
            "steps_per_sec_milli": int(
                self.step_rate.steps_per_sec * 1000
            ),
            "model_step": (
                int(self.state.step) if self.state is not None else 0
            ),
        }
        for phase, ms in _phase_timer.totals_milli().items():
            payload[f"phase_{phase}_ms"] = ms
        return payload

    def _train_task(self, task: pb.Task) -> int:
        if self._profile_dir and not self._profiled:
            self._profiled = True
            from elasticdl_tpu.common import profiler

            with profiler.trace(self._profile_dir):
                with profiler.annotate(f"task-{task.task_id}"):
                    records = self._train_task_inner(task)
                    if self.last_loss is not None:
                        jax.block_until_ready(self.last_loss)
            return records
        return self._train_task_inner(task)

    def _train_task_inner(self, task: pb.Task) -> int:
        records = 0
        steps_before = _phase_timer.steps

        def steps_done() -> int:   # of this task: one rank a process
            return _phase_timer.steps - steps_before

        # Slice-local reads (SURVEY §3.3 per-worker disjoint reads): each
        # rank reads only its addressable rows of every full global batch
        # — aggregate host IO is O(shard), not O(world_size * shard).
        local = mesh_lib.local_batch_range(self.mesh, self.minibatch_size)
        if local is not None:
            batches = self._data_service.local_batches_for_task(
                task, self.minibatch_size, self._feed,
                self._feed_bulk, local[0], local[1],
            )
        else:  # non-contiguous local rows: every rank reads everything
            batches = (
                (batch, real, False)
                for batch, real in self._data_service.batches_for_task(
                    task, self.minibatch_size, self._feed,
                    feed_bulk=self._feed_bulk,
                )
            )
        from elasticdl_tpu.worker.sync import fetch_loss
        from elasticdl_tpu.worker.task_data_service import prefetch_batches
        from elasticdl_tpu.worker.worker import finish_train_task

        def mark_recovered():
            if self._recovery_t0 is not None:
                # BASELINE.md's headline elasticity metric: preemption
                # (epoch bump observed) -> first post-restore optimizer
                # step.
                logger.info(
                    "elastic recovery: %.2fs (epoch %d, world %d, "
                    "resumed at step %d)",
                    time.time() - self._recovery_t0, self._epoch,
                    self.num_processes, int(self.state.step),
                )
                self._recovery_t0 = None

        def make_gb(one_batch, one_is_local):
            # Global-array assembly = this loop's host->device staging.
            with _phase_timer.phase("h2d_stage"):
                if one_is_local:
                    return mesh_lib.make_global_batch_from_local(
                        one_batch, self.mesh, self.minibatch_size,
                        local[0],
                    )
                return mesh_lib.make_global_batch(one_batch, self.mesh)

        # Second buffering level: the global batch for step k+1 is
        # assembled — shard transfers issued — on the consumer thread
        # while step k's collective executes.  The host batch rides along
        # untouched: _ensure_state wants host arrays.
        def device_stage(item):
            staged_batch, staged_real, staged_is_local = item
            if self.state is None:
                # init_state_global (first loop iteration) must be
                # the mesh's FIRST collective program; assembling
                # global arrays ahead of it breaks the multi-process
                # CPU backend used in tests.  Nothing to overlap
                # before step 1 anyway.
                return (*item, None)
            return (*item, make_gb(staged_batch, staged_is_local))

        # host read/parse overlaps the collective step (double buffering)
        for item in prefetch_batches(
            batches, device_stage=device_stage, phase_timer=_phase_timer
        ):
            batch, real, is_local, gb = item
            self._ensure_state(batch, global_rows=self.minibatch_size)
            records += real
            _phase_timer.mark(step=steps_done())
            if gb is None:
                gb = make_gb(batch, is_local)
            self.state, self.last_loss = self.trainer.train_on_global_batch(
                self.state, gb
            )
            mark_recovered()
            _phase_timer.step_done()
            self._maybe_checkpoint()
        _phase_timer.mark(step=None)
        _phase_timer.flush()
        if self.last_loss is not None:
            finish_train_task(
                self.step_rate, self._summary, steps_done(),
                fetch=lambda: fetch_loss(self.state, self.last_loss),
                model_step=lambda: int(self.state.step),
            )
        return records

    def _evaluate_task(self, task: pb.Task) -> int:
        from elasticdl_tpu.worker.sync import state_at_version

        records = 0
        all_labels, all_preds = [], []
        eval_state, actual_version = None, None
        for batch, real in self._data_service.batches_for_task(
            task, self.minibatch_size, self._feed,
            feed_bulk=self._feed_bulk,
        ):
            self._ensure_state(batch)
            if actual_version is None:
                # Deterministic across ranks (same state/saver contents),
                # so every rank restores — or falls back — together.
                eval_state, actual_version = state_at_version(
                    self.state, self._saver, task.model_version
                )
            features = mesh_lib.make_global_batch(
                batch["features"], self.mesh
            )
            preds = self.trainer.predict_on_global_batch(
                eval_state, features
            )
            # Data-sharded output: gather the full array onto every host
            # so metric fns (host-side, e.g. AUC) see all rows.
            preds = _allgather(preds)
            all_labels.append(np.asarray(batch["labels"])[:real])
            all_preds.append(np.asarray(preds)[:real])
            records += real
        if records and self.is_leader:
            from elasticdl_tpu.worker.worker import (
                report_evaluation_with_samples,
            )

            labels = np.concatenate(all_labels)
            preds = np.concatenate(all_preds)
            version = (
                actual_version
                if actual_version is not None and actual_version >= 0
                else int(self.state.step)
            )
            metrics = {
                name: float(fn(labels, preds))
                for name, fn in self.spec.eval_metrics.items()
            }
            report_evaluation_with_samples(
                self._client, self.worker_id, version,
                metrics, records, labels, preds, task_id=task.task_id,
            )
        return records

    def _predict_task(self, task: pb.Task) -> int:
        records = 0
        rows = []
        processor = self.spec.prediction_outputs_processor
        for batch, real in self._data_service.batches_for_task(
            task, self.minibatch_size, self._feed,
            feed_bulk=self._feed_bulk,
        ):
            self._ensure_state(batch)
            features = mesh_lib.make_global_batch(
                batch["features"], self.mesh
            )
            preds = _allgather(
                self.trainer.predict_on_global_batch(self.state, features)
            )
            rows.append(np.asarray(preds)[:real])
            records += real
        if rows and processor is not None and self.is_leader:
            # reference C18 contract; leader-only so the zoo's sink sees
            # each batch once, not once per rank — and buffered per task
            # (ADVICE r3) so a mid-task failure + re-queue cannot deliver
            # partial duplicates.  At-least-once at task granularity.
            for chunk in rows:
                processor.process(chunk, self.worker_id)
        if rows:
            # Keyed by task_id so a task re-processed after a remesh (the
            # lease was recovered before the leader reported) OVERWRITES
            # its rows instead of duplicating them; with an output dir the
            # leader also makes each task's rows durable immediately, so
            # rows reported before a process restart are never lost.
            self.predictions = getattr(self, "predictions", {})
            self.predictions[task.task_id] = np.concatenate(rows)
            if self.is_leader and self._output_dir:
                os.makedirs(self._output_dir, exist_ok=True)
                np.save(
                    os.path.join(
                        self._output_dir, f"part-{task.task_id:05d}.npy"
                    ),
                    self.predictions[task.task_id],
                )
        return records

    def _flush_predictions(self) -> None:
        """Cluster predict jobs: assemble the per-task part files (written
        durably as each task completed) into one predictions.npy — the
        same final artifact local mode produces (client/api.py)."""
        if not self.is_leader or not self._output_dir:
            return
        import glob

        parts = sorted(
            glob.glob(os.path.join(self._output_dir, "part-*.npy"))
        )
        if not parts:
            return
        merged = np.concatenate([np.load(p) for p in parts])
        np.save(os.path.join(self._output_dir, "predictions.npy"), merged)
        logger.info(
            "Merged %d prediction part files (%d rows) into %s",
            len(parts), len(merged),
            os.path.join(self._output_dir, "predictions.npy"),
        )

    def _has_trained_state(self) -> bool:
        if self.state is not None and int(self.state.step) > 0:
            return True
        return (
            self._saver is not None
            and self._saver.latest_step() is not None
        )

    # ---- elasticity ----------------------------------------------------

    # Exit code for a clean topology-change restart (distinct from the
    # watchdog's WEDGED_EXIT_CODE only for log forensics; both relaunch
    # WITHOUT charging the pod manager's failure budget).
    TOPOLOGY_RESTART_EXIT_CODE = 44

    def _restart_for_topology_change(self) -> None:
        """Exit for relaunch at a new topology, best-effort flushing any
        in-flight async checkpoint first.  The flush is time-bounded in a
        side thread: with all peers alive (scale events) it completes and
        preserves up to checkpoint_steps of work; with a dead peer the
        distributed flush cannot complete and we leave after the bound
        (recovery then restores the previous committed step)."""
        saver = self._saver
        if saver is not None:
            flusher = threading.Thread(
                target=lambda: saver.wait_until_finished(), daemon=True
            )
            flusher.start()
            flusher.join(timeout=10.0)
        logger.info(
            "Rank %d: topology change; restarting process for a clean "
            "runtime bootstrap", self.process_id,
        )
        os._exit(self.TOPOLOGY_RESTART_EXIT_CODE)

    def _re_rendezvous(self, settle_timeout_s: float = 60.0) -> bool:
        """Membership changed: rejoin with the new topology and restore
        state from the latest checkpoint.

        MULTI-PROCESS topologies restart the process instead of
        re-initializing in place: an in-process jax.distributed
        shutdown/re-init leaves per-process library state (observed:
        Orbax's distributed-barrier counters) out of sync with
        freshly-booted peers, which can hang the first post-remesh
        collective checkpoint; and a world-1 survivor cannot call
        jax.distributed.initialize at all once its backend exists.  A
        process restart makes every member of the new epoch identically
        fresh — the same, proven path the wedge watchdog and
        coordination-service aborts already take; recovery cost is the
        same restore-from-checkpoint cycle.  Only a topology that stays
        single-process (no distributed runtime involved on either side)
        re-meshes in place."""
        # Restart decision comes BEFORE any barrier participation: a rank
        # that confirmed the new epoch and THEN exited would release the
        # barrier for fresh joiners, who would initialize a world whose
        # members are already gone and wedge until their watchdogs fire.
        if jax.distributed.is_initialized() or self.num_processes > 1:
            self._restart_for_topology_change()
        self._recovery_t0 = time.time()
        # Peek (no confirmation) at the new spec: a single-process worker
        # growing into a multi-process world must also restart — its XLA
        # backend already exists, so jax.distributed.initialize would
        # refuse to run in this process.
        peek = self._rpc_policy.call(
            lambda: self._client.get_cluster_spec(
                pb.GetClusterSpecRequest(worker_id=self.worker_id)
            ),
            description="get_cluster_spec.peek",
        )
        if peek.world_size > 1 or peek.expected_world_size > 1:
            self._restart_for_topology_change()
        # Wait for a settled, group-confirmed epoch (the same barrier as
        # first join).  A timeout means the group never stabilised around
        # us — exit and let the pod manager relaunch a fresh process.
        self._in_rendezvous_wait = True
        try:
            spec, me = wait_for_confirmed_epoch(
                self._client,
                self.worker_id,
                poll_s=self._wait_sleep_s,
                timeout_s=settle_timeout_s,
                rpc_policy=self._rpc_policy,
            )
        finally:
            self._in_rendezvous_wait = False
        if spec is None:
            logger.warning(
                "Worker %d: no confirmed epoch within %.0fs; restarting",
                self.worker_id, settle_timeout_s,
            )
            return False
        if me is None or spec.world_size == 0:
            logger.warning(
                "Worker %d evicted at epoch %d; exiting",
                self.worker_id, spec.rendezvous_id,
            )
            return False
        self._epoch = spec.rendezvous_id
        self.process_id = me.rank
        self.num_processes = spec.world_size
        self._coordinator = spec.coordinator_address or self._coordinator
        self.state = None  # re-init + checkpoint restore on next batch
        self.trainer = None
        self.setup()
        self.remesh_count += 1
        logger.info(
            "Rank %d re-rendezvoused: epoch %d, world %d, coordinator %s "
            "(%.2fs)",
            self.process_id, self._epoch, self.num_processes,
            self._coordinator, time.time() - self._recovery_t0,
        )
        return True

    # ---- helpers -------------------------------------------------------

    def save_checkpoint_and_flush(self) -> None:
        """Synchronous final checkpoint (preemption hook: the process is
        about to die, so wait for the write to land).

        Multi-process mode must NOT save here: the Orbax save is a
        distributed collective, and SIGTERM reaches ranks at arbitrary
        points (possibly mid-step, at different state.step values), so a
        signal-time save can enter mismatched collectives — hanging the
        grace window or corrupting the checkpoint.  Instead the flag stops
        the main loop at the next task boundary; recovery rides the
        periodic checkpoints + task re-lease (the recovery unit is the
        task, not the step)."""
        if self.num_processes > 1:
            self._preempted = True
            logger.info(
                "Rank %d preempted; skipping signal-time collective save "
                "(periodic checkpoints + task re-lease cover recovery)",
                self.process_id,
            )
            return
        self._save(force=True)
        if self._saver is not None:
            self._saver.wait_until_finished()

    def _save(self, force: bool = False) -> None:
        # Orbax distributed save: EVERY rank participates (each writes its
        # addressable shards); the decision is deterministic on step so all
        # ranks enter together.
        if self._saver is not None and self.state is not None:
            self._saver.save(self.state, force=force)

    def _maybe_checkpoint(self) -> None:
        # Deterministic on step, so all ranks enter the collective save
        # together.
        if (
            self._saver is not None
            and self._checkpoint_steps
            and int(self.state.step) % self._checkpoint_steps == 0
        ):
            self._saver.save(self.state)

    def _feed(self, records):
        return self.spec.feed(records, getattr(self._reader, "metadata", {}))

    @property
    def _feed_bulk(self):
        """Vectorized-parse closure (same contract as Worker._feed_bulk)."""
        if self.wire_format == "dedup":  # unreachable today; see __init__
            fn = self.spec.feed_bulk_dedup
        elif self.compact_wire:
            fn = self.spec.feed_bulk_compact
        else:
            fn = self.spec.feed_bulk
        if fn is None:
            return None
        metadata = getattr(self._reader, "metadata", {})
        return lambda buf, sizes: fn(buf, sizes, metadata)


from elasticdl_tpu.parallel.collectives import (  # noqa: E402
    host_allgather as _allgather,
)
