"""Master process: job brain.

Parity: reference python/master/main.py (SURVEY.md C2, call stack §3.2):
build shards -> task manager -> gRPC servicer -> pod manager (cluster mode)
-> evaluation service -> wait for completion -> final eval/save -> exit.
"""

from __future__ import annotations

import threading
import time
from concurrent import futures
from typing import Optional

from elasticdl_tpu.common import args as args_lib
from elasticdl_tpu.common.constants import GRPC_MAX_MESSAGE_LENGTH
from elasticdl_tpu.common.k8s_client import parse_volumes
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.data.reader import create_data_reader
from elasticdl_tpu.master.evaluation_service import EvaluationService
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_manager import (
    TaskManager,
    create_shards_from_ranges,
)
from elasticdl_tpu.proto import elasticdl_pb2 as pb

logger = get_logger(__name__)


class Master:
    """Owns the control plane of one job.

    Cluster-elastic mode (SURVEY.md §3.2) engages when a k8s client is
    passed: the master constructs the RendezvousServer (membership epochs)
    and PodManager (create/watch/relaunch worker pods), generates worker
    pod commands by re-serializing its own args (argv is the config wire
    format, as in the reference), and injects a SAVE_MODEL task at job end
    so a worker exports the final model.  With `k8s_client=None` the
    master is control-plane-only (Local mode, unit tests).
    """

    def __init__(
        self, args, data_reader=None, validation_reader=None, k8s_client=None
    ):
        self.args = args
        self.job_type = getattr(args, "job_type", "train")
        self._reader = data_reader
        self._val_reader = validation_reader
        if self._reader is None and args.training_data:
            self._reader = create_data_reader(args.training_data)
        if self._val_reader is None and args.validation_data:
            self._val_reader = create_data_reader(args.validation_data)

        training_shards = (
            create_shards_from_ranges(
                self._reader.create_shards(), args.records_per_task
            )
            if self._reader and self.job_type == "train"
            else []
        )
        evaluation_shards = (
            create_shards_from_ranges(
                self._val_reader.create_shards(), args.records_per_task
            )
            if self._val_reader
            else []
        )
        prediction_shards = []
        if getattr(args, "prediction_data", "") and self.job_type == "predict":
            pred_reader = create_data_reader(args.prediction_data)
            prediction_shards = create_shards_from_ranges(
                pred_reader.create_shards(), args.records_per_task
            )
        if not (training_shards or evaluation_shards or prediction_shards):
            raise ValueError(
                f"job type {self.job_type!r} has no input data "
                "(--training_data / --validation_data / --prediction_data)"
            )
        persist_path = None
        restore_cutoff = None
        if getattr(args, "checkpoint_dir", "") and self.job_type == "train":
            import os

            # Master fault tolerance: completed-shard journal lives next
            # to the model checkpoints; a relaunched master pod resumes
            # the epoch instead of retraining it.  The journal is only
            # trusted up to the newest MODEL checkpoint's STEP — a shard
            # completed at a later model version has gradients the
            # restored params never saw, so it must re-run; with no model
            # checkpoint at all the journal is orphaned and discarded
            # (resuming the task queue without resuming the model would
            # silently drop that data from training).
            persist_path = os.path.join(
                args.checkpoint_dir, "task_state.json"
            )
            restore_cutoff = _latest_model_checkpoint_step(
                args.checkpoint_dir
            )
            if restore_cutoff is None and os.path.exists(persist_path):
                logger.warning(
                    "Discarding orphaned task journal %s (no model "
                    "checkpoint to pair it with)", persist_path,
                )
                try:
                    os.remove(persist_path)
                except OSError:
                    pass
        self.task_manager = TaskManager(
            training_shards=training_shards,
            evaluation_shards=evaluation_shards,
            prediction_shards=prediction_shards,
            num_epochs=args.num_epochs,
            lease_timeout_s=args.task_lease_timeout_s,
            shuffle_shards=True,
            shuffle_seed=0,
            persist_path=persist_path,
            restore_cutoff_step=restore_cutoff,
            straggler_multiple=getattr(args, "straggler_multiple", 3.0),
            straggler_min_tasks=getattr(args, "straggler_min_tasks", 3),
        )
        # evaluate-only jobs: the eval round IS the job — inject upfront.
        if self.job_type == "evaluate" and evaluation_shards:
            self.task_manager.create_evaluation_tasks(model_version=0)
        eval_summary = None
        if getattr(args, "tensorboard_log_dir", ""):
            import os

            from elasticdl_tpu.common.summary import SummaryWriter

            eval_summary = SummaryWriter(
                os.path.join(args.tensorboard_log_dir, "master")
            )
        self.evaluation_service = EvaluationService(
            self.task_manager,
            evaluation_steps=args.evaluation_steps,
            start_delay_secs=args.evaluation_start_delay_secs,
            throttle_secs=args.evaluation_throttle_secs,
            summary_writer=eval_summary,
            eval_metrics=self._load_eval_metrics(args),
        )
        self.rendezvous_server = None
        self.pod_manager = None
        self.recovery_clock = None
        self.policy_engine = None
        self.serving_fleet = None
        self.serving_policy = None
        self.freshness = None
        self.metric_history = None
        self.slo_evaluator = None
        self.flight_recorder = None
        self._k8s = k8s_client
        if k8s_client is not None:
            from elasticdl_tpu.master.pod_manager import PodManager
            from elasticdl_tpu.master.recovery import RecoveryClock
            from elasticdl_tpu.master.rendezvous_server import RendezvousServer

            self.recovery_clock = RecoveryClock()
            self.rendezvous_server = RendezvousServer(
                coordinator_port=getattr(args, "coordinator_port", 51001)
            )
            self.pod_manager = PodManager(
                k8s_client,
                task_manager=self.task_manager,
                rendezvous_server=self.rendezvous_server,
                job_name=args.job_name,
                num_workers=args.num_workers,
                image=getattr(args, "image_name", ""),
                worker_command=self._worker_command,
                relaunch_on_worker_failure=getattr(
                    args, "relaunch_on_worker_failure", 3
                ),
                worker_resources=_parse_resources(
                    getattr(args, "worker_resource_request", "")
                ),
                priority_class=getattr(args, "worker_pod_priority", ""),
                on_job_abort=self._on_job_abort,
                recovery_clock=self.recovery_clock,
                volumes=parse_volumes(getattr(args, "volume", "")),
                workers_per_group=getattr(args, "workers_per_group", 1),
            )
        self.servicer = MasterServicer(
            self.task_manager,
            evaluation_service=self.evaluation_service,
            rendezvous_server=self.rendezvous_server,
            recovery_clock=self.recovery_clock,
        )
        # The actuator that closes the elastic loop (ROADMAP item 4):
        # constructed whenever the pod machinery exists so snapshot()
        # and /metrics expose it, but its background thread only runs
        # with --policy_interval > 0.
        if self.pod_manager is not None:
            from elasticdl_tpu.master.policy import (
                PolicyConfig,
                PolicyEngine,
            )

            self.policy_engine = PolicyEngine(
                self.task_manager,
                self.pod_manager,
                PolicyConfig.from_args(args),
                telemetry_fn=self.servicer.worker_telemetry,
            )
        # Serving fleet supervisor (docs/SERVING.md "Fleet"): same
        # construction gate as the policy engine — needs the pod
        # machinery — plus an explicit replica count.
        if (
            self.pod_manager is not None
            and getattr(args, "serving_replicas", 0) > 0
        ):
            from elasticdl_tpu.master.freshness import FreshnessTracker
            from elasticdl_tpu.master.serving_fleet import (
                ServingFleetConfig,
                ServingFleetManager,
            )

            # Train-to-serve freshness: the manifest's own producer
            # stamp when a checkpoint dir is configured, observation
            # time otherwise.
            ckpt_dir = getattr(args, "checkpoint_dir", "")
            produced_time_fn = None
            if ckpt_dir:
                from elasticdl_tpu.common import save_utils

                def produced_time_fn(step, _dir=ckpt_dir):
                    meta = save_utils.read_produced_meta(_dir, step)
                    return meta.get("produced_unix_s") if meta else None

            self.freshness = FreshnessTracker(
                produced_time_fn=produced_time_fn
            )
            self.serving_fleet = ServingFleetManager(
                k8s_client,
                ServingFleetConfig.from_args(args),
                job_name=args.job_name,
                image=getattr(args, "image_name", ""),
                command_fn=self._serving_command,
                freshness=self.freshness,
            )
        # Metric history + SLO judgment (docs/OBSERVABILITY.md "Metric
        # history & SLOs"): constructed when either loop is enabled so
        # `elasticdl slo` has evidence to render; `0=off` keeps both
        # threads parked exactly like the policy engine.
        history_interval = float(getattr(args, "history_interval", 0.0))
        slo_interval = float(getattr(args, "slo_interval", 0.0))
        incident_dir = getattr(args, "incident_dir", "")
        if history_interval > 0 or slo_interval > 0 or incident_dir:
            from elasticdl_tpu.common.flight import FlightRecorder
            from elasticdl_tpu.common.history import MetricHistory
            from elasticdl_tpu.common.programs import (
                default_program_registry,
            )
            from elasticdl_tpu.common.slo import SloEvaluator, shipped_specs

            self.metric_history = MetricHistory(
                registries=self.telemetry_registries(),
                capacity=int(getattr(args, "history_capacity", 512)),
                interval_s=history_interval,
            )
            # Incident flight recorder (docs/OBSERVABILITY.md "Request
            # tracing & incident bundles"): taps the span-event stream
            # for its forensic rings; without --incident_dir the rings
            # still fill but captures are skipped.
            self.flight_recorder = FlightRecorder(
                incident_dir=incident_dir or None,
                ring_capacity=int(getattr(args, "incident_ring", 256)),
                max_bundles=int(
                    getattr(args, "incident_max_bundles", 8)
                ),
                snapshot_fn=self.snapshot,
                history=self.metric_history,
                # recompile storms pend an immediate capture through
                # the registry's on_storm hook, and every bundle gains
                # a programs.json ledger section
                program_registry=default_program_registry(),
            ).install()
            self.slo_evaluator = SloEvaluator(
                self.metric_history,
                specs=shipped_specs(args),
                interval_s=slo_interval,
                on_breach=self.flight_recorder.breach,
            )
        # Serving autoscaler (docs/SERVING.md "Autoscaling &
        # backpressure"): needs the fleet to actuate and an explicit
        # --max_serving_replicas opt-in.  Burn-rate and shed-ratio
        # signals degrade to 0 gracefully when the history/SLO loops
        # are not configured — the engine then only ever scales down on
        # batch fill, which is the safe direction.
        if (
            self.serving_fleet is not None
            and getattr(args, "max_serving_replicas", 0) > 0
        ):
            from elasticdl_tpu.master.policy import (
                ServingPolicyConfig,
                ServingPolicyEngine,
            )

            self.serving_policy = ServingPolicyEngine(
                self.serving_fleet,
                ServingPolicyConfig.from_args(args),
                history=self.metric_history,
                evaluator=self.slo_evaluator,
            )
        self._grpc_server = None
        self._done = threading.Event()
        self._aborted: Optional[str] = None
        self.bound_port: Optional[int] = None
        self.telemetry = None
        self.task_manager.add_all_done_callback(self._on_all_done)
        # Final evaluation over the validation set: injected atomically by
        # the task manager the moment the queue first drains (no window in
        # which workers can observe job_finished before the eval round).
        self._final_eval_done = False
        self._evaluation_shards = evaluation_shards
        if evaluation_shards and self.job_type == "train":
            self.task_manager.add_pre_finish_provider(self._final_eval_tasks)
        # Cluster mode: final export rides the task queue — ONE SAVE_MODEL
        # task with the output dir in its config rider is injected when the
        # queue drains (after the final eval round; providers run in
        # registration order); the leasing worker exports.
        self._save_model_done = False
        if (
            self.pod_manager is not None
            and self.job_type == "train"
            and getattr(args, "output", "")
        ):
            self.task_manager.add_pre_finish_provider(self._save_model_tasks)

    @staticmethod
    def _load_eval_metrics(args):
        """Lazily load the zoo module's eval_metrics_fn so job-level
        rank metrics (AUC) can be recomputed exactly over merged worker
        samples.  The reference master loaded user model code too
        (ModelHandler, SURVEY C14); failures degrade to weighted
        per-shard means, never abort the job brain."""
        model_zoo = getattr(args, "model_zoo", "")
        model_def = getattr(args, "model_def", "")
        if not model_zoo or not model_def:
            return None
        try:
            from elasticdl_tpu.common.model_handler import load_module

            module, _ = load_module(model_zoo, model_def)
            factory = getattr(
                module,
                getattr(args, "eval_metrics_fn", "") or "eval_metrics_fn",
                None,
            )
            return factory() if factory else None
        except Exception:
            logger.exception(
                "Could not load eval_metrics_fn on the master; job-level "
                "metrics fall back to weighted per-shard means"
            )
            return None

    def _save_model_tasks(self):
        if self._save_model_done:
            return []
        self._save_model_done = True
        import json

        rider = json.dumps({
            "output": self.args.output,
            "saved_model": bool(
                getattr(self.args, "export_saved_model", False)
            ),
        })
        return [(pb.Shard(), pb.SAVE_MODEL, -1, rider)]

    # ---- lifecycle -----------------------------------------------------

    def _worker_command(self, worker_id: int):
        """Worker pod command: this master's args re-serialized as argv
        plus the worker's identity and the master's address (the reference
        passed these through env + argv the same way — SURVEY.md C21)."""
        worker_args = args_lib.build_arguments_from_parsed_result(
            self.args,
            filter_args={"job_type", "worker_id", "master_addr", "func"},
        )
        port = self.bound_port if self.bound_port else self.args.port
        master_host = (
            self._k8s.master_host(self.args.job_name)
            if self._k8s is not None
            else f"{self.args.job_name}-master"
        )
        import sys

        return (
            [sys.executable, "-m", "elasticdl_tpu.worker.main"]
            + worker_args
            + [
                "--master_addr", f"{master_host}:{port}",
                "--worker_id", str(worker_id),
                "--job_type", self.job_type,
            ]
        )

    def _serving_command(self, replica_id: int):
        """Serving replica pod command: `elasticdl serve` over the job's
        live checkpoint dir, so every replica hot-reloads from the same
        stream of steps the trainer writes."""
        import sys

        command = [
            sys.executable, "-m", "elasticdl_tpu.client.main", "serve",
            "--model_zoo", getattr(self.args, "model_zoo", "model_zoo"),
            "--model_def", getattr(self.args, "model_def", ""),
            "--port", str(getattr(self.args, "serving_port", 50061)),
        ]
        if getattr(self.args, "checkpoint_dir", ""):
            command += ["--checkpoint_dir", self.args.checkpoint_dir]
        return command

    def start(self, port: Optional[int] = None) -> int:
        """Serve gRPC, then (cluster mode) create the worker pods."""
        actual = self.start_grpc(port)
        if self.pod_manager is not None:
            self.pod_manager.start()
        if self.policy_engine is not None and self.policy_engine.start():
            logger.info(
                "Policy engine ticking every %.1fs",
                self.policy_engine.config.interval_s,
            )
        if self.serving_fleet is not None:
            self.serving_fleet.start()
            logger.info(
                "Serving fleet: %d replicas placed (probe interval %.1fs)",
                self.serving_fleet.config.replicas,
                self.serving_fleet.config.interval_s,
            )
        if self.metric_history is not None and self.metric_history.start():
            logger.info(
                "Metric history sampling every %.1fs",
                self.metric_history.interval_s,
            )
        if self.slo_evaluator is not None and self.slo_evaluator.start():
            logger.info(
                "SLO evaluator ticking every %.1fs",
                self.slo_evaluator.interval_s,
            )
        if self.serving_policy is not None and self.serving_policy.start():
            logger.info(
                "Serving policy engine ticking every %.1fs "
                "(fleet bounds [%d, %d])",
                self.serving_policy.config.interval_s,
                self.serving_policy.config.min_replicas,
                self.serving_policy.config.max_replicas,
            )
        # A restored task journal may already be terminal (all shards of
        # the final epoch done): no worker report will ever drain the
        # queue, so give the finish check one proactive run.
        self.task_manager.maybe_finish_if_drained()
        return actual

    def start_grpc(self, port: Optional[int] = None) -> int:
        import grpc

        from elasticdl_tpu.proto.service import add_master_servicer_to_server

        options = [
            ("grpc.max_send_message_length", GRPC_MAX_MESSAGE_LENGTH),
            ("grpc.max_receive_message_length", GRPC_MAX_MESSAGE_LENGTH),
        ]
        self._grpc_server = grpc.server(
            futures.ThreadPoolExecutor(max_workers=64), options=options
        )
        add_master_servicer_to_server(self.servicer, self._grpc_server)
        bind = f"[::]:{port if port is not None else self.args.port}"
        actual = self._grpc_server.add_insecure_port(bind)
        self.bound_port = actual
        self._grpc_server.start()
        logger.info("Master gRPC serving on %s", actual)
        self.task_manager.start_lease_reaper()
        return actual

    def _final_eval_tasks(self):
        """Pre-finish provider (runs under the task-manager lock): the
        final evaluation round, exactly once."""
        if self._final_eval_done:
            return []
        self._final_eval_done = True
        version = self.servicer.max_model_version
        logger.info(
            "Final evaluation: %d tasks at version %d",
            len(self._evaluation_shards), version,
        )
        from elasticdl_tpu.proto import elasticdl_pb2 as pb

        return [
            (shard, pb.EVALUATION, version)
            for shard in self._evaluation_shards
        ]

    def _on_all_done(self):
        self._done.set()

    def _on_job_abort(self, reason: str):
        logger.error("Job aborted: %s", reason)
        self._aborted = reason
        self._done.set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        from elasticdl_tpu.common.constants import KEEP_ALIVE_INTERVAL_S

        deadline = None if timeout is None else time.time() + timeout
        stale_after = 3 * KEEP_ALIVE_INTERVAL_S
        next_stale_check = time.time() + stale_after
        while True:
            remaining = None if deadline is None else deadline - time.time()
            if remaining is not None and remaining <= 0:
                return False
            if self._done.wait(timeout=0.2 if remaining is None else min(0.2, remaining)):
                if self._aborted is not None:
                    return False
                if self.task_manager.finished:
                    return True
            if self.pod_manager is not None and time.time() > next_stale_check:
                next_stale_check = time.time() + stale_after
                stale = self.servicer.stale_workers(stale_after)
                # only CURRENT workers are interesting: dead workers keep
                # their last-seen entry forever and would warn every cycle
                alive = set(self.pod_manager.alive_workers())
                stale = {w: s for w, s in stale.items() if w in alive}
                if stale:
                    logger.warning(
                        "Workers silent > %.0fs (lease reaper will recover "
                        "their tasks): %s",
                        stale_after,
                        {w: round(s, 1) for w, s in stale.items()},
                    )

    def snapshot(self) -> dict:
        """One observability surface for chaos runs, job-end logging, and
        /varz (`elasticdl top`): task progress, recovery durations, pod
        churn, per-worker telemetry, and the process-wide fault/retry
        counters — every number read from the unified metrics registry
        through the components that own it."""
        from elasticdl_tpu.common import faults, resilience

        out = {"tasks": self.task_manager.snapshot()}
        online = self.task_manager.online_snapshot()
        if online is not None:
            # perpetual (online) jobs: the `elasticdl top` online line
            out["online"] = online
        if self.recovery_clock is not None:
            out["recovery"] = self.recovery_clock.snapshot()
        if self.pod_manager is not None:
            out["pods"] = self.pod_manager.snapshot()
        if self.policy_engine is not None:
            out["policy"] = self.policy_engine.snapshot()
        if self.serving_fleet is not None:
            out["serving_fleet"] = self.serving_fleet.snapshot()
        if self.serving_policy is not None:
            out["serving_policy"] = self.serving_policy.snapshot()
        if self.freshness is not None:
            out["freshness"] = self.freshness.snapshot()
        if self.slo_evaluator is not None:
            slo = self.slo_evaluator.snapshot()
            if self.metric_history is not None:
                slo["history"] = self.metric_history.snapshot()
                if online is not None:
                    # stream-lag coverage for `elasticdl slo`: how many
                    # samples of the armed-watermark lag gauge the
                    # history holds (docs/ONLINE.md)
                    slo["history"]["stream_lag_samples"] = len(
                        self.metric_history.series(
                            "master_stream_watermark_lag_seconds"
                        )
                    )
            out["slo"] = slo
        out["workers"] = self.servicer.worker_telemetry()
        # Straggler stats come from the task manager's lease clock, not
        # from worker self-reports — merge them onto the same per-worker
        # rows so /varz and `elasticdl top` show one table.
        for wid, stats in self.task_manager.straggler_snapshot().items():
            out["workers"].setdefault(wid, {}).update(stats)
        out["resilience"] = resilience.stats()
        out["faults"] = faults.stats()
        if self.flight_recorder is not None:
            out["flight"] = self.flight_recorder.snapshot()
        return out

    def telemetry_registries(self) -> list:
        """All registries the master exposes on /metrics: the process-wide
        default plus each per-component registry."""
        from elasticdl_tpu.common import metrics as metrics_lib

        registries = [
            metrics_lib.default_registry(),
            self.task_manager.counters.registry,
        ]
        if self.recovery_clock is not None:
            registries.append(self.recovery_clock.metrics_registry)
        if self.pod_manager is not None:
            registries.append(self.pod_manager.metrics_registry)
        if self.policy_engine is not None:
            registries.append(self.policy_engine.metrics_registry)
        if self.serving_fleet is not None:
            registries.append(self.serving_fleet.metrics_registry)
        if self.serving_policy is not None:
            registries.append(self.serving_policy.metrics_registry)
        if self.freshness is not None:
            registries.append(self.freshness.metrics_registry)
        if self.slo_evaluator is not None:
            registries.append(self.slo_evaluator.metrics_registry)
        return registries

    def start_telemetry(self, port: int = 0) -> Optional[int]:
        """Start the /metrics + /healthz + /varz HTTP endpoint; returns
        the bound port, or None when the server could not start (never
        fatal — telemetry must not take down the job brain)."""
        from elasticdl_tpu.common import telemetry as telemetry_lib

        if self.telemetry is not None:
            return self.telemetry.port
        self.telemetry = telemetry_lib.TelemetryServer(
            registries=self.telemetry_registries(),
            role="master",
            port=port,
            healthz_fn=lambda: {
                "job_finished": self.task_manager.finished,
                "aborted": self._aborted,
            },
            varz_fn=lambda: {
                "snapshot": self.snapshot(),
                "grpc_port": self.bound_port,
            },
        )
        try:
            started = self.telemetry.start()
            logger.info("Master telemetry on port %d", started)
            return started
        except Exception:
            logger.exception("telemetry server failed to start")
            self.telemetry = None
            return None

    def stop(self):
        if self.flight_recorder is not None:
            # write any tap-queued captures while components can still
            # contribute a coherent Master.snapshot(), then untap
            self.flight_recorder.flush()
            self.flight_recorder.close()
        if self.serving_policy is not None:
            self.serving_policy.stop()
        if self.slo_evaluator is not None:
            self.slo_evaluator.stop()
        if self.metric_history is not None:
            self.metric_history.stop()
        if self.policy_engine is not None:
            self.policy_engine.stop()
        if self.serving_fleet is not None:
            self.serving_fleet.stop()
        if self.pod_manager is not None:
            self.pod_manager.stop()
        if self._grpc_server is not None:
            self._grpc_server.stop(grace=1)
        if self.telemetry is not None:
            self.telemetry.stop()
            self.telemetry = None


def main(argv=None, k8s_client=None, linger_s: float = 5.0) -> int:
    """Master process entry point.  In cluster strategies this constructs
    the full elastic stack (rendezvous + pod manager over a real — or with
    --use_fake_k8s an in-memory — Kubernetes client); tests may inject
    `k8s_client` directly."""
    args = args_lib.parse_master_args(argv)
    if k8s_client is None and args.distribution_strategy != "Local":
        if args.use_process_k8s:
            from elasticdl_tpu.common.k8s_client import ProcessK8sClient

            k8s_client = ProcessK8sClient()
        elif args.use_fake_k8s:
            from elasticdl_tpu.common.k8s_client import FakeK8sClient

            k8s_client = FakeK8sClient()
        else:
            from elasticdl_tpu.common.k8s_client import K8sClient

            k8s_client = K8sClient(
                namespace=args.namespace, job_name=args.job_name
            )
    # chaos runs configure the master's fault schedule via the
    # environment, same wire as subprocess workers; no-op otherwise
    from elasticdl_tpu.common import events, faults

    faults.configure_from_env()
    # structured tracing: --event_log wins; otherwise inherit the env
    # wire (ELASTICDL_EVENT_LOG).  export_env=True propagates the path
    # to subprocess workers the same way the fault schedule travels.
    if getattr(args, "event_log", ""):
        events.configure(args.event_log, role="master", export_env=True)
    else:
        events.configure_from_env(role="master")
    master = Master(args, k8s_client=k8s_client)
    master.start()
    master.start_telemetry(getattr(args, "telemetry_port", 0))
    ok = master.wait()
    logger.info("Job complete: %s", master.snapshot())
    if master.recovery_clock is not None and master.recovery_clock.history:
        logger.info(
            "Elastic recoveries this job: %s",
            [round(s, 2) for s in master.recovery_clock.history],
        )
    metrics = master.evaluation_service.latest_metrics()
    if metrics:
        logger.info("Final metrics: %s", metrics)
    # Linger so workers polling get_task observe job_finished and exit
    # cleanly instead of hitting a torn-down server mid-RPC.
    time.sleep(linger_s)
    master.stop()
    return 0 if ok else 1


def _latest_model_checkpoint_step(checkpoint_dir: str):
    """STEP of the newest finalized Orbax checkpoint (its digit-named dir),
    or None when no finalized model checkpoint exists.  Step-based — never
    a clock comparison: async checkpoint writes and cross-host clock skew
    make mtimes unusable for durability decisions."""
    import os

    if not os.path.isdir(checkpoint_dir):
        return None
    steps = [
        int(name)
        for name in os.listdir(checkpoint_dir)
        if name.isdigit()
        and os.path.isdir(os.path.join(checkpoint_dir, name))
    ]
    return max(steps) if steps else None


def _parse_resources(spec: str):
    """'cpu=1,memory=4096Mi' -> {'cpu': '1', 'memory': '4096Mi'}"""
    out = {}
    for part in (spec or "").split(","):
        if "=" in part:
            key, value = part.split("=", 1)
            out[key.strip()] = value.strip()
    return out


if __name__ == "__main__":
    main()
