"""Worker process entry point.

Parity: reference python/worker/main.py (SURVEY.md C7).  Connects to the
master over gRPC, loads the model-zoo spec, builds the device mesh, runs
the task loop.
"""

from __future__ import annotations

import os

from elasticdl_tpu.common import args as args_lib
from elasticdl_tpu.common.constants import (
    GRPC_MAX_MESSAGE_LENGTH,
    WorkerEnv,
)
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.common.model_handler import get_model_spec
from elasticdl_tpu.data.reader import create_data_reader

logger = get_logger(__name__)


def build_master_client(addr: str, retry_policy=None):
    import grpc

    from elasticdl_tpu.common.resilience import (
        default_policy,
        wait_for_channel_ready,
    )
    from elasticdl_tpu.proto.service import MasterStub

    policy = retry_policy if retry_policy is not None else default_policy()
    channel = grpc.insecure_channel(
        addr,
        options=[
            ("grpc.max_send_message_length", GRPC_MAX_MESSAGE_LENGTH),
            ("grpc.max_receive_message_length", GRPC_MAX_MESSAGE_LENGTH),
        ],
    )
    # Bounded, jittered wait instead of a bare 60s block: a master that
    # never comes up turns into RetryBudgetExhausted -> exit code 45, a
    # charged relaunch, rather than an opaque hang-then-crash.
    wait_for_channel_ready(channel, policy)
    return MasterStub(channel, retry_policy=policy)


def start_keep_alive(client, worker_id: int, master_addr: str) -> str:
    """Self-report this worker's reachable address immediately, then keep
    reporting liveness on a daemon thread.  The address report closes the
    real-k8s gap where the watch delivers RUNNING before the pod IP is
    assigned (the coordinator address must never fall back to localhost on
    multi-host)."""
    import threading
    import time

    from elasticdl_tpu.common.constants import KEEP_ALIVE_INTERVAL_S
    from elasticdl_tpu.common.net_utils import get_reachable_address
    from elasticdl_tpu.proto import elasticdl_pb2 as pb

    address = get_reachable_address(master_addr)

    def beat():
        try:
            client.keep_alive(
                pb.KeepAliveRequest(
                    worker_id=worker_id,
                    timestamp_ms=int(time.time() * 1000),
                    address=address,
                )
            )
        except Exception:
            pass  # master briefly unreachable; liveness is best-effort

    beat()

    def loop():
        while True:
            time.sleep(KEEP_ALIVE_INTERVAL_S)
            beat()

    threading.Thread(target=loop, daemon=True).start()
    return address


def wait_for_membership(client, worker_id: int, poll_s: float = 0.5):
    """Block until this worker appears in a settled, group-confirmed
    cluster spec (see elasticdl_tpu.worker.spmd.wait_for_confirmed_epoch).
    """
    from elasticdl_tpu.worker.spmd import wait_for_confirmed_epoch

    return wait_for_confirmed_epoch(client, worker_id, poll_s=poll_s)


def main(argv=None):
    import sys

    from elasticdl_tpu.common import faults
    from elasticdl_tpu.common.resilience import (
        RETRY_EXHAUSTED_EXIT_CODE,
        RetryBudgetExhausted,
    )

    # Chaos runs propagate their seeded fault schedule to subprocess
    # workers via the environment; no-op otherwise.
    faults.configure_from_env()
    try:
        return _main(argv)
    except RetryBudgetExhausted as exc:
        # The master stayed unreachable past the whole retry budget
        # (at startup or mid-run).  Exit with the distinct charged code
        # so the pod manager relaunches us instead of us spinning on a
        # dead control plane.
        logger.error("Worker retry budget exhausted: %s", exc)
        sys.exit(RETRY_EXHAUSTED_EXIT_CODE)


def _main(argv=None):
    import time

    entered = time.perf_counter()
    args = args_lib.parse_worker_args(argv)
    # the process's start-up record (docs/OBSERVABILITY.md "Start-up
    # catalogue"): `boot` up to here, `job_setup` from here
    from elasticdl_tpu.common import profiler, programs

    startup = profiler.process_phase_timer()
    startup.begin_startup(entered)
    # A relaunched worker loads the train-step executable from the
    # persistent compile cache instead of recompiling — the biggest
    # single chunk of elastic recovery time.
    from elasticdl_tpu.common.virtual_mesh import enable_compile_cache

    enable_compile_cache(args.compilation_cache_dir)
    programs.install_compile_listeners()
    worker_id = int(
        os.environ.get(WorkerEnv.WORKER_ID, args.worker_id)
    )
    master_addr = os.environ.get(WorkerEnv.MASTER_ADDR, args.master_addr)
    # Cross-process tracing: --event_log wins; otherwise the master
    # exported ELASTICDL_EVENT_LOG into our environment (same wire as
    # the chaos schedule).
    from elasticdl_tpu.common import events

    if getattr(args, "event_log", ""):
        events.configure(args.event_log, role="worker",
                         worker_id=worker_id)
    else:
        events.configure_from_env(role="worker", worker_id=worker_id)
    # /metrics + /healthz + /varz.  Always an ephemeral port: worker argv
    # is the master's re-serialized argv, so a fixed port would collide
    # when master and workers share a host (tests, ProcessK8sClient).
    from elasticdl_tpu.common.telemetry import TelemetryServer

    telemetry = TelemetryServer(role="worker")
    try:
        telemetry.start()
        logger.info("Worker %d telemetry on port %d",
                    worker_id, telemetry.port)
    except Exception:
        logger.exception("telemetry server failed to start")
    from elasticdl_tpu.common.resilience import default_policy

    budget = getattr(args, "rpc_retry_budget_s", 0.0)
    rpc_policy = (
        default_policy(max_elapsed_s=budget) if budget else default_policy()
    )
    client = build_master_client(master_addr, retry_policy=rpc_policy)
    spec = get_model_spec(
        args.model_zoo,
        args.model_def,
        model_params=args.model_params,
        dataset_fn=args.dataset_fn,
        loss=args.loss,
        optimizer=args.optimizer,
        eval_metrics_fn=args.eval_metrics_fn,
        prediction_outputs_processor=getattr(
            args, "prediction_outputs_processor", ""
        ),
        arena_dtype=getattr(args, "arena_dtype", ""),
        store_cache_dtype=getattr(args, "store_cache_dtype", ""),
    )
    if spec.custom_data_reader is not None:
        reader = spec.custom_data_reader(data_origin=args.training_data)
    else:
        reader = create_data_reader(args.training_data)

    from elasticdl_tpu.worker.worker import Worker

    saver_factory = None
    if args.checkpoint_dir:
        # NOT constructed here: Orbax touches the XLA backend, and in
        # cluster mode jax.distributed.initialize must run first (the
        # SPMDWorker calls the factory inside setup()).
        def saver_factory():
            from elasticdl_tpu.common.save_utils import CheckpointSaver

            return CheckpointSaver(
                args.checkpoint_dir, keep_max=args.keep_checkpoint_max
            )

    tb_dir = (
        os.path.join(args.tensorboard_log_dir, f"worker-{worker_id}")
        if args.tensorboard_log_dir
        else ""
    )

    if args.distribution_strategy != "Local" and args.num_workers > 1:
        # Cluster SPMD: all worker processes form ONE global mesh and run
        # the same collective step — there is one model by construction
        # (worker/spmd.py).  Rank/topology comes from the master's
        # rendezvous; wait until this worker is a member of a settled
        # epoch.
        from elasticdl_tpu.proto import elasticdl_pb2 as pb
        from elasticdl_tpu.worker.spmd import SPMDWorker

        my_addr = start_keep_alive(client, worker_id, master_addr)
        cluster, me = wait_for_membership(client, worker_id)
        logger.info(
            "Worker %d joined epoch %d as rank %d/%d (addr=%s, "
            "coordinator=%s)",
            worker_id, cluster.rendezvous_id, me.rank, cluster.world_size,
            my_addr, cluster.coordinator_address,
        )
        worker = SPMDWorker(
            worker_id=worker_id,
            master_client=client,
            data_reader=reader,
            spec=spec,
            minibatch_size=args.minibatch_size,
            process_id=me.rank,
            num_processes=cluster.world_size,
            coordinator_address=cluster.coordinator_address,
            use_bf16=args.use_bf16,
            checkpoint_saver_factory=saver_factory,
            checkpoint_steps=args.checkpoint_steps,
            initial_epoch=cluster.rendezvous_id,
            output_dir=getattr(args, "output", ""),
            wedge_grace_s=args.wedge_grace_s,
            compact_wire=getattr(args, "compact_wire", False),
            wire_format=getattr(args, "wire_format", ""),
            tensorboard_dir=tb_dir,
            profile_dir=(
                os.path.join(args.profile_dir, f"worker-{worker_id}")
                if args.profile_dir
                else ""
            ),
            rpc_policy=rpc_policy,
        )
    else:
        worker = Worker(
            worker_id=worker_id,
            master_client=client,
            data_reader=reader,
            spec=spec,
            minibatch_size=args.minibatch_size,
            use_bf16=args.use_bf16,
            checkpoint_saver=saver_factory() if saver_factory else None,
            checkpoint_steps=args.checkpoint_steps,
            compact_wire=getattr(args, "compact_wire", False),
            wire_format=getattr(args, "wire_format", ""),
            tensorboard_dir=tb_dir,
            profile_dir=(
                os.path.join(args.profile_dir, f"worker-{worker_id}")
                if args.profile_dir
                else ""
            ),
        )
    drain_fn = (
        worker.save_checkpoint_and_flush
        if hasattr(worker, "save_checkpoint_and_flush")
        else worker.model_owner.save_and_flush
    )
    if saver_factory is not None:
        # Preemptible VMs: SIGTERM arrives with a grace window — flush one
        # final synchronous checkpoint so the next topology restores from
        # the last step, not the last periodic save (SURVEY.md §5).
        from elasticdl_tpu.common.preemption import install_preemption_hook

        install_preemption_hook(drain_fn)
    notice_source = getattr(args, "preemption_notice_file", "")
    if notice_source:
        # Maintenance-event awareness (SURVEY §7 C4 mapping): act on the
        # NOTICE — drain at a task boundary and checkpoint while the
        # grace window is still all ours — instead of racing the kill.
        from elasticdl_tpu.common.preemption import (
            MaintenanceNoticeWatcher,
            any_notice_checker,
            file_notice_checker,
            gce_metadata_checker,
        )

        checker = (
            any_notice_checker(
                gce_metadata_checker("preempted"),
                gce_metadata_checker("maintenance-event"),
            )
            if notice_source == "gce-metadata"
            else file_notice_checker(notice_source)
        )
        # The notice hook only SETS the drain flag; the main thread
        # checkpoints at its next task boundary (a save from the watcher
        # thread would race the training loop's state mutation).
        MaintenanceNoticeWatcher(checker, worker.drain_and_stop).start()

    # `job_setup` ends here; the loop closes `worker_setup` at its first
    # `get_task` (the SPMD loop after joining the distributed runtime)
    startup.startup("worker_setup")
    ok = worker.run()
    logger.info("Worker %d exiting (clean=%s)", worker_id, ok)


if __name__ == "__main__":
    main()
