"""Client API: job construction and submission.

Parity: reference elasticdl_client/api.py (SURVEY.md C18, call stack §3.1).
`Local` strategy runs master + worker in-process (no cluster); cluster
strategies build the master pod spec (command = `python -m
elasticdl_tpu.master.main` with all flags re-serialized as argv — argv is
the config wire format, as in the reference) and submit it through the
Kubernetes client.
"""

from __future__ import annotations

import threading

from elasticdl_tpu.common import args as args_lib
from elasticdl_tpu.common.constants import DistributionStrategy, PodType
from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger(__name__)


def train(args) -> int:
    if args.distribution_strategy == DistributionStrategy.LOCAL:
        return _train_local(args)
    return _submit_master_pod(args, job_type="train")


def evaluate(args) -> int:
    if args.distribution_strategy == DistributionStrategy.LOCAL:
        return _train_local(args, job_type="evaluate")
    return _submit_master_pod(args, job_type="evaluate")


def predict(args) -> int:
    if args.distribution_strategy == DistributionStrategy.LOCAL:
        return _train_local(args, job_type="predict")
    return _submit_master_pod(args, job_type="predict")


def _train_local(args, job_type: str = "train") -> int:
    """Master + worker(s) in one process: the zero-cluster path (and the
    dev loop for model-zoo modules)."""
    from elasticdl_tpu.common import profiler, programs
    from elasticdl_tpu.common.model_handler import get_model_spec
    from elasticdl_tpu.common.virtual_mesh import enable_compile_cache

    enable_compile_cache(getattr(args, "compilation_cache_dir", ""))
    # before the zoo module is imported: what it compiles is seen too
    programs.install_compile_listeners()
    from elasticdl_tpu.data.reader import create_data_reader
    from elasticdl_tpu.master.main import Master
    from elasticdl_tpu.proto.service import InProcessMasterClient
    from elasticdl_tpu.worker.worker import Worker

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
    args.job_type = job_type
    if job_type in ("evaluate", "predict") and not args.checkpoint_dir_for_init:
        raise ValueError(
            f"elasticdl {job_type} requires --checkpoint_dir_for_init "
            "(evaluating/predicting with random weights is meaningless)"
        )
    # Same observability surface as the cluster path (master/main.py):
    # span tracing via --event_log and /metrics + /healthz + /varz via
    # --telemetry_port.  One process here, so one telemetry server and
    # one event stream cover master and workers together.
    from elasticdl_tpu.common import events

    if getattr(args, "event_log", ""):
        events.configure(args.event_log, role="local")
    else:
        events.configure_from_env(role="local")
    master = Master(args)
    master.start_telemetry(getattr(args, "telemetry_port", 0))
    # The Local path never calls Master.start() (nothing to place on a
    # cluster), so the metric-history/SLO loops must start here for
    # --history_interval/--slo_interval to cover dev runs too.
    if master.metric_history is not None and master.metric_history.start():
        logger.info(
            "Metric history sampling every %.1fs",
            master.metric_history.interval_s,
        )
    if master.slo_evaluator is not None and master.slo_evaluator.start():
        logger.info(
            "SLO evaluator ticking every %.1fs",
            master.slo_evaluator.interval_s,
        )
    client = InProcessMasterClient(master.servicer)
    data_origin = {
        "train": args.training_data,
        "evaluate": args.validation_data,
        "predict": args.prediction_data,
    }[job_type]
    def make_reader():
        # One reader PER worker thread: the built-in readers are
        # thread-safe (pread-based), but zoo-contributed readers carry no
        # such contract, so never share an instance across workers.
        if spec.custom_data_reader is not None:
            return spec.custom_data_reader(data_origin=data_origin)
        return create_data_reader(data_origin)

    reader = make_reader()

    from elasticdl_tpu.common.save_utils import CheckpointSaver

    init_saver = None
    if job_type in ("evaluate", "predict"):
        init_saver = CheckpointSaver(args.checkpoint_dir_for_init)
        if init_saver.latest_step() is None:
            raise ValueError(
                f"--checkpoint_dir_for_init "
                f"{args.checkpoint_dir_for_init!r} contains no checkpoint"
            )

    def make_saver():
        # evaluate/predict: restore from the init checkpoint; train:
        # periodic checkpointing (optionally warm-started from
        # checkpoint_dir_for_init).
        if job_type in ("evaluate", "predict"):
            return init_saver
        if args.checkpoint_dir:
            return CheckpointSaver(
                args.checkpoint_dir, keep_max=args.keep_checkpoint_max
            )
        if args.checkpoint_dir_for_init:
            return CheckpointSaver(args.checkpoint_dir_for_init)
        return None

    # ONE model for the whole job: all worker threads share a ModelOwner
    # (trainer + state + update lock), so every task's gradients land in
    # the same params — the consistency the reference provided via its
    # PS/AllReduce machinery.  Per-worker private replicas would silently
    # train N diverging models on 1/N of the data each.
    from elasticdl_tpu.worker.sync import ModelOwner
    from elasticdl_tpu.worker.trainer import Trainer

    owner = ModelOwner(
        Trainer(
            model=spec.model,
            optimizer=spec.optimizer,
            loss_fn=spec.loss,
            use_bf16=args.use_bf16,
            param_sharding_fn=spec.param_sharding,
        ),
        checkpoint_saver=make_saver(),
        checkpoint_steps=args.checkpoint_steps,
    )
    # `job_setup` (client/main.py opened it) ends here; the first worker
    # thread's loop closes `worker_setup` (worker/worker.py: Worker.run)
    profiler.process_phase_timer().startup("worker_setup")

    # Tiered embedding store (elasticdl_tpu/store): a zoo module that
    # exports build_tiered_store() opts into the host-RAM bulk tier +
    # device hot-row cache.  The Local path never calls Master.start()
    # (the PR 10 gotcha), so the store's background threads — cold-miss
    # prefetcher, host-fold worker — must start HERE.
    tiered_store = None
    build_tiered_store = getattr(spec.module, "build_tiered_store", None)
    if build_tiered_store is not None and job_type == "train":
        if args.validation_data:
            raise ValueError(
                "tiered embedding store does not support mid-train "
                "evaluation yet: the eval path prepares admission plans "
                "it never applies, corrupting the cache map — drop "
                "--validation_data for tiered runs"
            )
        # Default registry so /metrics serves store_* next to the worker
        # families; the worker's PhaseTimer so cold-gather time lands in
        # worker_step_phase_seconds{phase="cold_gather"}.
        from elasticdl_tpu.common import metrics as metrics_lib
        from elasticdl_tpu.worker.worker import _phase_timer

        tiered_store = build_tiered_store(
            registry=metrics_lib.default_registry(),
            phase_timer=_phase_timer,
        )
        if args.num_workers != 1:
            # Multi-worker path: N feed producers cannot keep the strict
            # batch-order invariant eager planning needs, so planning is
            # DEFERRED to the trainer's step-serialized critical section
            # (ModelOwner's lock) — prepare+apply run in step order there
            # regardless of producer interleaving.  Costs the async
            # cold-gather overlap; see docs/PERF.md §4.  Row-range
            # sharding across workers is store/sharding.py.
            tiered_store.enable_deferred_prepare()
            logger.info(
                "Tiered store: deferred planning for %d workers",
                args.num_workers,
            )
        spec.feed = tiered_store.wrap_feed(spec.feed)
        spec.feed_bulk = tiered_store.wrap_feed(spec.feed_bulk)
        owner.trainer.tiered_store = tiered_store
        # Mesh-sharded seam (ISSUE 18b): declare the model-axis size so
        # plans carry per-chip sub-plans and per-chip byte accounting
        # matches the row-sharded cache tables XLA actually partitions.
        model_shards = int(dict(owner.trainer.mesh.shape).get("model", 1))
        if model_shards > 1:
            tiered_store.set_mesh_shards(model_shards)
        if owner.checkpoint_saver is not None:
            owner.checkpoint_saver.attach_tiered_store(tiered_store)
        tiered_store.start()
        logger.info(
            "Tiered embedding store active: cache_rows=%d host_dtype=%s "
            "cache_dtype=%s mesh_shards=%d",
            tiered_store.cache_rows, tiered_store.host.host_dtype,
            tiered_store.cache_dtype, tiered_store.mesh_shards,
        )

    # A restored task journal may already be terminal; the finish check
    # must run once proactively (it also injects the final-eval round for
    # the restored model) since no training report will ever drain the
    # queue.
    master.task_manager.maybe_finish_if_drained()

    workers = []
    threads = []
    for wid in range(args.num_workers):
        tb_dir = ""
        if getattr(args, "tensorboard_log_dir", ""):
            import os

            tb_dir = os.path.join(
                args.tensorboard_log_dir, f"worker-{wid}"
            )
        worker = Worker(
            worker_id=wid,
            master_client=client,
            data_reader=reader if wid == 0 else make_reader(),
            spec=spec,
            minibatch_size=args.minibatch_size,
            model_owner=owner,
            compact_wire=getattr(args, "compact_wire", False),
            wire_format=getattr(args, "wire_format", ""),
            tensorboard_dir=tb_dir,
            # one process, one profiler: only worker 0 may trace
            profile_dir=(
                getattr(args, "profile_dir", "") if wid == 0 else ""
            ),
        )
        workers.append(worker)
        thread = threading.Thread(target=worker.run, daemon=True)
        threads.append(thread)
        thread.start()
    ok = master.wait()
    for thread in threads:
        thread.join(timeout=60)
    if tiered_store is not None:
        # drain pending eviction write-backs, then stop both threads
        tiered_store.stop()
    if master.slo_evaluator is not None:
        master.slo_evaluator.stop()
    if master.metric_history is not None:
        master.metric_history.stop()
    if owner.checkpoint_saver is not None:
        # flush any in-flight async checkpoint writes
        owner.checkpoint_saver.wait_until_finished()
    metrics = master.evaluation_service.latest_metrics()
    if metrics:
        logger.info("Final metrics: %s", metrics)
    if job_type == "predict" and args.output:
        import numpy as np

        # per-task arrays keyed by task_id (rerun-safe); merge in task
        # order so the row order is deterministic across runs
        by_task = {}
        for w in workers:
            by_task.update(getattr(w, "predictions", {}) or {})
        if by_task:
            os_path = args.output
            if not os_path.endswith(".npy"):
                import os

                os.makedirs(os_path, exist_ok=True)
                os_path = f"{os_path}/predictions.npy"
            np.save(
                os_path,
                np.concatenate([by_task[t] for t in sorted(by_task)]),
            )
            logger.info("Wrote predictions to %s", os_path)
    elif args.output and owner.state is not None:
        from elasticdl_tpu.common.export import export_model

        export_model(
            owner.state, spec, args.output,
            saved_model=bool(getattr(args, "export_saved_model", False)),
            sample_features=owner.sample_features,
        )
        logger.info("Exported model to %s", args.output)
    logger.info("Job %s: %s", "succeeded" if ok else "failed",
                master.task_manager.snapshot())
    return 0 if ok else 1


def serve(args) -> int:
    """`elasticdl serve`: gRPC online inference for a zoo model, from a
    params.msgpack export (--export_dir) or a live checkpoint directory
    (--checkpoint_dir, with hot reload).  docs/SERVING.md."""
    from elasticdl_tpu.common import events

    if getattr(args, "event_log", ""):
        events.configure(args.event_log, role="serving")
    else:
        events.configure_from_env(role="serving")
    server = build_serving_server(args)
    port = server.start(args.port)
    logger.info(
        "serving %s on port %d (ctrl-c to stop)", args.model_def, port
    )
    try:
        server.wait()
    except KeyboardInterrupt:
        logger.info("shutting down")
    finally:
        server.stop()
    return 0


def build_serving_server(args):
    """Assemble (but do not start) the engine/batcher/reloader/server
    stack from parsed `elasticdl serve` args — split from serve() so
    tests and embedders drive the lifecycle themselves."""
    import json
    import os

    import numpy as np

    from elasticdl_tpu.common.model_handler import get_model_spec
    from elasticdl_tpu.common.virtual_mesh import enable_compile_cache
    from elasticdl_tpu.serving.batcher import DynamicBatcher
    from elasticdl_tpu.serving.engine import ServingEngine
    from elasticdl_tpu.serving.reloader import CheckpointReloader
    from elasticdl_tpu.serving.server import ServingServer

    if bool(args.export_dir) == bool(args.checkpoint_dir):
        raise ValueError(
            "elasticdl serve needs exactly one of --export_dir or "
            "--checkpoint_dir"
        )
    enable_compile_cache()
    spec = get_model_spec(
        args.model_zoo, args.model_def, model_params=args.model_params,
        arena_dtype=getattr(args, "arena_dtype", ""),
        store_cache_dtype=getattr(args, "store_cache_dtype", ""),
    )
    buckets = tuple(
        int(b) for b in str(args.batch_buckets).split(",") if b.strip()
    )
    reloader = None
    if args.export_dir:
        engine = ServingEngine.from_export(
            args.export_dir, spec, buckets=buckets
        )
    else:
        feature_spec = args.feature_spec
        if not feature_spec:
            raise ValueError(
                "--checkpoint_dir serving needs --feature_spec (inline "
                "JSON or a path to an export_meta.json)"
            )
        if os.path.exists(feature_spec):
            with open(feature_spec) as f:
                meta = json.load(f)
            feature_spec = meta.get("features", meta)
        else:
            feature_spec = json.loads(feature_spec)
        sample = {
            name: np.zeros(
                (1, *leaf["shape"]), np.dtype(leaf["dtype"])
            )
            for name, leaf in feature_spec.items()
        }
        from elasticdl_tpu.common.export import SINGLE_FEATURE_KEY

        if set(sample) == {SINGLE_FEATURE_KEY}:
            sample = sample[SINGLE_FEATURE_KEY]
        engine = ServingEngine.from_checkpoint(
            args.checkpoint_dir, spec, sample, buckets=buckets
        )
        reloader = CheckpointReloader(
            engine, args.checkpoint_dir,
            poll_interval_s=args.reload_poll_seconds,
        )
    batcher = DynamicBatcher(
        engine,
        max_latency_s=args.max_batch_latency_ms / 1000.0,
        max_queue_rows=args.max_queue_rows or None,
        reject_oversized=args.reject_oversized,
    )
    return ServingServer(
        engine, batcher, reloader,
        telemetry_port=getattr(args, "telemetry_port", 0),
    )


def _submit_master_pod(args, job_type: str) -> int:
    """Cluster mode: create the master pod through the Kubernetes API."""
    from elasticdl_tpu.common.k8s_client import (
        K8sClient,
        PodSpec,
        parse_volumes,
    )

    master_args = args_lib.build_arguments_from_parsed_result(
        args, filter_args={"func"}
    )
    command = (
        ["python", "-m", "elasticdl_tpu.master.main"]
        + master_args
        + ["--job_type", job_type]
    )
    client = K8sClient(namespace=args.namespace, job_name=args.job_name)
    master_name = f"{args.job_name}-master"
    client.create_pod(
        PodSpec(
            name=master_name,
            pod_type=PodType.MASTER,
            image=args.image_name,
            command=command,
            resources={},
            volumes=parse_volumes(getattr(args, "volume", "")),
        )
    )
    # Worker pods dial `{job_name}-master:{port}`; that DNS name only
    # exists if a Service fronts the master pod (selector = the labels
    # K8sClient.create_pod stamps on it).
    client.create_service(
        master_name,
        selector={
            "elasticdl-job": args.job_name,
            "elasticdl-type": PodType.MASTER,
        },
        port=args.port,
    )
    logger.info(
        "Submitted master pod %s-master to namespace %s",
        args.job_name, args.namespace,
    )
    return 0
