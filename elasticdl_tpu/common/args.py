"""The shared argparse surface.

Parity: reference python/common/args.py (SURVEY.md C21).  As in the
reference, one flag namespace is shared by client -> master -> worker and
argv is the config wire format: the client re-serializes parsed flags into
the master pod command, the master into worker commands
(`build_arguments_from_parsed_result`).
"""

from __future__ import annotations

import argparse
from itertools import chain


def pos_int(value):
    ivalue = int(value)
    if ivalue <= 0:
        raise argparse.ArgumentTypeError(f"{value} is not a positive integer")
    return ivalue


def non_neg_int(value):
    ivalue = int(value)
    if ivalue < 0:
        raise argparse.ArgumentTypeError(f"{value} is negative")
    return ivalue


def str2bool(value):
    if isinstance(value, bool):
        return value
    if value.lower() in ("yes", "true", "t", "y", "1"):
        return True
    if value.lower() in ("no", "false", "f", "n", "0"):
        return False
    raise argparse.ArgumentTypeError(f"Boolean value expected, got {value}")


def add_common_params(parser: argparse.ArgumentParser):
    parser.add_argument(
        "--job_name", default="elasticdl-job", help="Job / pod-name prefix"
    )
    parser.add_argument("--namespace", default="default")
    parser.add_argument(
        "--distribution_strategy",
        default="AllReduce",
        choices=["Local", "AllReduce", "ParameterServer"],
        help="ParameterServer is accepted for reference-CLI compatibility "
        "and maps onto the sharded-mesh path (no PS pods on TPU).",
    )
    parser.add_argument("--master_addr", default="", help="host:port of master")
    parser.add_argument("--port", type=pos_int, default=50001)
    parser.add_argument("--num_workers", type=pos_int, default=1)
    parser.add_argument("--image_name", default="")
    parser.add_argument("--worker_resource_request", default="cpu=1,memory=4096Mi")
    parser.add_argument("--worker_pod_priority", default="")
    parser.add_argument("--restart_policy", default="Never")
    parser.add_argument(
        "--volume", default="",
        help="Pod volume mounts, reference syntax: "
        "'host_path=/a,mount_path=/b' or 'claim_name=pvc,mount_path=/b'; "
        "multiple entries separated by ';'.  Mounted into the master pod "
        "and every worker pod (e.g. the --compilation_cache_dir volume).",
    )
    parser.add_argument(
        "--use_fake_k8s", type=str2bool, default=False,
        help="Use the in-memory fake cluster instead of the Kubernetes API "
        "(dev/test: exercises the full elastic control plane with no "
        "cluster)",
    )
    parser.add_argument(
        "--use_process_k8s", type=str2bool, default=False,
        help="Run worker pods as local OS subprocesses (single-machine "
        "e2e: the full master+worker entry points, rendezvous and "
        "jax.distributed bootstrap with no Kubernetes — the minikube-CI "
        "equivalent)",
    )
    parser.add_argument(
        "--workers_per_group", type=pos_int, default=1,
        help="Slice-granular failure handling (TPU: one preempted host "
        "stalls the whole slice's ICI collectives).  Workers are "
        "partitioned into groups of this size; when one member truly "
        "fails, the surviving members are proactively restarted "
        "(budget-free) instead of each waiting out its wedge-watchdog "
        "grace.  1 = per-worker granularity (the reference's model).",
    )
    parser.add_argument(
        "--preemption_notice_file", default="",
        help="Path polled for an upcoming-disruption notice (GKE TPU "
        "maintenance event / spot reclaim projected into the pod by a "
        "downward-API volume or node-watcher sidecar).  When the file "
        "appears the worker drains at the next task boundary and "
        "flushes a checkpoint — ahead of the SIGTERM.  'gce-metadata' "
        "polls the instance metadata server instead of a file.",
    )
    parser.add_argument(
        "--telemetry_port", type=non_neg_int, default=0,
        help="HTTP port for /metrics (Prometheus text), /healthz and "
        "/varz on this role (0 = ephemeral).  Workers always bind an "
        "ephemeral port: their argv is the master's re-serialized argv, "
        "so a fixed port would collide on shared hosts.",
    )
    parser.add_argument(
        "--event_log", default="",
        help="Append-only JSONL span-event log (task dispatch/claim/"
        "train/report, checkpoint save/restore, hot reload, elastic "
        "recovery).  The master exports the path to its workers via "
        "ELASTICDL_EVENT_LOG so one file correlates the whole cluster "
        "(docs/OBSERVABILITY.md).",
    )
    parser.add_argument(
        "--straggler_multiple", type=float, default=3.0,
        help="Flag a worker as a straggler when its mean task duration "
        "exceeds this multiple of the fleet-wide median (rolling window "
        "of recent tasks).  Flags surface in Master.snapshot()/varz, "
        "the master_straggler_workers gauge, straggler_detected span "
        "events and `elasticdl top`.  0 disables detection.",
    )
    parser.add_argument(
        "--straggler_min_tasks", type=pos_int, default=3,
        help="Minimum completed tasks per worker (and workers in the "
        "fleet) before straggler detection may flag anyone — avoids "
        "flagging on compile-warmup noise.",
    )
    # ---- policy engine (master/policy.py, docs/ROBUSTNESS.md) --------
    parser.add_argument(
        "--policy_interval", type=float, default=0.0,
        help="Seconds between policy-engine ticks (straggler eviction + "
        "autoscaling).  0 (the default) disables the control loop; the "
        "sensors keep running either way.",
    )
    parser.add_argument(
        "--min_workers", type=pos_int, default=1,
        help="Autoscaling floor: the policy engine never scales the "
        "fleet below this many workers.",
    )
    parser.add_argument(
        "--max_workers", type=int, default=0,
        help="Autoscaling ceiling.  0 means --num_workers (a fixed "
        "fleet unless raised).",
    )
    parser.add_argument(
        "--straggler_dwell_s", type=float, default=30.0,
        help="A straggler flag must persist this long before the policy "
        "engine evicts the worker — transient flags clear on their own.",
    )
    parser.add_argument(
        "--eviction_budget", type=pos_int, default=2,
        help="Lifetime cap on policy-engine evictions; a noisy detector "
        "must not be able to churn the fleet.",
    )
    parser.add_argument(
        "--eviction_cooldown_s", type=float, default=60.0,
        help="Minimum seconds between two policy-engine evictions.",
    )
    parser.add_argument(
        "--backlog_per_worker", type=float, default=4.0,
        help="Scale up when queued tasks per alive worker exceed this "
        "for --backlog_ticks consecutive policy ticks.",
    )
    parser.add_argument(
        "--backlog_ticks", type=pos_int, default=3,
        help="Consecutive over-threshold ticks before a backlog "
        "scale-up (hysteresis).",
    )
    parser.add_argument(
        "--data_wait_share", type=float, default=0.6,
        help="Scale down when the fleet-wide data_wait share of step "
        "time exceeds this for --data_wait_ticks consecutive ticks "
        "(input-starved workers add cost, not throughput).",
    )
    parser.add_argument(
        "--data_wait_ticks", type=pos_int, default=3,
        help="Consecutive over-threshold ticks before a data_wait "
        "scale-down (hysteresis).",
    )
    parser.add_argument(
        "--scale_step", type=pos_int, default=1,
        help="Workers added/removed per policy action, rounded to whole "
        "--workers_per_group slice groups.",
    )
    parser.add_argument(
        "--scale_hold_ticks", type=pos_int, default=2,
        help="Quiet ticks after any scale action before the next one — "
        "the fleet must re-converge before the signals mean anything.",
    )
    parser.add_argument(
        "--wedge_grace_s", type=float, default=20.0,
        help="Seconds a rank may lag a membership-epoch change before its "
        "watchdog assumes it is wedged in a collective with a dead peer "
        "and restarts the process",
    )
    parser.add_argument(
        "--coordinator_port", type=pos_int, default=51001,
        help="Port of the JAX coordination service bound by rank 0; the "
        "rendezvous serves rank 0's address + this port as the "
        "coordinator address",
    )
    parser.add_argument(
        "--rpc_retry_budget_s", type=float, default=0.0,
        help="Max elapsed seconds of backed-off retries any single "
        "control-plane RPC may consume before the worker gives up and "
        "exits with code 45 (charged relaunch).  0 defers to the "
        "ELASTICDL_RPC_MAX_ELAPSED_S env var, default 120 "
        "(docs/ROBUSTNESS.md).",
    )
    parser.add_argument(
        "--compilation_cache_dir", default="",
        help="Persistent XLA-executable cache directory.  A relaunched "
        "worker then LOADS the train-step executable instead of "
        "recompiling it — the AOT mitigation SURVEY.md hard part 1 "
        "calls for.  JAX_COMPILATION_CACHE_DIR, when set, wins over "
        "this flag; with neither, the cache is <checkout>/.jax_cache "
        "(common/virtual_mesh.compile_cache_dir).  Re-serialized into "
        "worker pod commands like every flag; on a real cluster pair it "
        "with --volume so the directory is a mount shared across pod "
        "relaunches (e.g. --volume 'claim_name=cache,mount_path=/cache' "
        "--compilation_cache_dir /cache).",
    )
    # ---- serving fleet (master/serving_fleet.py, docs/SERVING.md) ----
    parser.add_argument(
        "--serving_replicas", type=non_neg_int, default=0,
        help="Serving replicas the master places and supervises behind "
        "the job (docs/SERVING.md \"Fleet\").  0 (the default) disables "
        "the serving fleet entirely.",
    )
    parser.add_argument(
        "--serving_probe_interval", type=float, default=0.0,
        help="Seconds between fleet health-probe ticks (probe every "
        "replica's Health RPC, relaunch the dead, sequence rolling "
        "reloads).  0 disables the background loop; tests tick by hand.",
    )
    parser.add_argument(
        "--serving_probe_failures", type=pos_int, default=3,
        help="Consecutive failed health probes before a serving replica "
        "is relaunched (pod-phase death relaunches immediately).",
    )
    parser.add_argument(
        "--serving_step_skew_slo", type=non_neg_int, default=0,
        help="Max allowed cross-replica model_step spread.  A rolling "
        "reload that would exceed it is refused (exported as the "
        "serving_fleet_model_step_skew_steps gauge).  0 disables the "
        "bound.",
    )
    parser.add_argument(
        "--serving_port", type=pos_int, default=50061,
        help="gRPC port each serving replica listens on (the fleet "
        "manager probes {replica-service}:{this port}).",
    )
    # ---- serving autoscaler + backpressure (master/policy.py
    #      ServingPolicyEngine, docs/SERVING.md "Autoscaling &
    #      backpressure") ----
    parser.add_argument(
        "--max_serving_replicas", type=non_neg_int, default=0,
        help="Upper bound the serving policy engine may scale the fleet "
        "to.  0 (the default) disables serving autoscaling entirely; "
        "the fleet stays at --serving_replicas.",
    )
    parser.add_argument(
        "--min_serving_replicas", type=non_neg_int, default=0,
        help="Lower bound the serving policy engine may scale the fleet "
        "down to.  0 defaults to --serving_replicas (the placed size).",
    )
    parser.add_argument(
        "--serving_policy_interval", type=float, default=0.0,
        help="Seconds between serving policy engine ticks (SLO burn / "
        "shed-ratio / batch-fill signals -> at most one scale action).  "
        "0 disables the background loop; tests tick by hand.",
    )
    parser.add_argument(
        "--serving_burn_threshold", type=float, default=1.0,
        help="Fast-window SLO burn rate at or above which a serving "
        "scale-up streak accrues (1.0 = spending exactly the error "
        "budget).",
    )
    parser.add_argument(
        "--serving_shed_threshold", type=float, default=0.02,
        help="Windowed whole-fleet shed ratio at or above which a "
        "serving scale-up streak accrues (capacity exhaustion evidence "
        "even before an SLO burns).",
    )
    parser.add_argument(
        "--serving_fill_low", type=float, default=0.2,
        help="Mean healthy-replica batch fill at or below which a calm "
        "fleet accrues a scale-down streak (paying for replicas the "
        "batcher cannot fill).",
    )
    parser.add_argument(
        "--serving_up_ticks", type=pos_int, default=2,
        help="Consecutive overloaded ticks before the serving policy "
        "engine scales up (hysteresis entry gate).",
    )
    parser.add_argument(
        "--serving_down_ticks", type=pos_int, default=3,
        help="Consecutive calm, underfilled ticks before the serving "
        "policy engine scales down.",
    )
    parser.add_argument(
        "--serving_scale_step", type=pos_int, default=1,
        help="Replicas added or retired per serving scale action.",
    )
    parser.add_argument(
        "--serving_scale_hold_ticks", type=non_neg_int, default=2,
        help="Quiet ticks after any serving scale action before the "
        "next one — the fleet must re-converge (probe, warm, drain) "
        "before the signals mean anything again.",
    )
    parser.add_argument(
        "--serving_shed_window_s", type=float, default=30.0,
        help="Metric-history window the serving policy engine computes "
        "its shed ratio over (a past spike ages out of the evidence).",
    )
    parser.add_argument(
        "--backpressure_threshold", type=float, default=0.25,
        help="serving_pressure (SLO burn rate x fleet shed ratio) above "
        "which the online pipeline slows its stream poll/arm cadence — "
        "train yields to serve until the pressure clears.",
    )
    parser.add_argument(
        "--backpressure_stride", type=pos_int, default=4,
        help="While backpressured, the online pipeline polls/arms only "
        "every this-many-th tick (queued tasks still drain every "
        "tick).",
    )
    # ---- metric history + SLOs (common/history.py, common/slo.py,
    #      docs/OBSERVABILITY.md "Metric history & SLOs") ----
    parser.add_argument(
        "--history_interval", type=float, default=0.0,
        help="Seconds between metric-history samples (ring-buffer "
        "recorder over every /metrics registry; the evidence the SLO "
        "evaluator and `elasticdl slo` read).  0 disables the sampling "
        "thread; tests tick by hand.",
    )
    parser.add_argument(
        "--history_capacity", type=pos_int, default=512,
        help="Samples retained per metric series in the history ring "
        "buffer (oldest evicted first).  Must cover the slowest SLO "
        "window: capacity * --history_interval >= slow_window_s.",
    )
    parser.add_argument(
        "--slo_interval", type=float, default=0.0,
        help="Seconds between SLO evaluator ticks (burn-rate math over "
        "the metric history; emits slo_breach/slo_recovered span "
        "events).  0 disables the thread; tests tick by hand.",
    )
    parser.add_argument(
        "--slo_staleness_p99_s", type=float, default=60.0,
        help="Objective of the staleness_p99 SLO: 99%% of predict "
        "responses must be served from a checkpoint no older than this "
        "many seconds behind the latest produced one.",
    )
    # ---- request tracing + incident flight recorder (common/flight.py,
    #      docs/OBSERVABILITY.md "Request tracing & incident bundles") --
    parser.add_argument(
        "--trace_sample_rate", type=float, default=1.0,
        help="Fraction of routed Predict requests whose predict_span "
        "is recorded end to end (deterministic every-k'th sampling, "
        "k = round(1/rate); 0 disables).  Error, shed, and failover "
        "outcomes always capture regardless of the rate.",
    )
    parser.add_argument(
        "--incident_dir", default="",
        help="Directory the incident flight recorder writes bundles "
        "into on an slo_breach, policy eviction, or terminal reload "
        "refusal (one JSON dir per incident: recent request spans, "
        "decisions, metric-history windows, Master.snapshot(), fault "
        "stats).  Empty disables capture; the forensic rings still "
        "fill.  Render with `elasticdl incident`.",
    )
    parser.add_argument(
        "--incident_ring", type=pos_int, default=256,
        help="Recent predict_span and decision events retained in the "
        "flight recorder's in-memory rings (each; oldest evicted "
        "first).",
    )
    parser.add_argument(
        "--incident_max_bundles", type=pos_int, default=8,
        help="Bundles kept under --incident_dir before the oldest is "
        "rotated out — soak runs cannot fill the disk.",
    )


def add_model_params(parser: argparse.ArgumentParser):
    parser.add_argument(
        "--model_zoo", required=False, default="model_zoo",
        help="Directory containing model definitions",
    )
    parser.add_argument(
        "--model_def", required=False, default="",
        help="module.function returning the model, e.g. "
        "mnist.mnist_functional_api.custom_model",
    )
    parser.add_argument("--model_params", default="", help="free-form kwargs")
    parser.add_argument(
        "--arena_dtype", default="", choices=["", "float32", "int8"],
        help="Embedding arena storage dtype: int8 stores rows as "
        "quantized codes with per-row fp32 scales (docs/PERF.md "
        "'Quantized arena'); empty defers to the model's default "
        "(float32).  Forwarded into model_params for zoos whose "
        "custom_model accepts arena_dtype.",
    )
    parser.add_argument(
        "--store_cache_dtype", default="",
        choices=["", "float32", "int8"],
        help="Tiered-store device hot-row cache storage dtype: int8 "
        "stores cache rows as quantized codes with per-row fp32 scales "
        "(docs/PERF.md §4).  Empty defers to the model's default "
        "(float32).  Forwarded into model_params as cache_dtype for "
        "zoos whose custom_model accepts it; zoos without tiered "
        "support ignore it.",
    )
    parser.add_argument("--dataset_fn", default="feed")
    parser.add_argument("--loss", default="loss")
    parser.add_argument("--optimizer", default="optimizer")
    parser.add_argument("--eval_metrics_fn", default="eval_metrics_fn")
    parser.add_argument("--custom_data_reader", default="custom_data_reader")
    parser.add_argument("--prediction_outputs_processor", default="")
    parser.add_argument("--callbacks", default="callbacks")


def add_train_params(parser: argparse.ArgumentParser):
    parser.add_argument("--minibatch_size", type=pos_int, default=64)
    parser.add_argument("--num_epochs", type=pos_int, default=1)
    parser.add_argument("--training_data", default="")
    parser.add_argument("--validation_data", default="")
    parser.add_argument("--prediction_data", default="")
    parser.add_argument("--evaluation_steps", type=non_neg_int, default=0)
    parser.add_argument("--evaluation_start_delay_secs", type=non_neg_int, default=0)
    parser.add_argument("--evaluation_throttle_secs", type=non_neg_int, default=0)
    parser.add_argument("--checkpoint_steps", type=non_neg_int, default=0)
    parser.add_argument("--checkpoint_dir", default="")
    parser.add_argument("--keep_checkpoint_max", type=non_neg_int, default=3)
    parser.add_argument("--output", default="", help="final model export dir")
    parser.add_argument(
        "--export_saved_model", type=str2bool, default=False, nargs="?",
        const=True,
        help="Also export a TF SavedModel under <output>/saved_model "
        "(forward pass staged via jax2tf, polymorphic batch dim) — the "
        "serving handoff the reference's SavedModel export provided.  "
        "Mesh-manual models (ring attention / GPipe) do not convert; the "
        "msgpack export is always written regardless.",
    )
    parser.add_argument(
        "--checkpoint_dir_for_init", default="",
        help="checkpoint to warm-start from",
    )
    parser.add_argument(
        "--profile_dir", default="",
        help="capture a JAX profiler trace (Perfetto/XPlane, readable in "
        "TensorBoard) of the first training task into this directory",
    )
    parser.add_argument(
        "--tensorboard_log_dir", default="",
        help="write train-loss/steps-per-sec/eval scalars (workers) and "
        "aggregated eval metrics (master) as TensorBoard event files "
        "under this directory",
    )
    parser.add_argument(
        "--relaunch_on_worker_failure", type=non_neg_int, default=3,
        help="max relaunches per failed worker pod",
    )
    parser.add_argument("--use_bf16", type=str2bool, default=True,
                        help="compute in bfloat16 on the MXU where safe")
    parser.add_argument(
        "--compact_wire", type=str2bool, default=False,
        help="ship batches in the zoo's compact device wire format "
        "(feed_bulk_compact, elasticdl_tpu.data.wire) when the zoo "
        "provides one — fewer host->device bytes per example on "
        "bandwidth-limited links",
    )
    parser.add_argument(
        "--wire_format", default="", choices=["", "plain", "compact", "dedup"],
        help="host->device wire format: plain (feed_bulk), compact "
        "(feed_bulk_compact, same as --compact_wire=true), or dedup "
        "(feed_bulk_dedup — host-hashed ids dedup'd per field into "
        "frequency-ranked uniques + a 1-byte inverse plane; fewest "
        "bytes/example on skewed id streams).  Empty defers to "
        "--compact_wire.  SPMD slice-local sharding ignores 'dedup' "
        "(per-rank unique counts diverge -> collective shape mismatch)",
    )
    parser.add_argument("--data_reader_params", default="")
    parser.add_argument("--records_per_task", type=pos_int, default=4096)
    parser.add_argument(
        "--task_lease_timeout_s", type=pos_int, default=900,
        help="re-queue a leased task if not reported within this window",
    )


def add_evaluate_params(parser):
    parser.add_argument("--minibatch_size", type=pos_int, default=64)
    parser.add_argument("--validation_data", default="")
    parser.add_argument("--checkpoint_dir_for_init", default="")
    parser.add_argument("--records_per_task", type=pos_int, default=4096)
    parser.add_argument("--data_reader_params", default="")


def add_predict_params(parser):
    parser.add_argument("--minibatch_size", type=pos_int, default=64)
    parser.add_argument("--prediction_data", default="")
    parser.add_argument("--checkpoint_dir_for_init", default="")
    parser.add_argument("--records_per_task", type=pos_int, default=4096)
    parser.add_argument("--data_reader_params", default="")


def add_serve_params(parser):
    """`elasticdl serve`: online inference from an export or a live
    checkpoint directory (docs/SERVING.md)."""
    parser.add_argument(
        "--export_dir", default="",
        help="directory with params.msgpack + export_meta.json "
        "(from --output of a training job)",
    )
    parser.add_argument(
        "--checkpoint_dir", default="",
        help="serve the newest verified checkpoint and hot-reload as "
        "the trainer writes new steps (alternative to --export_dir)",
    )
    parser.add_argument("--port", type=non_neg_int, default=50061)
    parser.add_argument(
        "--batch_buckets", default="1,4,16,64",
        help="comma-separated batch sizes to precompile; requests are "
        "padded to the nearest bucket",
    )
    parser.add_argument(
        "--max_batch_latency_ms", type=float, default=10.0,
        help="max time a queued request waits for batch-mates",
    )
    parser.add_argument(
        "--max_queue_rows", type=non_neg_int, default=0,
        help="admission-control bound on queued rows "
        "(0 = 4x the largest bucket)",
    )
    parser.add_argument(
        "--reject_oversized", type=str2bool, default=False,
        help="reject requests larger than the largest bucket instead "
        "of splitting them",
    )
    parser.add_argument(
        "--reload_poll_seconds", type=float, default=10.0,
        help="checkpoint-directory poll interval for hot reload",
    )
    parser.add_argument(
        "--telemetry_port", type=non_neg_int, default=0,
        help="HTTP port for /metrics, /healthz and /varz on the serving "
        "replica (0 = ephemeral)",
    )
    parser.add_argument(
        "--event_log", default="",
        help="append-only JSONL span-event log (hot-reload events join "
        "the cluster's trace stream)",
    )
    parser.add_argument(
        "--feature_spec", default="",
        help="serving signature for --checkpoint_dir mode when no "
        "export_meta.json is available: inline JSON "
        '{"name": {"shape": [..], "dtype": ".."}} or a path to an '
        "export_meta.json",
    )


def add_trace_params(parser: argparse.ArgumentParser):
    """`elasticdl trace`: offline event-log analysis (client/trace.py)."""
    parser.add_argument(
        "event_log",
        help="span-event JSONL written by --event_log (a rolled "
        "<path>.1 generation, if present, is read automatically)",
    )
    parser.add_argument(
        "--chrome", default="",
        help="write Chrome trace-event JSON here; open in "
        "https://ui.perfetto.dev or chrome://tracing",
    )
    parser.add_argument(
        "--summary", action="store_true",
        help="print per-worker task-latency quantiles, slowest tasks "
        "and the aggregate step-phase breakdown (default when --chrome "
        "is not given)",
    )
    parser.add_argument(
        "--slowest", type=non_neg_int, default=5,
        help="how many slowest tasks the summary lists",
    )


def add_lineage_params(parser: argparse.ArgumentParser):
    """`elasticdl lineage`: per-window freshness waterfalls from an
    event log (client/lineage.py)."""
    parser.add_argument(
        "event_log",
        help="span-event JSONL written by --event_log (a rolled "
        "<path>.1 generation, if present, is read automatically)",
    )
    parser.add_argument(
        "--slowest", type=non_neg_int, default=3,
        help="how many slowest windows get a full waterfall",
    )
    parser.add_argument(
        "--window", type=int, default=None,
        help="render the waterfall for this one window id only",
    )


def add_incident_params(parser: argparse.ArgumentParser):
    """`elasticdl incident`: postmortem reports from flight-recorder
    bundles (client/incident.py)."""
    parser.add_argument(
        "incident_dir",
        help="directory the master's --incident_dir flight recorder "
        "wrote bundles into",
    )
    parser.add_argument(
        "--bundle", default="",
        help="bundle name (or unambiguous prefix) to render a full "
        "postmortem report for; omitted = list all bundles",
    )
    parser.add_argument(
        "--spans", type=non_neg_int, default=10,
        help="how many of the slowest request spans the report lists",
    )


def parse_master_args(argv=None):
    parser = argparse.ArgumentParser(description="elasticdl-tpu master")
    add_common_params(parser)
    add_model_params(parser)
    add_train_params(parser)
    parser.add_argument("--job_type", default="train",
                        choices=["train", "evaluate", "predict"])
    args, _ = parser.parse_known_args(argv)
    return args


def parse_worker_args(argv=None):
    parser = argparse.ArgumentParser(description="elasticdl-tpu worker")
    add_common_params(parser)
    add_model_params(parser)
    add_train_params(parser)
    parser.add_argument("--worker_id", type=int, default=0)
    parser.add_argument("--job_type", default="train")
    args, _ = parser.parse_known_args(argv)
    return args


def build_arguments_from_parsed_result(args, filter_args=None) -> list:
    """Re-serialize a parsed namespace back into argv (the config wire
    format between client -> master -> worker pods, as in the reference)."""
    items = vars(args).items()
    if filter_args:
        items = [(k, v) for k, v in items if k not in filter_args]
    arguments = []
    for key, value in items:
        if value is None or value == "":
            continue
        arguments.append("--" + key)
        arguments.append(str(value))
    return arguments


def wrap_python_args_with_string(args: list) -> list:
    """Quote values so argv survives a shell boundary in a pod command."""
    return list(chain.from_iterable(
        (a,) if a.startswith("--") else (f"'{a}'",) for a in args
    ))
