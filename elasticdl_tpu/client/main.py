"""The `elasticdl` CLI.

Parity: reference elasticdl_client/main.py (SURVEY.md C18):

    elasticdl train    --model_zoo ... --model_def pkg.fn --training_data ...
    elasticdl evaluate --model_zoo ... --validation_data ...
    elasticdl predict  --model_zoo ... --prediction_data ...
    elasticdl zoo init|build|push

Flag surface mirrors the reference (SURVEY.md C21) so zoo jobs launch
unchanged; TPU-specific flags (--use_bf16, mesh axes) extend it.
"""

from __future__ import annotations

import argparse
import sys
import time

from elasticdl_tpu.common import args as args_lib
from elasticdl_tpu.common.constants import DistributionStrategy


def _build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="elasticdl",
        description="elasticdl-tpu: elastic distributed training on TPU",
    )
    subparsers = parser.add_subparsers(dest="command")

    train_parser = subparsers.add_parser("train", help="submit a training job")
    args_lib.add_common_params(train_parser)
    args_lib.add_model_params(train_parser)
    args_lib.add_train_params(train_parser)
    train_parser.set_defaults(func="train")

    eval_parser = subparsers.add_parser("evaluate", help="run evaluation")
    args_lib.add_common_params(eval_parser)
    args_lib.add_model_params(eval_parser)
    args_lib.add_train_params(eval_parser)
    eval_parser.set_defaults(func="evaluate")

    predict_parser = subparsers.add_parser("predict", help="run prediction")
    args_lib.add_common_params(predict_parser)
    args_lib.add_model_params(predict_parser)
    args_lib.add_train_params(predict_parser)
    predict_parser.set_defaults(func="predict")

    serve_parser = subparsers.add_parser(
        "serve", help="serve an exported model or live checkpoint dir"
    )
    args_lib.add_model_params(serve_parser)
    args_lib.add_serve_params(serve_parser)
    serve_parser.set_defaults(func="serve")

    top_parser = subparsers.add_parser(
        "top", help="live cluster table from a master's /varz endpoint"
    )
    top_parser.add_argument(
        "master_varz",
        help="master telemetry address: host:port or http URL "
        "(--telemetry_port of the master)",
    )
    top_parser.add_argument(
        "--serving_addr", default="",
        help="optionally also scrape a serving replica's telemetry "
        "address for a serving summary row",
    )
    top_parser.add_argument(
        "--watch", action="store_true",
        help="refresh continuously instead of printing one frame",
    )
    top_parser.add_argument(
        "--interval_s", type=float, default=2.0,
        help="refresh interval with --watch",
    )
    top_parser.set_defaults(func="top")

    slo_parser = subparsers.add_parser(
        "slo", help="SLO report (state, burn rates, window evidence) "
        "from a master's /varz endpoint"
    )
    slo_parser.add_argument(
        "master_varz",
        help="master telemetry address: host:port or http URL "
        "(--telemetry_port of the master)",
    )
    slo_parser.add_argument(
        "--json", action="store_true",
        help="dump the raw SLO snapshot as JSON instead of the table",
    )
    slo_parser.set_defaults(func="slo")

    programs_parser = subparsers.add_parser(
        "programs",
        help="XLA program observatory (compiles, retraces, cost ledger, "
        "live MFU) from any role's /varz endpoint",
    )
    programs_parser.add_argument(
        "varz_addr",
        help="telemetry address of any role: host:port or http URL "
        "(--telemetry_port of a master, worker, or serving replica)",
    )
    programs_parser.add_argument(
        "--json", action="store_true",
        help="dump the raw program ledger as JSON instead of the table",
    )
    programs_parser.set_defaults(func="programs")

    trace_parser = subparsers.add_parser(
        "trace",
        help="convert an --event_log JSONL to Chrome trace JSON "
        "(Perfetto / chrome://tracing) or print a latency summary",
    )
    args_lib.add_trace_params(trace_parser)
    trace_parser.set_defaults(func="trace")

    lineage_parser = subparsers.add_parser(
        "lineage",
        help="per-window ingest->first-serve freshness waterfalls from "
        "an --event_log JSONL (the train-path twin of `trace`)",
    )
    args_lib.add_lineage_params(lineage_parser)
    lineage_parser.set_defaults(func="lineage")

    incident_parser = subparsers.add_parser(
        "incident",
        help="list incident flight-recorder bundles (--incident_dir of "
        "the master) or render one into a postmortem report",
    )
    args_lib.add_incident_params(incident_parser)
    incident_parser.set_defaults(func="incident")

    zoo_parser = subparsers.add_parser("zoo", help="model zoo image tools")
    zoo_sub = zoo_parser.add_subparsers(dest="zoo_command")
    zoo_init = zoo_sub.add_parser("init", help="scaffold a model zoo dir")
    zoo_init.add_argument("--model_zoo", default="model_zoo")
    zoo_init.add_argument("--base_image", default="python:3.12")
    zoo_init.set_defaults(func="zoo_init")
    zoo_build = zoo_sub.add_parser("build", help="build the job image")
    zoo_build.add_argument("--model_zoo", default="model_zoo")
    zoo_build.add_argument("--image", required=True)
    zoo_build.set_defaults(func="zoo_build")
    zoo_push = zoo_sub.add_parser("push", help="push the job image")
    zoo_push.add_argument("image")
    zoo_push.set_defaults(func="zoo_push")
    return parser


def main(argv=None) -> int:
    entered = time.perf_counter()
    parser = _build_parser()
    # Strict parsing: a typo'd flag must error, not silently fall back to
    # a default (the master/worker argv wire format stays tolerant via
    # parse_known_args in common/args.py; the human-facing CLI does not).
    args = parser.parse_args(argv)
    if not getattr(args, "func", None):
        parser.print_help()
        return 2

    if args.func in ("train", "evaluate", "predict") and (
        args.distribution_strategy == DistributionStrategy.LOCAL
    ):
        # this process is the job: its start-up record begins here
        # (docs/OBSERVABILITY.md "Start-up catalogue")
        from elasticdl_tpu.common import profiler

        profiler.process_phase_timer().begin_startup(entered)

    from elasticdl_tpu.client import api, image_builder

    if args.func in ("train", "evaluate", "predict", "serve"):
        try:
            return getattr(api, args.func)(args)
        except (ImportError, ModuleNotFoundError) as exc:
            print(
                f"elasticdl {args.func}: cannot load --model_def "
                f"{args.model_def!r} from --model_zoo {args.model_zoo!r}: "
                f"{exc}",
                file=sys.stderr,
            )
            return 1
        except ValueError as exc:
            print(f"elasticdl {args.func}: {exc}", file=sys.stderr)
            return 1
    if args.func == "top":
        from elasticdl_tpu.client.top import top

        return top(args)
    if args.func == "slo":
        from elasticdl_tpu.client.slo import slo

        return slo(args)
    if args.func == "programs":
        from elasticdl_tpu.client.programs import programs

        return programs(args)
    if args.func == "trace":
        from elasticdl_tpu.client.trace import trace

        return trace(args)
    if args.func == "lineage":
        from elasticdl_tpu.client.lineage import lineage

        return lineage(args)
    if args.func == "incident":
        from elasticdl_tpu.client.incident import incident

        return incident(args)
    if args.func == "zoo_init":
        return image_builder.init_zoo(args.model_zoo, args.base_image)
    if args.func == "zoo_build":
        return image_builder.build_image(args.model_zoo, args.image)
    if args.func == "zoo_push":
        return image_builder.push_image(args.image)
    parser.print_help()
    return 2


if __name__ == "__main__":
    sys.exit(main())
