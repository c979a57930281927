"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py

drives the normal path once on the accelerator, at the width of the
configurations the repo benchmarks, through the entry points a user calls:

  preflight  versions, device, compile-cache rule, native scanner, peaks row
  train      `elasticdl train` (Local) of DeepFM: master task dispatch ->
             Worker/Trainer -> Orbax checkpoints -> export
  serve      `elasticdl serve` on that export, Predict over gRPC
  kernel     `elasticdl train` of BERT-base with the flash kernel compiled
             by Mosaic, then the kernel alone against the O(L^2) reference
             at the corners `flash_shapes_ok` admits
  cache      per-program compile seconds, cache entries before and after

ONE process owns every chip of the host and nothing is spawned.  The first
failure raises: no phase's exception is caught and reported as a field.
There is no CPU continuation — without an accelerator the script prints its
preflight and exits non-zero.  On success the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

With more than one device it also checks that the Local mesh spans all of
them (state on every device, batch split over `data`) and that the losses
agree with the same seed and global batch trained on one device.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ZOO = os.path.join(ROOT, "model_zoo")

DEEPFM_DEF = "deepfm.deepfm_functional_api.custom_model"
DEEPFM_PARAMS = "vocab_capacity=1048576;embed_dim=16;bf16=True"
DEEPFM_BATCH = 65536
DEEPFM_STEPS = 8
SERVE_BUCKETS = (8, 64)

BERT_DEF = "bert.bert_finetune.custom_model"
BERT_PARAMS = (
    "hidden=768;num_layers=12;heads=12;mlp_dim=3072;max_len=512;bf16=True"
)
BERT_BATCH = 64
BERT_SEQ = 512
BERT_STEPS = 3

# (batch, length, heads, head_dim, dtype): BERT-base's shape, the longest
# lengths the K/V residency guard admits at H*D=768, the multichip dry
# run's sub-128 shape (an f32 model), and the widest head.
KERNEL_CORNERS = (
    (2, 512, 12, 64, "bfloat16"),
    (2, 1024, 12, 64, "bfloat16"),
    (1, 1664, 12, 64, "bfloat16"),
    (2, 16, 4, 16, "float32"),
    (2, 512, 6, 128, "bfloat16"),
)
# Two bf16 computations of the same function (the kernel against an f32
# highest-precision reference, the served forward against the trainer's);
# a dropped tile, a wrong mask or a leaked padding row is O(0.1).
BF16_TOL = 2e-2
# One device against N devices, same seed and global batch: the forward is
# per-example identical, only the f32 reduction order of the batch mean
# and of the gradient all-reduce differs.
MULTI_DEVICE_LOSS_RTOL = 1e-2


def say(phase: str, message: str) -> None:
    print(f"[chip_smoke] {phase}: {message}", flush=True)


@contextlib.contextmanager
def capture_instances(cls):
    """Record every `cls` built while the CLI runs: `client.main` returns
    only an exit code, and the smoke must look at the live objects (the
    train state's placement, the per-step losses) behind it."""
    made = []
    init = cls.__init__

    def recording_init(self, *args, **kwargs):
        init(self, *args, **kwargs)
        made.append(self)

    cls.__init__ = recording_init
    try:
        yield made
    finally:
        cls.__init__ = init


def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def compile_seconds(program: str) -> float:
    """Seconds the program registry has charged to `program` so far:
    trace + XLA compile, or the load from the persistent cache."""
    from elasticdl_tpu.common import programs

    record = programs.default_program_registry().ledger().get(program)
    return record["compile_seconds_total"] if record else 0.0


# ---- preflight -----------------------------------------------------------


def preflight():
    """Print what this process runs on; return (devices, cache_dir).
    Exits non-zero unless the platform is `tpu`."""
    import jax
    import jaxlib

    from elasticdl_tpu.common import programs
    from elasticdl_tpu.common.virtual_mesh import enable_compile_cache
    from elasticdl_tpu.data import native_io

    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "not installed"
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    say(
        "preflight",
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu_version} platform={platform} "
        f"device_kind={devices[0].device_kind!r} count={len(devices)} "
        f"compile_cache={cache_dir} "
        f"(from JAX_COMPILATION_CACHE_DIR: "
        f"{bool(os.environ.get('JAX_COMPILATION_CACHE_DIR'))}, "
        f"entries: {cache_entries(cache_dir)}) "
        f"native_scanner={native_io.available()}",
    )
    if platform != "tpu":
        raise SystemExit(
            f"chip_smoke: platform is {platform!r}, not 'tpu' — this "
            "script has no CPU continuation"
        )
    if not native_io.available():
        raise SystemExit(
            "chip_smoke: the native record scanner did not load "
            "(scripts/build_native.sh shows why)"
        )
    return devices, cache_dir


# ---- train ---------------------------------------------------------------


def write_criteo_tfrecord(path: str, n_records: int, record_bytes: int):
    """Seeded Criteo-format records (13 f32 dense | 26 i32 ids | 1 label
    byte), zipf ids over a 4M raw space like real CTR traffic."""
    import numpy as np

    from elasticdl_tpu.data.record_io import write_tfrecords_bulk

    rng = np.random.RandomState(0)
    arr = np.empty((n_records, record_bytes), np.uint8)
    arr[:, :52] = rng.rand(n_records, 13).astype(np.float32).view(np.uint8)
    arr[:, 52:156] = (
        (rng.zipf(1.5, size=(n_records, 26)) % (1 << 22))
        .astype(np.int32).view(np.uint8)
    )
    arr[:, 156] = rng.randint(0, 2, n_records)
    write_tfrecords_bulk(
        path, arr.reshape(-1), np.full(n_records, record_bytes, np.int64)
    )


def write_bert_tfrecord(path: str, n_records: int, seq_len: int):
    """Seeded `seq_len` int32 token ids | 1 label byte records."""
    import numpy as np

    from elasticdl_tpu.data.record_io import write_tfrecords_bulk

    rng = np.random.RandomState(0)
    record_bytes = seq_len * 4 + 1
    arr = np.empty((n_records, record_bytes), np.uint8)
    arr[:, :-1] = (
        rng.randint(0, 8192, size=(n_records, seq_len))
        .astype(np.int32).view(np.uint8)
    )
    arr[:, -1] = rng.randint(0, 2, n_records)
    write_tfrecords_bulk(
        path, arr.reshape(-1), np.full(n_records, record_bytes, np.int64)
    )


def cli_train(model_def, model_params, data, batch, steps, tasks, extra=()):
    """`elasticdl train --distribution_strategy Local` in this process,
    the data cut into `tasks` tasks; returns the Worker the CLI built
    (one worker: the default), its per-step losses and the seconds the
    train step took to compile."""
    from elasticdl_tpu.client.main import main as cli_main
    from elasticdl_tpu.worker.worker import Worker

    argv = [
        "train",
        "--model_zoo", ZOO,
        "--model_def", model_def,
        "--model_params", model_params,
        "--use_bf16", "true",
        "--distribution_strategy", "Local",
        "--training_data", data,
        "--minibatch_size", str(batch),
        "--records_per_task", str(steps * batch // tasks),
        "--num_epochs", "1",
        *extra,
    ]
    compiled_before = compile_seconds("worker_train_step")
    with capture_instances(Worker) as workers:
        rc = cli_main(argv)
    compile_s = compile_seconds("worker_train_step") - compiled_before
    assert rc == 0, f"elasticdl train exited {rc}"
    assert len(workers) == 1, f"expected one Worker, CLI built {len(workers)}"
    worker = workers[0]
    losses = [float(loss) for loss in worker.losses]
    assert len(losses) == steps, f"{len(losses)} losses for {steps} steps"
    assert all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}"
    assert int(worker.state.step) == steps
    return worker, losses, compile_s


def check_state_placement(worker, devices) -> None:
    """Every array of the train state lives on the accelerator, on ALL
    devices of the host (the Local mesh is data=N; state replicates)."""
    import jax

    expected = set(devices)
    for path, leaf in jax.tree_util.tree_leaves_with_path(worker.state):
        assert isinstance(leaf, jax.Array), f"{path}: {type(leaf)}"
        assert set(leaf.sharding.device_set) == expected, (
            f"{jax.tree_util.keystr(path)} on {leaf.sharding.device_set}, "
            f"expected all of {expected}"
        )
    mesh_shape = dict(worker.trainer.mesh.shape)
    assert mesh_shape["data"] == len(devices), mesh_shape
    for device in devices:
        stats = device.memory_stats()
        assert stats["peak_bytes_in_use"] > 0, f"{device} never held data"


def check_batch_split(worker, batch, devices) -> None:
    """The trainer's own placement splits the batch's rows evenly over
    every device."""
    import jax

    from elasticdl_tpu.parallel import mesh as mesh_lib

    rows = len(batch["labels"])
    placed = mesh_lib.shard_batch(batch, worker.trainer.mesh)
    for leaf in jax.tree.leaves(placed):
        shards = leaf.addressable_shards
        assert {s.device for s in shards} == set(devices)
        assert all(
            s.data.shape[0] == rows // len(devices) for s in shards
        ), [s.data.shape for s in shards]


def first_batches(zoo_module, data, batch, steps):
    """The first `steps` batches of `data` through the worker's own batch
    cutter and the zoo's plain feed — what the CLI run trained on."""
    from elasticdl_tpu.data.reader.tfrecord_reader import TFRecordDataReader
    from elasticdl_tpu.proto import elasticdl_pb2 as pb
    from elasticdl_tpu.worker.task_data_service import TaskDataService

    service = TaskDataService(None, TFRecordDataReader(data), worker_id=0)
    task = pb.Task(
        task_id=0, type=pb.TRAINING,
        shard=pb.Shard(name=data, start=0, end=steps * batch),
    )
    return [
        b for b, _ in service.batches_for_task(
            task, batch, zoo_module.feed, feed_bulk=zoo_module.feed_bulk
        )
    ]


def one_device_losses(model_def, model_params, batches):
    """The same seed and global batches on a one-device mesh."""
    import jax

    from elasticdl_tpu.common.model_handler import get_model_spec
    from elasticdl_tpu.parallel import mesh as mesh_lib
    from elasticdl_tpu.worker.trainer import Trainer

    spec = get_model_spec(ZOO, model_def, model_params=model_params)
    trainer = Trainer(
        model=spec.model, optimizer=spec.optimizer, loss_fn=spec.loss,
        mesh=mesh_lib.create_mesh(jax.devices()[:1]), use_bf16=True,
        param_sharding_fn=spec.param_sharding,
    )
    state = trainer.init_state(jax.random.PRNGKey(0), batches[0]["features"])
    losses = []
    for batch in batches:
        state, loss = trainer.train_on_batch(state, batch)
        losses.append(float(loss))
    return losses


def train_deepfm(work, devices, *, model_params=DEEPFM_PARAMS,
                 batch=DEEPFM_BATCH, steps=DEEPFM_STEPS):
    import numpy as np

    from elasticdl_tpu.common.model_handler import load_module
    from elasticdl_tpu.common.save_utils import CheckpointSaver

    zoo_module, _ = load_module(ZOO, DEEPFM_DEF)
    data = os.path.join(work, "criteo.tfrecord")
    write_criteo_tfrecord(data, steps * batch, zoo_module.RECORD_BYTES)
    checkpoints = os.path.join(work, "checkpoints")
    export = os.path.join(work, "export")
    t0 = time.perf_counter()
    # ONE task: the master shuffles shards, and the one-device reference
    # below must train on the same batches in the same order.  (BERT's
    # run is where several tasks are dispatched.)
    worker, losses, compile_s = cli_train(
        DEEPFM_DEF, model_params, data, batch, steps, tasks=1,
        extra=(
            "--checkpoint_dir", checkpoints,
            "--checkpoint_steps", str(steps // 2),
            "--output", export,
        ),
    )
    seconds = time.perf_counter() - t0

    saver = CheckpointSaver(checkpoints)
    try:
        assert saver.latest_step() == steps, saver.all_steps()
        assert saver.verify_step(steps), "final checkpoint fails its manifest"
        saved_steps = sorted(saver.all_steps())
    finally:
        saver.close()
    with open(os.path.join(export, "export_meta.json")) as f:
        assert json.load(f)["step"] == steps
    assert os.path.exists(os.path.join(export, "params.msgpack"))
    check_state_placement(worker, devices)
    say(
        "train",
        f"DeepFM {model_params} B={batch}: {steps} optimizer steps in "
        f"{seconds:.1f}s (train step compile {compile_s:.1f}s), losses="
        f"{[round(x, 5) for x in losses]}, checkpoints at {saved_steps}, "
        f"export written, state on {len(devices)} {devices[0].platform} "
        "device(s)",
    )

    if len(devices) > 1:
        batches = first_batches(zoo_module, data, batch, steps)
        check_batch_split(worker, batches[0], devices)
        reference = one_device_losses(DEEPFM_DEF, model_params, batches)
        np.testing.assert_allclose(
            losses, reference, rtol=MULTI_DEVICE_LOSS_RTOL,
            err_msg="N-device losses drifted from the one-device run",
        )
        worst = max(
            abs(a - b) / abs(b) for a, b in zip(losses, reference)
        )
        say(
            "train",
            f"mesh data={len(devices)}: batch rows split "
            f"{batch // len(devices)}/device, state on every device, "
            f"losses agree with one device within rel {worst:.2e} "
            f"(allowed {MULTI_DEVICE_LOSS_RTOL:.0e}); one-device losses="
            f"{[round(x, 5) for x in reference]}",
        )
    return worker, export


# ---- serve ---------------------------------------------------------------


def serve_deepfm(worker, export, *, model_params=DEEPFM_PARAMS,
                 steps=DEEPFM_STEPS, buckets=SERVE_BUCKETS):
    """`elasticdl serve --export_dir` on an ephemeral port; Predict over
    localhost gRPC at two bucket sizes, checked against the trainer's own
    forward on the state the export was written from."""
    import grpc
    import numpy as np

    from elasticdl_tpu.client.api import build_serving_server
    from elasticdl_tpu.client.main import _build_parser
    from elasticdl_tpu.common.resilience import default_policy
    from elasticdl_tpu.proto import serving_pb2 as spb
    from elasticdl_tpu.proto.service import ServingStub
    from elasticdl_tpu.serving.server import (
        from_tensor_proto,
        make_predict_request,
    )

    args = _build_parser().parse_args([
        "serve",
        "--model_zoo", ZOO,
        "--model_def", DEEPFM_DEF,
        "--model_params", model_params,
        "--export_dir", export,
        "--batch_buckets", ",".join(str(b) for b in buckets),
    ])
    # rows that pad into each bucket and rows that fill it; their sum
    # divides over 1, 2 and 4 devices for the one reference forward
    request_rows = (buckets[0] // 2, buckets[0], buckets[-1] - 24,
                    buckets[-1])
    rng = np.random.RandomState(1)
    features = {
        "dense": rng.rand(sum(request_rows), 13).astype(np.float32),
        "sparse": (
            rng.zipf(1.5, size=(sum(request_rows), 26)) % (1 << 22)
        ).astype(np.int32),
    }
    want = np.asarray(
        worker.trainer.predict_on_batch(worker.state, features)
    )
    server = build_serving_server(args)
    answered = []
    try:
        port = server.start(0)
        channel = grpc.insecure_channel(f"localhost:{port}")
        stub = ServingStub(channel, retry_policy=default_policy())
        start = 0
        for rows in request_rows:
            rows_slice = slice(start, start + rows)
            start += rows
            t0 = time.perf_counter()
            resp = stub.predict(make_predict_request(
                {k: v[rows_slice] for k, v in features.items()}
            ))
            millis = (time.perf_counter() - t0) * 1e3
            assert resp.code == spb.SERVING_OK, (resp.code, resp.error)
            assert resp.model_step == steps, resp.model_step
            preds = from_tensor_proto(resp.predictions)
            assert preds.shape == want[rows_slice].shape, preds.shape
            assert np.isfinite(preds).all()
            np.testing.assert_allclose(
                preds, want[rows_slice], rtol=BF16_TOL, atol=BF16_TOL,
                err_msg="served predictions drifted from the trainer's",
            )
            answered.append((rows, round(millis, 1)))
        health = stub.health(spb.HealthRequest())
        assert list(health.buckets) == list(buckets), health.buckets
        assert health.compile_count == len(buckets), (
            f"{health.compile_count} compiles for {len(buckets)} buckets"
        )
        channel.close()
    finally:
        server.stop()
    say(
        "serve",
        f"buckets {buckets} on port {port}: {len(answered)} Predict RPCs "
        f"answered at model_step={steps}, (rows, ms)={answered}, finite, "
        "matching the trainer's forward",
    )


# ---- kernel --------------------------------------------------------------


def train_bert(work, devices, *, model_params=BERT_PARAMS, batch=BERT_BATCH,
               seq=BERT_SEQ, steps=BERT_STEPS):
    """BERT through the same CLI path; then prove the flash forward in
    that very train step was compiled by Mosaic, not interpreted."""
    from elasticdl_tpu.common.model_handler import load_module
    from elasticdl_tpu.ops.flash_attention import use_interpret
    from elasticdl_tpu.parallel import mesh as mesh_lib

    data = os.path.join(work, "bert.tfrecord")
    write_bert_tfrecord(data, steps * batch, seq)
    t0 = time.perf_counter()
    worker, losses, compile_s = cli_train(
        BERT_DEF, model_params, data, batch, steps, tasks=steps
    )
    seconds = time.perf_counter() - t0
    check_state_placement(worker, devices)

    assert not use_interpret(), "flash kernel would run interpreted here"
    zoo_module, _ = load_module(ZOO, BERT_DEF)
    sample = first_batches(zoo_module, data, batch, 1)[0]
    compiled = worker.trainer.train_step.aot_compile(
        worker.state, mesh_lib.shard_batch(sample, worker.trainer.mesh)
    )
    kernels = compiled.as_text().count("tpu_custom_call")
    assert kernels > 0, "no Mosaic custom call in the compiled train step"
    say(
        "kernel",
        f"BERT {model_params} B={batch}: {steps} optimizer steps in "
        f"{seconds:.1f}s (train step compile {compile_s:.1f}s), losses="
        f"{[round(x, 5) for x in losses]}; compiled train step holds "
        f"{kernels} tpu_custom_call site(s), use_interpret()=False, "
        "shard_map vma audit on",
    )


def check_kernel_corners(corners=KERNEL_CORNERS):
    """The kernel alone against the O(L^2) reference at every listed
    corner, causal and not.  A corner `flash_shapes_ok` does not admit is
    reported as dispatched to the lax body — never attempted."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from elasticdl_tpu.ops.flash_attention import (
        flash_attention,
        flash_shapes_ok,
    )
    from elasticdl_tpu.ops.ring_attention import full_attention_reference

    for batch, length, heads, dim, dtype in corners:
        shape = (batch, length, heads, dim)
        if not flash_shapes_ok(shape, shape):
            say("kernel", f"corner {shape} {dtype}: not admitted, lax body")
            continue
        rng = np.random.RandomState(length + dim)
        q, k, v = (
            jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.5, dtype)
            for _ in range(3)
        )
        for causal in (False, True):
            t0 = time.perf_counter()
            out = jax.jit(
                functools.partial(flash_attention, causal=causal)
            )(q, k, v)
            out = np.asarray(out.astype(jnp.float32))
            seconds = time.perf_counter() - t0
            with jax.default_matmul_precision("highest"):
                want = np.asarray(full_attention_reference(
                    *(x.astype(jnp.float32) for x in (q, k, v)),
                    causal=causal,
                ))
            assert out.shape == shape and np.isfinite(out).all()
            np.testing.assert_allclose(
                out, want, rtol=BF16_TOL, atol=BF16_TOL,
                err_msg=f"flash corner {shape} {dtype} causal={causal}",
            )
            say(
                "kernel",
                f"corner {shape} {dtype} causal={causal}: compiled and "
                f"ran in {seconds:.1f}s, max |err| vs reference "
                f"{np.abs(out - want).max():.2e} (allowed {BF16_TOL})",
            )


# ---- cache ---------------------------------------------------------------


def report_cache(cache_dir: str, entries_before: int) -> None:
    from elasticdl_tpu.common import programs

    ledger = programs.default_program_registry().ledger()
    compiled = {
        name: {
            "compiles": rec["compiles"],
            "seconds": round(rec["compile_seconds_total"], 2),
        }
        for name, rec in ledger.items() if rec["compiles"]
    }
    after = cache_entries(cache_dir)
    say(
        "cache",
        f"compile seconds per registered program (trace + XLA compile or "
        f"cache load): {json.dumps(compiled, sort_keys=True)}; "
        f"{cache_dir} entries before={entries_before} after={after} "
        f"added={after - entries_before}",
    )


def main() -> int:
    # Every executable persists (jax's default skips compiles under 1 s),
    # so "a second run adds no cache entry" is a check, not a coin toss.
    # Read by jax at import.
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    sys.path.insert(0, ROOT)
    t0 = time.perf_counter()
    devices, cache_dir = preflight()
    entries_before = cache_entries(cache_dir)
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        worker, export = train_deepfm(work, devices)
        serve_deepfm(worker, export)
        del worker
        train_bert(work, devices)
        check_kernel_corners()
        report_cache(cache_dir, entries_before)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    say("done", f"all phases passed in {time.perf_counter() - t0:.0f}s")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
