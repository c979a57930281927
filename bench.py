"""Benchmark entry point: prints ONE JSON line with the headline metric.

Headline config tracks BASELINE.md #4 (north star): DeepFM on Criteo-style
data — the sparse-embedding stress path (the reference's PS-mode flagship).
Runs on the real TPU chip.  The reference publishes no numbers
(BASELINE.json `published: {}`), so `vs_baseline` is 1.0 by definition.

Secondary benches (run with `python bench.py all`): MNIST CNN, BERT ring
attention.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _ROOT)
_ZOO = os.path.join(_ROOT, "model_zoo")

# Persistent XLA-executable cache: a re-run loads BERT-base and the
# other large executables from disk instead of recompiling them.
from elasticdl_tpu.common.virtual_mesh import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()


def _trainer_for(model_def: str, model_params: str = "", use_bf16=False):
    from elasticdl_tpu.common.model_handler import get_model_spec
    from elasticdl_tpu.worker.trainer import Trainer

    spec = get_model_spec(_ZOO, model_def, model_params=model_params)
    return spec, Trainer(
        model=spec.model,
        optimizer=spec.optimizer,
        loss_fn=spec.loss,
        use_bf16=use_bf16,
        param_sharding_fn=spec.param_sharding,
    )


def _device_peaks():
    """Peak numbers for MFU/roofline; None on the CPU platform (MFU
    then omitted), an error for an accelerator not in the table.
    Delegates to the program observatory so bench reports and live
    /varz telemetry divide by the same roofline table."""
    from elasticdl_tpu.common import programs

    return programs.device_peaks()


def _cost(compiled) -> dict:
    """flops / bytes-accessed from XLA's own cost model — the program
    observatory's reader (one code path shared with the live ledger)."""
    from elasticdl_tpu.common import programs

    return programs.cost_analysis_dict(compiled)


def _arena_bytes_per_step(
    batch_size: int,
    vocab_capacity: int,
    embed_dim: int,
    arena_dtype: str,
    n_fields: int = 26,
) -> dict:
    """Analytic bytes the ARENA PLANES contribute to one DeepFM train
    step, from capacity/dim/dtype alone — the attributable counterpart
    to the XLA cost-model total (which mixes in MLP/FM traffic and
    fusion estimates).  Per table (embed_dim-wide + the dim-1 linear):

    - gather plane: n_ids rows x dim x itemsize (1 byte int8 / 4 fp32),
      plus a 4-byte per-row scale read in int8 mode.  This is the
      RANDOM-ACCESS plane — the memory-wall term int8 exists to shrink;
    - scatter plane: the backward writes an fp32 zeros gradient table
      (capacity x dim x 4) and scatter-adds n_ids fp32 rows — identical
      in both modes (the gradient/optimizer path stays fp32);
    - int8 write-back fold: re-reads and re-writes the full code +
      scale planes (2 x capacity x (dim + 4)) — SEQUENTIAL streaming,
      cheap per byte next to the gather's random access, but it makes
      the int8 train-step TOTAL larger at small batch.  The gather
      component is the like-for-like reduction figure (and the whole
      story for serving, which runs no fold).
    """
    n_ids = batch_size * n_fields
    out = {"gather": 0, "scatter": 0, "fold": 0}
    for dim in (embed_dim, 1):  # fm_embedding + fm_linear
        item = 1 if arena_dtype == "int8" else 4
        gather = n_ids * dim * item
        if arena_dtype == "int8":
            gather += n_ids * 4  # per-row scale read
        out["gather"] += gather
        out["scatter"] += vocab_capacity * dim * 4 + n_ids * dim * 4
        if arena_dtype == "int8":
            out["fold"] += 2 * vocab_capacity * (dim + 4)
    out["total"] = out["gather"] + out["scatter"] + out["fold"]
    return out


def _make_criteo_batch(batch_size: int):
    rng = np.random.RandomState(0)
    return {
        "features": {
            "dense": rng.rand(batch_size, 13).astype(np.float32),
            # zipf-distributed ids over a large raw space: real CTR
            # traffic is heavily skewed, but large fields have millions
            # of distinct values — a small modulus would make the table
            # trivially cache-resident and flatter the bench
            "sparse": (
                rng.zipf(1.5, size=(batch_size, 26)) % (1 << 22)
            ).astype(np.int32),
        },
        "labels": rng.randint(0, 2, batch_size).astype(np.int32),
    }


def _deepfm_auc(
    steps: int = 32,
    batch_size: int = 4096,
    arena_dtype: str = "float32",
) -> float:
    """Short convergence run with planted structure (BASELINE.md: steps/sec
    only counts *at matching AUC*; this proves the measured step learns)."""
    import jax

    from model_zoo.common.metrics import auc as auc_fn
    from model_zoo.deepfm.data import synthetic_criteo

    spec, trainer = _trainer_for(
        "deepfm.deepfm_functional_api.custom_model",
        model_params=(
            "vocab_capacity=1048576;embed_dim=16;bf16=True;lr=0.005;"
            f"arena_dtype='{arena_dtype}'"
        ),
        use_bf16=True,
    )
    dense, sparse, labels = synthetic_criteo(steps * batch_size, seed=0)
    state = trainer.init_state(
        jax.random.PRNGKey(0),
        {"dense": dense[:batch_size], "sparse": sparse[:batch_size]},
    )
    for i in range(steps):
        sl = slice(i * batch_size, (i + 1) * batch_size)
        state, _ = trainer.train_on_batch(
            state,
            {
                "features": {"dense": dense[sl], "sparse": sparse[sl]},
                "labels": labels[sl].astype(np.int32),
            },
        )
    vd, vs, vy = synthetic_criteo(16384, seed=1000)
    preds = trainer.predict_on_batch(state, {"dense": vd, "sparse": vs})
    return float(auc_fn(vy, preds))


def bench_deepfm(iters: int = 30, arena_dtype: str = "float32"):
    """North-star bench (BASELINE.md #4): DeepFM/Criteo sparse stress.

    bf16 MLP compute (params f32), batch-size sweep for the headline, XLA
    cost-model MFU + HBM utilisation, an embedding-gather roofline probe
    (the step is gather-bound by design — SURVEY.md hard part 2), and AUC
    from a short convergence run so the steps/sec number is of a step that
    demonstrably learns.  `arena_dtype="int8"` runs the same bench with
    quantized embedding storage (ISSUE 9) — dispatch key `deepfm-int8`."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.parallel import mesh as mesh_lib

    spec, trainer = _trainer_for(
        "deepfm.deepfm_functional_api.custom_model",
        model_params=(
            "vocab_capacity=1048576;embed_dim=16;bf16=True;"
            f"arena_dtype='{arena_dtype}'"
        ),
        use_bf16=True,
    )
    peaks = _device_peaks()
    sweep = {}
    best = None
    state = None
    # Device-honest timing throughout (timed_steps_per_sec_fused): a
    # fused on-device loop returning the step counter PLUS a
    # params-derived anchor (without the anchor XLA DCEs the training
    # chain and the loop times one round trip), value-fetch synced.
    # two points only: each size costs a fresh ~40s XLA compile, and the
    # driver runs this under a wall-clock budget.  The step is
    # embedding-gather-bound (cost ~linear in ids = 26*batch), so
    # throughput is roughly flat in batch with mild regime effects —
    # measured honestly, the mid sizes win (the old large-batch sweep
    # points were chosen on DCE-inflated numbers).  Median-of-3 per
    # sweep point: one noisy sample must not pick the regime winner.
    for batch_size in (16384, 65536):
        batch = _make_criteo_batch(batch_size)
        state = trainer.init_state(jax.random.PRNGKey(0), batch["features"])
        point = sorted(
            trainer.timed_steps_per_sec_fused(state, batch, iters=iters)
            for _ in range(3)
        )[1]
        examples_per_sec = point * batch_size
        sweep[batch_size] = round(examples_per_sec, 1)
        if best is None or examples_per_sec > best[1]:
            best = (batch_size, examples_per_sec, point)
    batch_size = best[0]
    # median-of-5 at the winning batch (each repeat is compile-free so
    # the extra runs cost seconds)
    batch = _make_criteo_batch(batch_size)
    state = trainer.init_state(jax.random.PRNGKey(0), batch["features"])
    repeats = [
        trainer.timed_steps_per_sec_fused(state, batch, iters=iters)
        for _ in range(5)
    ]
    steps_per_sec = sorted(repeats)[2]
    examples_per_sec = steps_per_sec * batch_size
    sweep[batch_size] = round(examples_per_sec, 1)
    detail_repeats = [round(r * batch_size, 1) for r in repeats]

    # XLA cost model on the winning shape -> MFU + HBM utilisation
    batch = _make_criteo_batch(batch_size)
    state = trainer.init_state(jax.random.PRNGKey(0), batch["features"])
    sharded = mesh_lib.shard_batch(batch, trainer.mesh)
    cost = trainer.train_step.cost_for(state, sharded)
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    detail = {
        "steps_per_sec": round(steps_per_sec, 2),
        "batch_size": batch_size,
        "batch_sweep_examples_per_sec": sweep,
        "headline_repeats_examples_per_sec": detail_repeats,
        "vocab_capacity": 1 << 20,
        "embed_dim": 16,
        "compute_dtype": "bfloat16",
        "param_dtype": "float32",
        "arena_dtype": arena_dtype,
        "device": str(jax.devices()[0]),
        "step_flops_xla": flops,
        # XLA cost-model operand bytes: an upper bound on logical access,
        # NOT physical HBM traffic (fusion/VMEM reuse make it exceed the
        # HBM roof) — recorded for step-to-step comparison only.
        "step_bytes_accessed_xla_costmodel": bytes_accessed,
        # Analytic arena-plane traffic (gather + scatter + int8 fold),
        # from capacity/dim/dtype — the attributable slice of the number
        # above; see _arena_bytes_per_step for the formula.
        "arena_bytes_per_step": _arena_bytes_per_step(
            batch_size, 1 << 20, 16, arena_dtype
        ),
    }
    if flops:
        detail["achieved_tflops"] = round(flops * steps_per_sec / 1e12, 2)
    if peaks and flops:
        detail["mfu"] = round(flops * steps_per_sec / peaks["bf16_flops"], 4)

    # Registry-backed program ledger: cost_for above recorded its AOT
    # compile into the process-wide observatory, so this block and live
    # /varz telemetry report from ONE ledger (no private bench-only
    # cost path).  Reconciliation: the analytic arena planes must be an
    # attributable SUBSET of XLA's cost-model operand bytes (which add
    # MLP/FM/optimizer traffic plus fusion estimates) — share in
    # (0, tolerance], with 1.05 slack for cost-model rounding on fused
    # gathers.  Measured share on the headline shape is ~0.08-0.2; a
    # share near or above 1 means the cost model stopped seeing the
    # arena traffic (a fusion regression worth failing loudly on).
    from elasticdl_tpu.common import programs as programs_lib

    reconciliation = {"tolerance_max_share": 1.05}
    if bytes_accessed:
        share = _arena_bytes_per_step(
            batch_size, 1 << 20, 16, arena_dtype
        )["total"] / bytes_accessed
        reconciliation["arena_share_of_costmodel_bytes"] = round(share, 4)
        reconciliation["within_tolerance"] = bool(0.0 < share <= 1.05)
    detail["program_ledger"] = {
        "programs": programs_lib.default_program_registry().ledger(),
        "reconciliation": reconciliation,
    }

    # Embedding fwd+bwd probe, isolated and device-honest (fused loop,
    # scalar out): the design-note evidence for the XLA gather/scatter
    # path vs SparseCore (SURVEY.md §7 hard part 2).
    import time as _time

    from elasticdl_tpu.layers.embedding import _lookup

    table = state.params["params"]["fm_embedding"]["embedding"]
    flat_ids = jnp.asarray(
        batch["features"]["sparse"].reshape(-1) % (1 << 20)
    )

    def _emb_loop(t, ids):
        grad_fn = jax.grad(lambda tt: (_lookup(tt, ids) ** 2).sum())

        def body(_, acc):
            # the carry feeds the input so XLA cannot hoist the grad out
            # of the loop (loop-invariant code motion would otherwise
            # under-report by the iteration factor)
            return acc + grad_fn(t + 0.0 * acc)[0, 0]

        return jax.lax.fori_loop(0, iters, body, jnp.zeros((), jnp.float32))

    probe = jax.jit(_emb_loop)
    jax.device_get(probe(table, flat_ids))
    t0 = _time.perf_counter()
    jax.device_get(probe(table, flat_ids))
    gather_s = (_time.perf_counter() - t0) / iters
    # isolated => UNFUSED upper bound (the real step fuses the lookup
    # backward with surrounding work and runs faster than this probe)
    detail["embedding_fwd_bwd_isolated_upper_bound_ms"] = round(
        gather_s * 1e3, 3
    )

    detail["auc_synthetic_criteo"] = round(
        _deepfm_auc(arena_dtype=arena_dtype), 4
    )
    detail["timing_method"] = (
        "fused on-device fori_loop, step-counter + params-anchor "
        "outputs, value-fetch synced.  The anchor matters: without a "
        "params-derived output XLA DCEs the whole training chain and "
        "the loop times one device round trip regardless of iters."
    )
    return {
        "metric": "deepfm_criteo_train_examples_per_sec",
        "value": round(examples_per_sec, 1),
        "unit": "examples/sec",
        "vs_baseline": 1.0,
        "detail": detail,
    }


def _bench_data_dir() -> str:
    import tempfile

    d = os.path.join(
        tempfile.gettempdir(), f"elasticdl_bench_{os.getuid()}"
    )
    os.makedirs(d, mode=0o700, exist_ok=True)
    return d


def _ensure_bench_criteo(n_records: int) -> str:
    """Generate (once, cached) a Criteo-format TFRecord file whose id
    distribution matches the synthetic bench batches (zipf over a 4M raw
    space), so e2e and synthetic numbers time the same device work."""
    path = os.path.join(_bench_data_dir(), f"criteo_{n_records}.tfrecord")
    if os.path.exists(path):
        return path
    from elasticdl_tpu.data.record_io import write_tfrecords_bulk
    from model_zoo.deepfm.deepfm_functional_api import RECORD_BYTES

    rng = np.random.RandomState(0)
    arr = np.empty((n_records, RECORD_BYTES), np.uint8)
    arr[:, :52] = (
        rng.rand(n_records, 13).astype(np.float32).view(np.uint8)
    )
    arr[:, 52:156] = (
        (rng.zipf(1.5, size=(n_records, 26)) % (1 << 22))
        .astype(np.int32).view(np.uint8)
    )
    arr[:, 156] = rng.randint(0, 2, n_records)
    write_tfrecords_bulk(
        path, arr.reshape(-1), np.full(n_records, RECORD_BYTES, np.int64)
    )
    return path


def bench_deepfm_e2e(
    n_records: int = 1 << 21,
    batch_size: int = 65536,
    records_per_task: int = 1 << 19,
    steps_per_execution: int = 8,
    wire: str = "dedup",
):
    """End-to-end input pipeline bench: reader -> feed_bulk -> device
    train step, timed as one wall-clock pass over a real TFRecord file
    through the worker's actual batch cutter (TaskDataService) and the
    worker's steps_per_execution dispatch grouping.  VERDICT r3 weak #2:
    the synthetic bench times already-materialized batches; this one
    proves the host data plane keeps the device fed (target: within ~15%
    of the synthetic number).  Sync discipline: final value fetch."""
    import jax

    from elasticdl_tpu.data.reader.tfrecord_reader import TFRecordDataReader
    from elasticdl_tpu.proto import elasticdl_pb2 as pb
    from elasticdl_tpu.worker.task_data_service import TaskDataService
    from model_zoo.deepfm import deepfm_functional_api as zoo

    path = _ensure_bench_criteo(n_records)
    spec, trainer = _trainer_for(
        "deepfm.deepfm_functional_api.custom_model",
        model_params="vocab_capacity=1048576;embed_dim=16;bf16=True",
        use_bf16=True,
    )
    reader = TFRecordDataReader(path)
    service = TaskDataService(None, reader, worker_id=0)
    tasks = [
        pb.Task(
            task_id=i, type=pb.TRAINING,
            shard=pb.Shard(name=path, start=start,
                           end=min(start + records_per_task, n_records)),
        )
        for i, start in enumerate(range(0, n_records, records_per_task))
    ]

    # Wire format: on a bandwidth-limited link the pipeline ceiling is
    # H2D/bytes-per-example, and bytes-per-example is the framework's
    # lever (VERDICT r4 weak #2).  "compact" = dense bf16 + b22 ids +
    # uint8 labels (99 B/ex vs plain 160); "dedup" additionally ships
    # each field's distinct HOST-HASHED rows once plus a 1-byte inverse
    # (~61-64 B/ex on this zipf stream; see --sparse-path for the
    # format-by-format breakdown).
    wire_feed = {
        "plain": zoo.feed_bulk,
        "compact": zoo.feed_bulk_compact,
        "dedup": zoo.feed_bulk_dedup,
    }[wire]

    def feed_bulk(buf, sizes):
        return wire_feed(buf, sizes)

    def batches(task):
        return service.batches_for_task(
            task, batch_size, zoo.feed, feed_bulk=feed_bulk
        )

    # warm-up: compile both dispatch programs (K-stack and single step)
    warm = [b for b, _ in batches(tasks[0])][:steps_per_execution]
    state = trainer.init_state(jax.random.PRNGKey(0), warm[0]["features"])
    state, losses = trainer.train_on_batch_stack(state, warm)
    state, loss = trainer.train_on_batch(state, warm[0])
    jax.device_get((losses, loss))

    import time as _time

    # Host-only pipeline rate (reader -> feed_bulk -> stacked host
    # arrays): proves the host side independent of the device link.
    t0 = _time.perf_counter()
    host_count = 0
    for batch, real in batches(tasks[0]):
        host_count += real
    host_only = host_count / (_time.perf_counter() - t0)

    # Sustained host->device bandwidth, value-fetch synced.  AMORTIZED
    # over several back-to-back transfers (one transfer's fixed
    # round-trip latency would understate the link), best of 3.
    probe = np.random.RandomState(0).rand(
        batch_size, 40
    ).astype(np.float32)
    n_bufs = 6
    put = jax.jit(lambda x: x[0, 0], donate_argnums=())
    jax.device_get(put(jax.device_put(probe)))          # warm the path
    h2d_mb_s = 0.0
    for _ in range(3):
        t0 = _time.perf_counter()
        handles = [jax.device_put(probe) for _ in range(n_bufs)]
        jax.device_get([put(h) for h in handles])
        h2d_mb_s = max(
            h2d_mb_s,
            n_bufs * probe.nbytes / 1e6 / (_time.perf_counter() - t0),
        )

    # Timed end-to-end pass.  A producer thread runs the host pipeline
    # (read -> parse -> stack) so device transfers/compute overlap host
    # work — the worker-loop shape a real deployment wants.
    import queue as _queue
    import threading as _threading

    q: "_queue.Queue" = _queue.Queue(maxsize=2)

    def shapes_of(batch):
        return [np.shape(x) for x in jax.tree.leaves(batch)]

    def produce():
        pending = []
        for task in tasks:
            for batch, real in batches(task):
                if pending and shapes_of(batch) != shapes_of(pending[0][0]):
                    # dedup sticky caps can grow between batches; a
                    # mixed-shape group can't np.stack — flush it
                    q.put(("tail", pending))
                    pending = []
                pending.append((batch, real))
                if len(pending) == steps_per_execution:
                    q.put(("stack", pending))
                    pending = []
        if pending:
            q.put(("tail", pending))
        q.put(None)

    # Phase attribution over the timed pass only (warm-up/compile and
    # the host-only pass above must not pollute the breakdown): the
    # same PhaseTimer hooks the worker loops use — TaskDataService
    # times pack on the producer thread, the trainer times
    # h2d_stage/compute, and the q.get below is data_wait.
    from elasticdl_tpu.common.profiler import PhaseTimer

    phase_timer = PhaseTimer(flush_every=1 << 30)
    trainer.phase_timer = phase_timer
    service.phase_timer = phase_timer

    t0 = _time.perf_counter()
    producer = _threading.Thread(target=produce, daemon=True)
    producer.start()
    count = 0
    wire_bytes = 0
    n_batches = 0
    while True:
        t_wait = _time.perf_counter()
        item = q.get()
        phase_timer.add("data_wait", _time.perf_counter() - t_wait)
        if item is None:
            break
        kind, group = item
        count += sum(real for _, real in group)
        for b, _ in group:
            wire_bytes += sum(x.nbytes for x in jax.tree.leaves(b))
        n_batches += len(group)
        if kind == "stack":
            state, losses = trainer.train_on_batch_stack(
                state, [b for b, _ in group]
            )
        else:
            for batch, _ in group:
                state, losses = trainer.train_on_batch(state, batch)
        for _ in group:
            phase_timer.step_done()
    jax.device_get(losses)
    elapsed = _time.perf_counter() - t0
    e2e = count / elapsed
    # measured over the whole timed pass (dedup batch sizes vary a
    # little with the sticky unique/escape caps), not just warm[0]
    batch_mb = wire_bytes / max(n_batches, 1) / 1e6
    detail = {
        "e2e_examples_per_sec": round(e2e, 1),
        "e2e_records": count,
        "e2e_batch_size": batch_size,
        "e2e_wire_format": wire,
        "e2e_steps_per_execution": steps_per_execution,
        "e2e_seconds": round(elapsed, 2),
        "e2e_file_mb": round(os.path.getsize(path) / 1e6, 1),
        "e2e_host_pipeline_examples_per_sec": round(host_only, 1),
        # compact wire format (elasticdl_tpu/data/wire.py): bytes that
        # actually cross the link per batch — dense bf16, ids
        # b22-packed, labels uint8
        "e2e_batch_mb": round(batch_mb, 2),
        "e2e_wire_bytes_per_example": round(
            batch_mb * 1e6 / batch_size, 1
        ),
    }
    # The transfer ceiling this link imposes on ANY input pipeline:
    # examples/s <= H2D bandwidth / wire-bytes-per-example.  The link's
    # demonstrated capability is the MAX of the probe and the timed
    # pass's own implied wire rate; the max keeps ceiling >= measured
    # by construction while both components stay recorded for
    # transparency.
    implied_mb_s = count * (batch_mb / batch_size) / elapsed
    best_mb_s = max(h2d_mb_s, implied_mb_s)
    detail["e2e_h2d_mb_per_sec_probe"] = round(h2d_mb_s, 1)
    detail["e2e_h2d_mb_per_sec_implied_by_pipeline"] = round(
        implied_mb_s, 1
    )
    detail["e2e_transfer_ceiling_examples_per_sec"] = round(
        best_mb_s / (batch_mb / batch_size), 1
    )
    detail["e2e_link_utilization"] = round(implied_mb_s / best_mb_s, 3)
    # Where each step's wall time went (docs/OBSERVABILITY.md "Phase
    # catalogue"): mean seconds per phase per step + the phase's share
    # of all attributed time.  data_wait ~0 means the host pipeline
    # kept the device fed; a large h2d_stage share means the link, not
    # compute, bounds e2e (the transfer-ceiling story above, but
    # measured in-band).
    detail["e2e_phase_breakdown"] = {
        p: {
            "mean_s_per_step": round(s["mean_s"], 5),
            "share": round(s["share"], 3),
        }
        for p, s in phase_timer.snapshot().items()
        if s["total_s"] > 0
    }
    return detail


def bench_mnist(batch_size: int = 256, iters: int = 50):
    import jax

    from elasticdl_tpu.parallel import mesh as mesh_lib

    spec, trainer = _trainer_for("mnist.mnist_functional_api.custom_model")
    rng = np.random.RandomState(0)
    batch = {
        "features": rng.rand(batch_size, 784).astype(np.float32),
        "labels": rng.randint(0, 10, batch_size).astype(np.int32),
    }
    state = trainer.init_state(jax.random.PRNGKey(0), batch["features"])
    steps_per_sec = trainer.timed_steps_per_sec_fused(
        state, batch, iters=iters
    )
    detail = {"steps_per_sec": round(steps_per_sec, 2),
              "batch_size": batch_size}
    # flops/TFLOPs detail so a regression in anything but raw throughput
    # is visible (VERDICT r4 weak #7); this tiny model is dispatch-bound,
    # so MFU is recorded for trend, not as a utilization claim
    sharded = mesh_lib.shard_batch(batch, trainer.mesh)
    cost = trainer.train_step.cost_for(state, sharded)
    flops = float(cost.get("flops", 0.0))
    peaks = _device_peaks()
    if flops:
        detail["step_flops_xla"] = flops
        detail["achieved_tflops"] = round(flops * steps_per_sec / 1e12, 3)
        if peaks:
            detail["mfu"] = round(
                flops * steps_per_sec / peaks["bf16_flops"], 5
            )
    return {
        "metric": "mnist_cnn_train_examples_per_sec",
        "value": round(steps_per_sec * batch_size, 1),
        "unit": "examples/sec",
        "vs_baseline": 1.0,
        "detail": detail,
    }


def _measured_matmul_roofline_tflops(iters: int = 20) -> float:
    """Best sustained bf16 matmul rate THIS device actually delivers
    (8192^3 chained matmuls, value-fetch synced).  Recorded alongside
    the datasheet peak, so utilization is reported against both
    (mfu = datasheet; mfu_vs_measured_roofline = this)."""
    import jax
    import jax.numpy as jnp

    m = 8192
    a = jnp.asarray(np.random.rand(m, m), jnp.bfloat16)
    b = jnp.asarray(np.random.rand(m, m), jnp.bfloat16)

    def loop(a, b):
        def body(_, acc):
            c = jax.lax.dot_general(
                a + 0.0 * acc[0, 0].astype(a.dtype), b,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return acc + c

        return jax.lax.fori_loop(
            0, iters, body, jnp.zeros((m, m), jnp.float32)
        )[0, 0]

    import time as _time

    fn = jax.jit(loop)
    jax.device_get(fn(a, b))
    t0 = _time.perf_counter()
    jax.device_get(fn(a, b))
    return 2 * m * m * m * iters / (_time.perf_counter() - t0) / 1e12


def bench_bert(batch_size: int = 64, seq_len: int = 512, iters: int = 30):
    """Compute-bound MFU headline (VERDICT r3 weak #1: a TPU framework
    with no MXU-bound number is unproven on the axis TPUs exist for).
    BERT-base, bf16 end-to-end, fixed 512-seq; MFU from the XLA cost
    model on the honest fused timing, reported against BOTH the
    datasheet peak and the device's measured matmul roofline."""
    import jax

    from elasticdl_tpu.parallel import mesh as mesh_lib

    spec, trainer = _trainer_for(
        "bert.bert_finetune.custom_model",
        model_params=(
            f"hidden=768;num_layers=12;heads=12;mlp_dim=3072;"
            f"max_len={seq_len};bf16=True"
        ),
        use_bf16=True,
    )
    rng = np.random.RandomState(0)
    batch = {
        "features": {
            "input_ids": rng.randint(
                0, 8192, size=(batch_size, seq_len)
            ).astype(np.int32)
        },
        "labels": rng.randint(0, 2, batch_size).astype(np.int32),
    }
    state = trainer.init_state(jax.random.PRNGKey(0), batch["features"])
    repeats = [
        trainer.timed_steps_per_sec_fused(state, batch, iters=iters)
        for _ in range(3)
    ]
    steps_per_sec = sorted(repeats)[1]
    detail = {
        "steps_per_sec": round(steps_per_sec, 3),
        "batch_size": batch_size, "seq_len": seq_len,
        "compute_dtype": "bfloat16",
    }
    sharded = mesh_lib.shard_batch(batch, trainer.mesh)
    cost = trainer.train_step.cost_for(state, sharded)
    flops = float(cost.get("flops", 0.0))
    peaks = _device_peaks()
    if flops:
        detail["step_flops_xla"] = flops
        detail["achieved_tflops"] = round(flops * steps_per_sec / 1e12, 2)
    if peaks and flops:
        detail["mfu"] = round(
            flops * steps_per_sec / peaks["bf16_flops"], 4
        )
        roofline = _measured_matmul_roofline_tflops()
        detail["matmul_roofline_tflops_measured"] = round(roofline, 1)
        detail["mfu_vs_measured_roofline"] = round(
            flops * steps_per_sec / (roofline * 1e12), 4
        )
    return {
        "metric": "bert_base_finetune_examples_per_sec",
        "value": round(steps_per_sec * batch_size, 1),
        "unit": "examples/sec",
        "vs_baseline": 1.0,
        "detail": detail,
    }


def bench_full():
    """Default driver entry: ONE JSON line.  Headline stays the DeepFM
    north star (BASELINE.md #4); `detail` carries the e2e input-pipeline
    number and the BERT/MNIST sub-benches so every round records the
    compute-bound MFU alongside the sparse path (VERDICT r3 next-round
    items 1 and 2)."""
    result = bench_deepfm()
    e2e = bench_deepfm_e2e()
    result["detail"].update(e2e)
    result["detail"]["e2e_vs_synthetic"] = round(
        e2e["e2e_examples_per_sec"] / result["value"], 3
    )
    # always-present top-level wire economics (satellite: every
    # bench run records what the link pays per example and how much
    # of the demonstrated link the pipeline keeps busy)
    result["bytes_per_example"] = e2e["e2e_wire_bytes_per_example"]
    result["link_utilization"] = e2e["e2e_link_utilization"]
    result["detail"]["sparse_path"] = bench_sparse_path()["detail"]
    for key, fn in (("bert_base_finetune", bench_bert),
                    ("mnist_cnn", bench_mnist)):
        sub = fn()
        result["detail"][key] = {
            "examples_per_sec": sub["value"], **sub["detail"]
        }
    return result


def bench_serving(
    requests_per_client: int = 30,
    loads=(2, 8, 32),
    model_def: str = "mnist.mnist_functional_api.custom_model",
):
    """Online-serving bench: closed-loop clients against the in-process
    engine+batcher stack (no sockets — this measures batching/execution,
    not the NIC).  Three offered loads (concurrent clients); per load:
    p50/p99 client-observed latency, row throughput, batch-fill ratio."""
    import threading
    import time

    import jax

    from elasticdl_tpu.common.export import feature_meta
    from elasticdl_tpu.common.model_handler import get_model_spec
    from elasticdl_tpu.serving.batcher import OK, DynamicBatcher
    from elasticdl_tpu.serving.engine import ServingEngine

    spec = get_model_spec(_ZOO, model_def)
    sample = np.random.RandomState(0).rand(1, 784).astype(np.float32)
    variables = dict(spec.model.init(jax.random.PRNGKey(0), sample))
    engine = ServingEngine(
        spec.model, variables, step=0,
        feature_spec=feature_meta(sample), buckets=(1, 8, 32),
    )
    sizes = (1, 2, 5, 8)  # mixed request sizes, exercising padding
    per_load = []
    for clients in loads:
        batcher = DynamicBatcher(engine, max_latency_s=0.002)
        latencies, errors = [], []
        lock = threading.Lock()

        def run_client(seed):
            rng = np.random.RandomState(seed)
            mine = []
            for _ in range(requests_per_client):
                n = sizes[rng.randint(len(sizes))]
                x = rng.rand(n, 784).astype(np.float32)
                t0 = time.perf_counter()
                result = batcher.submit({"features": x}).result(timeout=60)
                dt = time.perf_counter() - t0
                if result.code == OK:
                    mine.append((dt, n))
                else:
                    with lock:
                        errors.append(result.code)
            with lock:
                latencies.extend(mine)

        threads = [
            threading.Thread(target=run_client, args=(i,))
            for i in range(clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        rows = sum(n for _, n in latencies)
        lat_s = np.array([dt for dt, _ in latencies]) if latencies \
            else np.array([0.0])
        snapshot = batcher.metrics.snapshot()
        batcher.shutdown()
        per_load.append({
            "clients": clients,
            "rows_per_sec": round(rows / elapsed, 1),
            "p50_ms": round(float(np.percentile(lat_s, 50)) * 1e3, 3),
            "p99_ms": round(float(np.percentile(lat_s, 99)) * 1e3, 3),
            "batch_fill_ratio": round(snapshot["batch_fill_ratio"], 3),
            "errors": len(errors),
        })
    return {
        "bench": "serving",
        "value": max(load["rows_per_sec"] for load in per_load),
        "unit": "rows_per_sec",
        "detail": {
            "model": model_def,
            "buckets": list(engine.buckets),
            "compile_count": engine.compile_count,
            "loads": per_load,
        },
    }


def bench_serving_fleet(
    clients: int = 4,
    requests_per_client: int = 50,
    replicas: int = 3,
    model_def: str = "mnist.mnist_functional_api.custom_model",
):
    """Fleet bench (`python bench.py --serving-fleet`): offered load
    against N in-process serving replicas behind the FleetRouter while
    the ServingFleetManager absorbs one mid-run replica kill and
    sequences one rolling hot-reload (docs/SERVING.md "Fleet").  Reports
    client-observed p50/p99, the failed-request count (the failover
    guarantee says it must be 0), the max observed cross-replica
    model_step skew vs the SLO, train-to-serve staleness p50/p99, the
    max staleness burn rate the SLO evaluator saw during the roll, the
    per-phase serve latency breakdown (queue_wait/compute/... p50/p99
    from the predict_span stream at full sampling), and the router-side
    tracing overhead (traced vs untraced mean latency over a calm
    sequential pass — the <2%% budget in docs/OBSERVABILITY.md)."""
    import tempfile
    import threading
    import time

    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.common import events as events_lib
    from elasticdl_tpu.common.constants import PodStatus
    from elasticdl_tpu.common.history import MetricHistory
    from elasticdl_tpu.common.k8s_client import FakeK8sClient
    from elasticdl_tpu.common.model_handler import get_model_spec
    from elasticdl_tpu.common.resilience import RetryPolicy
    from elasticdl_tpu.common.save_utils import CheckpointSaver
    from elasticdl_tpu.common.slo import SloEvaluator, shipped_specs
    from elasticdl_tpu.master.freshness import FreshnessTracker
    from elasticdl_tpu.master.serving_fleet import (
        ServingFleetConfig,
        ServingFleetManager,
    )
    from elasticdl_tpu.proto import serving_pb2 as spb
    from elasticdl_tpu.proto.service import (
        FleetRouter,
        InProcessServingClient,
    )
    from elasticdl_tpu.serving.batcher import DynamicBatcher
    from elasticdl_tpu.serving.engine import ServingEngine
    from elasticdl_tpu.serving.reloader import CheckpointReloader
    from elasticdl_tpu.serving.server import (
        ServingServicer,
        make_predict_request,
    )
    from elasticdl_tpu.worker.trainer import TrainState

    class _Killable:
        """In-process client whose kill switch stands in for a dead pod."""

        def __init__(self, servicer):
            self._inner = InProcessServingClient(servicer)
            self.killed = False

        def predict(self, request, timeout=None):
            if self.killed:
                raise ConnectionError("replica killed")
            return self._inner.predict(request, timeout=timeout)

        def health(self, request, timeout=None):
            if self.killed:
                raise ConnectionError("replica killed")
            return self._inner.health(request, timeout=timeout)

    spec = get_model_spec(_ZOO, model_def)
    sample = np.random.RandomState(0).rand(2, 784).astype(np.float32)
    variables = dict(spec.model.init(jax.random.PRNGKey(0), sample))
    params = {"params": variables.pop("params")}

    with tempfile.TemporaryDirectory() as tmp:
        saver = CheckpointSaver(tmp, async_save=False)

        def save_step(step, scale):
            scaled = jax.tree.map(lambda a: a * scale, params)
            saver.save(TrainState(
                step=jnp.asarray(step, jnp.int32), params=scaled,
                opt_state=spec.optimizer.init(scaled),
                model_state=variables,
            ), force=True)
            saver.wait_until_finished()

        save_step(1, 1.0)
        latest = [1]
        fleet = {}
        for rid in range(replicas):
            engine = ServingEngine.from_checkpoint(
                tmp, spec, sample, buckets=(2, 8)
            )
            batcher = DynamicBatcher(engine, max_latency_s=0.002)
            reloader = CheckpointReloader(
                engine, tmp, poll_interval_s=3600.0
            )
            fleet[rid] = {
                "batcher": batcher,
                "reloader": reloader,
                "servicer": ServingServicer(engine, batcher, reloader),
                "client": None,
            }

        def client_factory(rid, _addr):
            fleet[rid]["client"] = _Killable(fleet[rid]["servicer"])
            return fleet[rid]["client"]

        k8s = FakeK8sClient()
        freshness = FreshnessTracker(
            produced_time_fn=lambda step: (
                saver.produced_meta(step) or {}
            ).get("produced_unix_s"),
        )
        router = FleetRouter(
            retry_policy=RetryPolicy(
                initial_backoff_s=0.001, max_backoff_s=0.01,
                max_elapsed_s=30.0, max_attempts=8,
            ),
            freshness=freshness,
        )
        # per-phase serve latency from the predict_span stream (the
        # router defaults to full sampling): an in-process tap collects
        # every span's phase durations across all replicas
        phase_values = {}
        phase_lock = threading.Lock()

        def collect_span(record):
            if record.get("event") != events_lib.PREDICT_SPAN:
                return
            phases = record.get("phases_s")
            if not isinstance(phases, dict):
                return
            with phase_lock:
                for phase, seconds in phases.items():
                    phase_values.setdefault(phase, []).append(
                        float(seconds)
                    )

        events_lib.add_observer(collect_span)
        manager = ServingFleetManager(
            k8s,
            ServingFleetConfig(
                replicas=replicas, interval_s=0.0,
                probe_failures=2, step_skew_slo=16,
            ),
            job_name="bench",
            client_factory=client_factory,
            reload_fn=lambda rid: fleet[rid]["reloader"].check_once(),
            pending_step_fn=lambda: latest[0],
            router=router,
            freshness=freshness,
        )
        manager.place()
        manager.tick()  # prime: every replica probed healthy

        # staleness SLO watcher riding the same freshness evidence the
        # master would evaluate; ticked after every fleet tick
        history = MetricHistory(
            registries=[freshness.metrics_registry,
                        manager.metrics_registry],
        )
        evaluator = SloEvaluator(history, specs=[shipped_specs()[0]])
        max_burn = [0.0]

        def observe_slo():
            history.tick()
            evaluator.tick()
            max_burn[0] = max(max_burn[0], evaluator.max_burn())

        observe_slo()

        sizes = (1, 2, 5, 8)  # mixed request sizes, exercising padding
        latencies, failed = [], []
        lock = threading.Lock()

        def run_client(seed):
            rng = np.random.RandomState(seed)
            mine = []
            for _ in range(requests_per_client):
                n = sizes[rng.randint(len(sizes))]
                x = rng.rand(n, 784).astype(np.float32)
                t0 = time.perf_counter()
                try:
                    resp = router.predict(make_predict_request(x))
                    ok = resp.code == spb.SERVING_OK
                except Exception:
                    ok = False
                dt = time.perf_counter() - t0
                if ok:
                    mine.append(dt)
                else:
                    with lock:
                        failed.append(seed)
            with lock:
                latencies.extend(mine)

        threads = [
            threading.Thread(target=run_client, args=(i,))
            for i in range(clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        # mid-run chaos, while the clients hammer the router: kill one
        # replica (transport AND pod), let a tick replace it, then land
        # a newer checkpoint and roll it one replica per tick
        time.sleep(0.1)
        fleet[1]["client"].killed = True
        k8s.emit(manager.snapshot()["replicas"][1]["pod"],
                 PodStatus.FAILED, exit_code=1)
        time.sleep(0.05)  # a probe-interval of traffic hits the dead pod
        manager.tick()  # sees the FAILED pod -> relaunch
        observe_slo()
        time.sleep(0.05)
        save_step(2, 1.5)
        latest[0] = 2
        for _ in range(replicas + 1):
            manager.tick()  # one sequenced hot-swap per tick
            observe_slo()
            time.sleep(0.03)
        for t in threads:
            t.join()
        observe_slo()
        elapsed = time.perf_counter() - t0
        staleness = freshness.quantiles()

        snap = manager.snapshot()
        stats = router.stats()
        events_lib.remove_observer(collect_span)

        # Tracing-overhead calibration over the same warm fleet: calm
        # sequential traffic through a fresh router at full sampling
        # (with a span tap attached, the worst case) vs sampling off.
        def mean_latency_s(rate, n=80):
            probe = FleetRouter(
                clients={
                    rid: rep["client"] for rid, rep in fleet.items()
                },
                retry_policy=RetryPolicy(
                    initial_backoff_s=0.001, max_backoff_s=0.01,
                    max_elapsed_s=30.0, max_attempts=8,
                ),
                trace_sample_rate=rate,
            )
            x = np.random.RandomState(7).rand(4, 784).astype(np.float32)
            t0 = time.perf_counter()
            for _ in range(n):
                probe.predict(make_predict_request(x))
            return (time.perf_counter() - t0) / n

        def span_sink(record):
            pass

        events_lib.add_observer(span_sink)
        traced_s = mean_latency_s(1.0)
        events_lib.remove_observer(span_sink)
        untraced_s = mean_latency_s(0.0)
        trace_overhead_pct = (
            (traced_s - untraced_s) / untraced_s * 100.0
            if untraced_s > 0 else 0.0
        )

        for rep in fleet.values():
            rep["batcher"].shutdown()
        saver.close()
    lat_s = np.array(latencies) if latencies else np.array([0.0])
    return {
        "bench": "serving_fleet",
        "value": round(len(latencies) / elapsed, 1),
        "unit": "requests_per_sec",
        "detail": {
            "model": model_def,
            "replicas": replicas,
            "clients": clients,
            "requests": clients * requests_per_client,
            "p50_ms": round(float(np.percentile(lat_s, 50)) * 1e3, 3),
            "p99_ms": round(float(np.percentile(lat_s, 99)) * 1e3, 3),
            "failed_requests": len(failed),
            "failovers": stats["failovers"],
            "relaunches": snap["relaunches"],
            "reload_steps": snap["reload_steps"],
            "max_model_step_skew": max(
                snap["max_model_step_skew"],
                router.max_observed_step_skew,
            ),
            "step_skew_slo": snap["step_skew_slo"],
            "staleness_p50_steps": staleness["staleness_p50_steps"],
            "staleness_p99_steps": staleness["staleness_p99_steps"],
            "staleness_p50_s": staleness["staleness_p50_s"],
            "staleness_p99_s": staleness["staleness_p99_s"],
            "max_burn_rate": round(max_burn[0], 3),
            "phase_latency_ms": {
                phase: {
                    "p50": round(
                        float(np.percentile(vals, 50)) * 1e3, 3
                    ),
                    "p99": round(
                        float(np.percentile(vals, 99)) * 1e3, 3
                    ),
                }
                for phase, vals in sorted(phase_values.items())
            },
            "trace_overhead_pct": round(trace_overhead_pct, 2),
        },
    }


def _lineage_reconciliation(records):
    """Reconcile the per-window phase decompositions against the
    measured ingest->first-serve times (docs/OBSERVABILITY.md "Window
    lineage"): over completed, non-dropped windows, the p99 of
    sum(phases) must sit within 5% of the p99 of the measured e2e —
    the contract that the decomposition accounts for ALL the staleness,
    not an approximation of it."""
    done = [
        r for r in records
        if r.get("complete") and not r.get("dropped")
    ]
    if not done:
        return {
            "windows": 0, "phase_sum_p99_s": 0.0, "e2e_p99_s": 0.0,
            "delta_pct": 0.0, "within_5pct": True,
            "max_abs_delta_s": 0.0,
        }
    sums = np.array([sum(r["phases"].values()) for r in done])
    e2e = np.array([r["e2e_s"] for r in done])
    p99_sum = float(np.percentile(sums, 99))
    p99_e2e = float(np.percentile(e2e, 99))
    delta_pct = (
        abs(p99_sum - p99_e2e) / p99_e2e * 100.0 if p99_e2e else 0.0
    )
    return {
        "windows": len(done),
        "phase_sum_p99_s": round(p99_sum, 6),
        "e2e_p99_s": round(p99_e2e, 6),
        "delta_pct": round(delta_pct, 3),
        "within_5pct": delta_pct <= 5.0,
        "max_abs_delta_s": round(
            float(np.max(np.abs(sums - e2e))), 6
        ),
    }


def _online_chaos_run(seed: int):
    """One seeded chaos pass of the online loop under a FAKE clock and a
    strictly sequential driver: a stream stall (`stream.poll`), a lost
    window re-arm (`task.rearm`), a rejected hot-reload
    (`serving.reload`), a deferred shard move (`store.shard_handoff`),
    a mid-run replica kill, TWO trainer-worker kills (the second retries
    the deferred shard move), and a master restart landed while a window
    is mid-flight WITH its reader buffers wiped — the survivors must
    replay those windows from the deterministic source, and the lineage
    must keep their ORIGINAL ingest attribution.  Returns
    (canonical_text, summary): the text concatenates the fault trace,
    the fleet manager's and SLO evaluator's clock-free decision lists,
    the normalized span-event stream (window_span lineage stamps
    included), and the completed window-lineage decompositions —
    byte-identical across same-seed runs (the acceptance bar of
    docs/ONLINE.md).  The exactly-once claim is checked in summary:
    zero lost windows, zero duplicate shard reports; the lineage claim
    too: phase sums reconcile with measured e2e within 5%, replayed
    windows keep pre-restart ingest stamps."""
    import tempfile

    from elasticdl_tpu.common import events as events_lib
    from elasticdl_tpu.common import faults
    from elasticdl_tpu.common.faults import FaultRegistry, FaultSpec
    from elasticdl_tpu.common.model_handler import get_model_spec
    from elasticdl_tpu.online import OnlineConfig, OnlinePipeline
    from elasticdl_tpu.proto import serving_pb2 as spb
    from elasticdl_tpu.serving.server import make_predict_request
    from model_zoo.clickstream import ctr_mlp

    clk = [1_000_000.0]

    def clock():
        clk[0] += 0.125
        return clk[0]

    # Explicit (still seed-stamped) schedule: every fault is one the
    # driver is guaranteed to reach, so `all_fired()` holds and the
    # trace compares byte-for-byte (the chaos-soak discipline).
    registry = faults.install(FaultRegistry(
        schedule=[
            FaultSpec(faults.POINT_STREAM_POLL, 2, "raise"),
            FaultSpec(faults.POINT_TASK_REARM, 3, "raise"),
            FaultSpec(faults.POINT_SERVING_RELOAD, 2, "raise"),
            # first handoff attempt (trainer 2's shard) defers; the
            # second kill's evacuation retries and completes it
            FaultSpec(faults.POINT_STORE_SHARD_HANDOFF, 1, "raise"),
        ],
        seed=seed,
    ))
    keep = ("window", "tasks", "records", "step",
            "shard", "from_worker", "to_worker",
            "window_id", "phase", "reason", "at_unix_s", "ingest_unix_s")
    norm_events = []

    def observe(record):
        norm_events.append({
            "event": record.get("event"),
            **{k: record[k] for k in keep if k in record},
        })

    events_lib.add_observer(observe)
    rng = np.random.RandomState(seed)
    failed = 0
    restart_at = None
    try:
        spec = get_model_spec(_ZOO, "clickstream.ctr_mlp.custom_model")
        with tempfile.TemporaryDirectory() as tmp:
            pipe = OnlinePipeline(
                tmp, spec,
                OnlineConfig(
                    seed=seed, window_records=64, records_per_poll=64,
                    records_per_task=16, checkpoint_every_windows=2,
                    replicas=2, workers=3, num_shards=4,
                ),
                clock=clock,
            )
            for i in range(12):
                if i == 7:
                    # leave the tick's window mid-flight (1 of its 4
                    # shards trained), wipe the reader's buffers (full
                    # master-process amnesia), then kill the master
                    # brain: the replacement must re-arm exactly the 3
                    # undone shards from the journal AND replay the
                    # wiped windows from the deterministic source —
                    # their lineage must keep the original ingest stamp
                    pipe.tick(max_train_tasks=1)
                    wiped = pipe.drop_window_buffers()
                    restart_at = clk[0]
                    restored = pipe.restart_master()
                    faults.note(
                        "master.restart",
                        "windows=%d tasks=%d buffers_wiped=%d" % (
                            restored["windows_restored"],
                            restored["tasks_rearmed"],
                            wiped,
                        ),
                    )
                else:
                    pipe.tick()
                if i == 3:
                    pipe.kill_replica(1)
                    faults.note("replica.kill", "replica=1")
                if i == 4:
                    info = pipe.kill_worker(2)
                    faults.note(
                        "trainer.kill",
                        "worker=2 handoffs=%d" % info["handoffs"],
                    )
                if i == 9:
                    info = pipe.kill_worker(1)
                    faults.note(
                        "trainer.kill",
                        "worker=1 handoffs=%d" % info["handoffs"],
                    )
                for _ in range(2):
                    x = ctr_mlp.encode(
                        rng.randint(0, 512, 2), rng.randint(0, 128, 2)
                    )
                    try:
                        resp = pipe.predict(make_predict_request(x))
                        if resp.code != spb.SERVING_OK:
                            failed += 1
                    except Exception:
                        failed += 1
            # drain the restart's re-armed remainder before snapshotting
            pipe.tick()
            snap = pipe.snapshot()
            lineage_records = pipe.lineage.records()
            # open windows too: a replayed window still blocked in
            # reload_wait must already carry its original ingest stamp
            all_lineage = lineage_records + pipe.lineage.open_decompositions()
            pipe.shutdown()
    finally:
        events_lib.remove_observer(observe)
        faults.uninstall()

    canonical = json.dumps({
        "fault_trace": registry.trace_text(),
        "fleet_decisions": snap["serving_fleet"]["decisions"],
        "slo_decisions": snap["slo"]["decisions"],
        "events": norm_events,
        "lineage": lineage_records,
    }, sort_keys=True)
    summary = {
        "all_faults_fired": registry.all_fired(),
        "failed_requests": failed,
        "rearm_faults": snap["online"]["rearm_faults"],
        "poll_faults": snap["stream"]["poll_faults"],
        "last_reload_step": snap["online"]["last_reload_step"],
        "windows_trained": snap["windows_trained"],
        "handoffs": snap["online"]["handoffs"],
        "pending_handoffs": snap["online"]["pending_handoffs"],
        "handoff_faults": snap["store"]["handoff_faults"],
        "windows_released": snap["online"]["windows_released"],
        "windows_lost": snap["online"]["windows_lost"],
        "duplicate_reports": snap["online"]["duplicate_reports"],
        "master_restarts": snap["online"]["master_restarts"],
        "alive_trainers": snap["online"]["alive_trainers"],
        "replayed_windows": snap["stream"]["replayed_windows"],
        # ---- window lineage (docs/OBSERVABILITY.md "Window lineage") --
        "lineage_windows": snap["lineage"]["windows_traced"],
        "lineage_replayed": sum(
            1 for r in all_lineage if r.get("replayed")
        ),
        "lineage_dominant_phase": snap["lineage"]["dominant_phase"],
        "lineage_reconcile": _lineage_reconciliation(lineage_records),
        # replayed windows must keep their PRE-restart ingest stamp —
        # replay re-buffers records, it never re-bases attribution
        "replayed_original_ingest": (
            restart_at is not None
            and any(r.get("replayed") for r in all_lineage)
            and all(
                r.get("ingest_unix_s") is not None
                and float(r["ingest_unix_s"]) < restart_at
                for r in all_lineage if r.get("replayed")
            )
        ),
    }
    return canonical, summary


def bench_online(
    windows: int = 8,
    load_clients: int = 2,
    chaos_seed: int = 20260805,
):
    """Online loop bench (`python bench.py --online`): the whole
    continuous-learning pipeline — unbounded stream -> perpetual task
    queue -> train -> checkpoint -> rolling hot-reload — sustained for
    `windows` stream windows UNDER CONCURRENT PREDICT LOAD, then a
    seeded chaos determinism check (docs/ONLINE.md).  Reports sustained
    train examples/s (the headline), served QPS and client-observed p99
    while the model keeps swapping underneath, train-to-serve staleness
    p50/p99 in steps AND seconds (real produced->served lag on a real
    clock), the max staleness-SLO burn rate, the number of
    checkpoint->hot-reload cycles completed behind live traffic (must
    be >= 2), and the failed-request count (must be 0).  The chaos
    variant runs twice with the same seed under a fake clock — stream
    stall + window re-arm loss + rejected reload + replica kill + two
    trainer kills (shard handoff, one move fault-deferred then retried)
    + a mid-flight master restart — and asserts the fault trace / fleet
    decisions / SLO decisions / event stream compare byte-identical,
    with zero lost windows and zero duplicate shard reports."""
    import tempfile
    import threading
    import time

    from elasticdl_tpu.common.model_handler import get_model_spec
    from elasticdl_tpu.online import OnlineConfig, OnlinePipeline
    from elasticdl_tpu.proto import serving_pb2 as spb
    from elasticdl_tpu.serving.server import make_predict_request
    from model_zoo.clickstream import ctr_mlp

    spec = get_model_spec(_ZOO, "clickstream.ctr_mlp.custom_model")
    cfg = OnlineConfig(
        window_records=64, records_per_poll=64, records_per_task=16,
        checkpoint_every_windows=2, replicas=2,
    )
    with tempfile.TemporaryDirectory() as tmp:
        pipe = OnlinePipeline(tmp, spec, cfg)
        stop = threading.Event()
        latencies, failed = [], []
        lock = threading.Lock()

        def run_load(seed):
            rng = np.random.RandomState(seed)
            mine = []
            while not stop.is_set():
                n = (1, 2, 4)[rng.randint(3)]
                x = ctr_mlp.encode(
                    rng.randint(0, cfg.source_users, n),
                    rng.randint(0, cfg.source_items, n),
                )
                t0 = time.perf_counter()
                try:
                    resp = pipe.predict(make_predict_request(x))
                    ok = resp.code == spb.SERVING_OK
                except Exception:
                    ok = False
                dt = time.perf_counter() - t0
                if ok:
                    mine.append(dt)
                else:
                    with lock:
                        failed.append(seed)
            with lock:
                latencies.extend(mine)

        threads = [
            threading.Thread(target=run_load, args=(i,))
            for i in range(load_clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        ticks = 0
        while pipe._windows_trained < windows and ticks < windows * 4:
            pipe.tick()
            ticks += 1
        stop.set()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        staleness = pipe.freshness.quantiles()
        snap = pipe.snapshot()
        lineage_records = pipe.lineage.records()
        pipe.shutdown()

    trace_a, summary_a = _online_chaos_run(chaos_seed)
    trace_b, summary_b = _online_chaos_run(chaos_seed)

    lat_s = np.array(latencies) if latencies else np.array([0.0])
    fleet = snap["serving_fleet"]
    train_eps = snap["examples_trained"] / elapsed
    return {
        "bench": "online",
        "value": round(train_eps, 1),
        "unit": "train_examples_per_sec",
        "detail": {
            "model": "clickstream.ctr_mlp.custom_model",
            "windows_trained": snap["windows_trained"],
            "ticks": ticks,
            "elapsed_s": round(elapsed, 3),
            "train_examples_per_sec": round(train_eps, 1),
            "served_qps": round(len(latencies) / elapsed, 1),
            "requests": len(latencies) + len(failed),
            "p50_ms": round(float(np.percentile(lat_s, 50)) * 1e3, 3),
            "p99_ms": round(float(np.percentile(lat_s, 99)) * 1e3, 3),
            "failed_requests": len(failed),
            # distinct checkpoint steps the fleet rolled onto replicas
            # behind live traffic — the >= 2 cycles acceptance bar
            "reload_cycles": len({
                d["target_step"] for d in fleet["decisions"]
                if d.get("action") == "reload_step"
            }),
            "replica_hot_swaps": fleet["reload_steps"],
            "last_reload_step": snap["online"]["last_reload_step"],
            "staleness_p50_steps": staleness["staleness_p50_steps"],
            "staleness_p99_steps": staleness["staleness_p99_steps"],
            "staleness_p50_s": staleness["staleness_p50_s"],
            "staleness_p99_s": staleness["staleness_p99_s"],
            "max_burn_rate": round(snap["max_burn"], 3),
            "watermark_lag_s": snap["stream"]["watermark_lag_s"],
            "dropped_windows": snap["stream"]["dropped_windows"],
            # per-window staleness decomposition: where the traced
            # windows' ingest->first-serve time went, and the proof the
            # phases account for the whole measured e2e
            "lineage": {
                "windows_traced": snap["lineage"]["windows_traced"],
                "e2e_p99_s": snap["lineage"]["e2e_p99_s"],
                "dominant_phase": snap["lineage"]["dominant_phase"],
                "phase_p99_s": snap["lineage"]["phase_p99_s"],
                "reconcile": _lineage_reconciliation(lineage_records),
            },
            "chaos": {
                "seed": chaos_seed,
                "deterministic": trace_a == trace_b,
                **summary_a,
                "failed_requests_run_b":
                    summary_b["failed_requests"],
            },
        },
    }


def _traffic_spike_run(seed: int, ticks: int = 44,
                       capacity_per_tick: int = 12):
    """One seeded pass of the serving control loop under a FAKE clock:
    the replayable traffic generator offers a 5x spike at an autoscaling
    fleet whose replicas each serve `capacity_per_tick` requests per
    generator tick (the capacity gate models a replica's finite
    throughput — the real in-process engine answers everything a
    sequential driver offers, so overload has to be declared, not
    discovered).  Returns (canonical_text, summary): the text is the
    offered schedule + serving-scale decision list + fleet-size trace +
    normalized scale/SLO events, byte-identical across same-seed runs.

    The loop under test (docs/SERVING.md "Autoscaling & backpressure"):
    spike -> whole-fleet sheds -> predict_shed_ratio SLO burns -> the
    flight recorder captures an incident bundle at the breach -> the
    serving policy engine scales up within its hysteresis window ->
    serving_pressure slows the pipeline's poll/arm cadence -> spike
    passes, evidence ages out of the shed window -> the fleet scales
    back to min."""
    import tempfile

    from elasticdl_tpu.common import events as events_lib
    from elasticdl_tpu.common.flight import FlightRecorder
    from elasticdl_tpu.common.model_handler import get_model_spec
    from elasticdl_tpu.online import OnlineConfig, OnlinePipeline
    from elasticdl_tpu.proto import serving_pb2 as spb
    from elasticdl_tpu.traffic import (
        TrafficConfig,
        TrafficGenerator,
        router_request_fn,
    )
    from model_zoo.clickstream import ctr_mlp

    clk = [2_000_000.0]

    def clock():
        clk[0] += 0.125
        return clk[0]

    class _CapacityGate:
        """Per-tick admission control in front of a real replica: the
        first `capacity_per_tick` requests pass through, the rest shed
        with SERVING_OVERLOADED — exactly the response a saturated
        batcher queue sends."""

        def __init__(self, inner):
            self._inner = inner
            self.used = 0

        def reset(self):
            self.used = 0

        def predict(self, request, timeout=None):
            if self.used >= capacity_per_tick:
                response = spb.PredictResponse()
                response.code = spb.SERVING_OVERLOADED
                response.error = "per-tick capacity exhausted"
                return response
            self.used += 1
            return self._inner.predict(request, timeout=timeout)

        def health(self, request, timeout=None):
            return self._inner.health(request, timeout=timeout)

    gates = {}

    def client_wrapper(rid, inner):
        gates[rid] = _CapacityGate(inner)
        return gates[rid]

    # Clock-free projection of the decision-bearing events: enough to
    # pin the control loop's story, nothing that varies run to run.
    keep = ("action", "reason", "tick", "requested", "replicas",
            "slo", "state")
    watched = (
        events_lib.SERVING_SCALE, events_lib.SLO_BREACH,
        events_lib.SLO_RECOVERED, events_lib.INCIDENT_CAPTURED,
    )
    norm_events = []

    def observe(record):
        if record.get("event") in watched:
            norm_events.append({
                "event": record["event"],
                **{k: record[k] for k in keep if k in record},
            })

    events_lib.add_observer(observe)
    try:
        spec = get_model_spec(_ZOO, "clickstream.ctr_mlp.custom_model")
        with tempfile.TemporaryDirectory() as tmp:
            incident_dir = os.path.join(tmp, "incidents")
            pipe = OnlinePipeline(
                tmp, spec,
                OnlineConfig(
                    seed=seed, window_records=64, records_per_poll=64,
                    records_per_task=16, checkpoint_every_windows=2,
                    replicas=1, max_serving_replicas=4,
                    serving_up_ticks=2, serving_down_ticks=3,
                    serving_scale_hold_ticks=2,
                    serving_shed_window_s=30.0,
                    backpressure_threshold=0.25,
                    backpressure_stride=4,
                ),
                clock=clock,
                client_wrapper=client_wrapper,
            )
            recorder = FlightRecorder(
                incident_dir=incident_dir,
                snapshot_fn=pipe.snapshot,
                history=pipe.history,
            ).install()
            pipe.evaluator.set_on_breach(recorder.breach)

            def encode_fn(rows, payload_seed):
                rng = np.random.RandomState(payload_seed % (2 ** 31))
                return ctr_mlp.encode(
                    rng.randint(0, 512, rows), rng.randint(0, 128, rows)
                )

            gen = TrafficGenerator(
                router_request_fn(pipe.router, encode_fn),
                TrafficConfig(
                    profile="spike", base_qps=8.0, clients=4, seed=seed,
                    tick_interval_s=1.0, spike_at_tick=8, spike_ticks=4,
                    spike_factor=5.0,
                ),
            )
            fleet_sizes, pressures = [], []
            try:
                for _ in range(ticks):
                    for gate in gates.values():
                        gate.reset()
                    gen.tick()
                    pipe.tick()
                    fleet_sizes.append(pipe.fleet_manager.live_replicas())
                    pressures.append(pipe._serving_pressure)
                snap = pipe.snapshot()
                traffic = gen.snapshot()
                recorder.flush()
                bundles = (
                    sorted(os.listdir(incident_dir))
                    if os.path.isdir(incident_dir) else []
                )
            finally:
                recorder.close()
                pipe.shutdown()
    finally:
        events_lib.remove_observer(observe)

    policy = snap["serving_policy"]
    canonical = json.dumps({
        "schedule": traffic["schedule"],
        "decisions": policy["decisions"],
        "fleet_sizes": fleet_sizes,
        "events": norm_events,
        "bundles": bundles,
    }, sort_keys=True)
    summary = {
        "offered": traffic["offered"],
        "offered_qps": traffic["offered_qps"],
        "ok": traffic["ok"],
        "shed": traffic["shed"],
        "failed_requests": traffic["failed"],
        "shed_ratio": traffic["shed_ratio"],
        "min_fleet": 1,
        "peak_fleet": max(fleet_sizes),
        "final_fleet": fleet_sizes[-1],
        "scale_ups": snap["serving_fleet"]["scale_ups"],
        "scale_downs": snap["serving_fleet"]["scale_downs"],
        "decisions": len(policy["decisions"]),
        "polls_skipped": snap["backpressure"]["polls_skipped"],
        "peak_pressure": round(max(pressures), 4),
        "incident_bundles": bundles,
        "max_burn_rate": round(snap["max_burn"], 3),
    }
    return canonical, summary


def bench_traffic(seed: int = 20260807):
    """Serving control-loop bench (`python bench.py --traffic`): the
    seeded 5x spike scenario, run twice to pin byte-stability.  The
    headline value is the offered spike load absorbed without a single
    failed request while the fleet autoscales."""
    trace_a, summary_a = _traffic_spike_run(seed)
    trace_b, summary_b = _traffic_spike_run(seed)
    return {
        "bench": "traffic",
        "value": summary_a["offered_qps"],
        "unit": "offered_qps",
        "detail": {
            "seed": seed,
            "deterministic": trace_a == trace_b,
            "spike_absorbed": summary_a["failed_requests"] == 0,
            "scaled_up": summary_a["peak_fleet"] > summary_a["min_fleet"],
            "returned_to_min":
                summary_a["final_fleet"] == summary_a["min_fleet"],
            "incident_captured":
                len(summary_a["incident_bundles"]) > 0,
            "backpressure_engaged": summary_a["polls_skipped"] > 0,
            **summary_a,
        },
    }


def bench_sparse_path(batch_size: int = 65536):
    """Sparse-path economics (`python bench.py --sparse-path`):

    - wire bytes/example for the three device wire formats on the zipf
      criteo batch (plain / compact b22 / dedup'd) and the dedup ratio;
    - host pack throughput for the dedup packer (it runs on the reader
      thread, so it must stay far above the link-bound example rate);
    - device unpack bit-exactness (unpack_rows_dedup == the host rows it
      packed — the format is lossless by construction, this proves it);
    - gather/scatter kernel counts from compiled HLO: N separate
      embedding tables vs the fused arena (layers/arena.py).  The
      arena's one-gather/one-scatter regardless of feature count is the
      fused-sparse-path claim, counted in the artifact XLA actually runs.
    """
    import time as _time

    import flax.linen as nn
    import jax

    from elasticdl_tpu.data.wire import (
        DedupPacker,
        pack_f32_to_bf16,
        pack_int_to_b22,
        unpack_rows_dedup,
    )
    from elasticdl_tpu.layers.arena import EmbeddingArena
    from elasticdl_tpu.layers.embedding import DistributedEmbedding
    from model_zoo.deepfm import deepfm_functional_api as zoo

    vocab_capacity = 1 << 20
    batch = _make_criteo_batch(batch_size)
    dense = batch["features"]["dense"]
    sparse = batch["features"]["sparse"]

    def nbytes(tree):
        return sum(np.asarray(x).nbytes for x in jax.tree.leaves(tree))

    plain = nbytes(
        {"dense": dense, "sparse": sparse, "labels": batch["labels"]}
    )
    compact = nbytes({
        "dense": pack_f32_to_bf16(dense),
        "sparse": pack_int_to_b22(sparse),
        "labels": batch["labels"].astype(np.uint8),
    })

    rows = zoo.hash_field_rows_host(sparse, vocab_capacity)
    packer = DedupPacker()
    packed = packer.pack(rows)
    # steady state (sticky caps already set): time re-packs
    reps = 3
    t0 = _time.perf_counter()
    for _ in range(reps):
        packed = packer.pack(rows)
    pack_sec = (_time.perf_counter() - t0) / reps
    dedup = nbytes({
        "dense": pack_f32_to_bf16(dense),
        "sparse": packed,
        "labels": batch["labels"].astype(np.uint8),
    })

    unpacked = np.asarray(unpack_rows_dedup(packed))
    detail = {
        "batch_size": batch_size,
        "wire_bytes_per_example": {
            "plain": round(plain / batch_size, 1),
            "compact_b22": round(compact / batch_size, 1),
            "dedup": round(dedup / batch_size, 1),
        },
        "dedup_vs_compact": round(dedup / compact, 3),
        "dedup_reduction_vs_compact": round(1 - dedup / compact, 3),
        "pack_examples_per_sec": round(batch_size / pack_sec, 1),
        "pack_us_per_example": round(pack_sec / batch_size * 1e6, 3),
        "device_unpack_bit_exact": bool((unpacked == rows).all()),
    }

    # Kernel-count evidence: same logical lookup (8 features, 4096 rows
    # each, dim 8) as N separate tables vs one fused arena, compiled
    # forward+backward.
    n_feat, cap, dim = 8, 4096, 8
    feats = tuple((f"f{i}", cap) for i in range(n_feat))
    toy_ids = np.random.RandomState(1).randint(
        0, 1 << 20, size=(1024, n_feat)
    ).astype(np.int32)

    class _ArenaToy(nn.Module):
        @nn.compact
        def __call__(self, ids):
            vecs = EmbeddingArena(feats, dim, name="arena")(
                {f"f{i}": ids[:, i] for i in range(n_feat)}
            )
            return sum(v.sum() for v in vecs.values())

    class _PerFeatureToy(nn.Module):
        @nn.compact
        def __call__(self, ids):
            total = 0.0
            for i in range(n_feat):
                total = total + DistributedEmbedding(
                    cap, dim, hash_input=True, name=f"emb_{i}"
                )(ids[:, i]).sum()
            return total

    class _ArenaToyQ(nn.Module):
        @nn.compact
        def __call__(self, ids):
            vecs = EmbeddingArena(
                feats, dim, name="arena", arena_dtype="int8"
            )({f"f{i}": ids[:, i] for i in range(n_feat)})
            return sum(v.sum() for v in vecs.values())

    def kernel_counts(model):
        import re

        variables = model.init(jax.random.PRNGKey(0), toy_ids)
        params = {"params": variables["params"]}
        # non-params collections (the int8 code/scale planes) ride as
        # constants: they are integer storage, not differentiable leaves
        rest = {k: v for k, v in variables.items() if k != "params"}

        def step(p, ids):
            return jax.value_and_grad(
                lambda q: model.apply({**q, **rest}, ids)
            )(p)

        # count in the lowered StableHLO (what XLA receives): the CPU
        # backend expands scatters into while loops post-optimization,
        # so the compiled text under-counts off-TPU
        text = jax.jit(step).lower(params, toy_ids).as_text()
        return {
            "gather": len(re.findall(r'= "stablehlo\.gather"', text)),
            "scatter": len(re.findall(r'= "stablehlo\.scatter"', text)),
        }

    detail["kernel_counts"] = {
        "features": n_feat,
        "per_feature_tables": kernel_counts(_PerFeatureToy()),
        "fused_arena": kernel_counts(_ArenaToy()),
        # int8 storage keeps the fused shape: one code gather + one
        # scale gather + one scatter-add, independent of feature count
        "fused_arena_int8": kernel_counts(_ArenaToyQ()),
    }

    # Quantized-vs-fp32 economics (ISSUE 9): the headline DeepFM config
    # in both arena storage modes — examples/s, XLA cost-model bytes,
    # the analytic arena-plane bytes, and the AUC delta from the short
    # convergence run.  int8 shrinks the gather plane ~4x (1-byte codes
    # + a per-row fp32 scale vs 4-byte rows) while gradients and the
    # optimizer stay fp32 — see docs/PERF.md "Quantized arena".
    from elasticdl_tpu.parallel import mesh as mesh_lib

    qb = min(batch_size, 16384)
    qbatch = _make_criteo_batch(qb)
    modes = {}
    for dtype in ("float32", "int8"):
        _, trainer = _trainer_for(
            "deepfm.deepfm_functional_api.custom_model",
            model_params=(
                "vocab_capacity=1048576;embed_dim=16;bf16=True;"
                f"arena_dtype='{dtype}'"
            ),
            use_bf16=True,
        )
        state = trainer.init_state(
            jax.random.PRNGKey(0), qbatch["features"]
        )
        sps = sorted(
            trainer.timed_steps_per_sec_fused(state, qbatch, iters=8)
            for _ in range(3)
        )[1]
        sharded = mesh_lib.shard_batch(qbatch, trainer.mesh)
        cost = trainer.train_step.cost_for(state, sharded)
        modes[dtype] = {
            "examples_per_sec": round(sps * qb, 1),
            "step_bytes_accessed_xla_costmodel": float(
                cost.get("bytes accessed", 0.0)
            ),
            "arena_bytes_per_step": _arena_bytes_per_step(
                qb, 1 << 20, 16, dtype
            ),
            "auc_synthetic_criteo": round(
                _deepfm_auc(arena_dtype=dtype), 4
            ),
        }
    f32, i8 = modes["float32"], modes["int8"]
    detail["quantized_vs_fp32"] = {
        "batch_size": qb,
        **modes,
        "examples_per_sec_speedup_int8": round(
            i8["examples_per_sec"] / max(f32["examples_per_sec"], 1e-9), 3
        ),
        "bytes_accessed_reduction_xla": round(
            1
            - i8["step_bytes_accessed_xla_costmodel"]
            / max(f32["step_bytes_accessed_xla_costmodel"], 1e-9),
            3,
        ),
        # The memory-wall figure: the random-access gather plane (the
        # whole arena story for serving; the fold/scatter streams are
        # sequential and mode-invariant-or-cheap — see
        # _arena_bytes_per_step)
        "arena_gather_bytes_reduction": round(
            1
            - i8["arena_bytes_per_step"]["gather"]
            / f32["arena_bytes_per_step"]["gather"],
            3,
        ),
        "arena_total_bytes_reduction": round(
            1
            - i8["arena_bytes_per_step"]["total"]
            / f32["arena_bytes_per_step"]["total"],
            3,
        ),
        "auc_delta_int8_minus_fp32": round(
            i8["auc_synthetic_criteo"] - f32["auc_synthetic_criteo"], 4
        ),
    }
    return {
        "bench": "sparse_path",
        "value": detail["wire_bytes_per_example"]["dedup"],
        "unit": "bytes_per_example",
        "detail": detail,
    }


def bench_tiered(
    parity_steps: int = 8,
    parity_batch: int = 128,
    throughput_steps: int = 24,
    throughput_batch: int = 128,
    cache_dtype: str = "float32",
    fused_k: int = 8,
):
    """Tiered embedding store bench (`python bench.py --tiered`, or
    `--tiered --cache_dtype int8` for the quantized device cache;
    docs/PERF.md "Tiered embedding store").  Six sub-benches:

    1. EXACT parity vs the flat arena on an all-hot working set: the
       host tier is backfilled from the flat model's init table over a
       collision-free id subset, so every admitted cache row starts at
       the flat value and the two training runs must stay bitwise
       identical (losses, predictions, and the trained rows themselves).
    2. Cache efficacy on the canonical zipfian stream (the same config
       `scripts/store_summary.py` prints in CI): hit rate + lazy growth.
    3. A beyond-budget config the flat arena cannot run: under a
       declared device-embedding byte budget, the flat table's
       params+Adam-moments footprint exceeds the budget while the tiered
       run holds only the fixed cache on device and grows the full
       vocabulary in host RAM — and the vocabulary it actually grows
       exceeds the largest flat table the budget could hold.
    4. Equal-vocab throughput, flat vs tiered, on the zipfian stream —
       plus the cold-gather overlap share (fraction of host-gather
       seconds absorbed by the prefetcher thread instead of the
       consumer's critical path).
    5. Analytic device-cache bytes (ISSUE 18a): fp32 vs int8 VALUE
       bytes at capacity and per step, aggregate and per plane — the
       carrier + Adam moments are identical in both modes and the
       forward never reads the carrier's bytes (XLA folds the
       exact-zero add), so they cancel; the headline reduction is the
       quantized embedding plane's (the byte-dominant one), with the
       aggregate (diluted by the dim-1 linear plane's fixed scale
       overhead) reported alongside.
    6. K-step fused-block parity (ISSUE 18c, `fused_k` steps via ONE
       `train_on_batch_stack` scan with a union admission block) vs
       the flat arena driven through the SAME K-step scan — the
       bitwise train-path contract of sub-bench 1 extended to
       steps_per_execution > 1.

    `cache_dtype="int8"` runs 1/4/6 with the quantized device cache:
    the bitwise-vs-flat contract only holds for fp32 (int8 admissions
    quantize the backfilled values), so parity fields are reported but
    gated only when `parity_gated` says so.
    """
    import time as _time

    import jax

    from elasticdl_tpu.layers.embedding import hash_ids_host
    from elasticdl_tpu.store.tiered import TieredStore
    from model_zoo.deepfm.deepfm_functional_api import NUM_SPARSE
    from scripts.store_summary import zipfian_batches, zipfian_summary

    detail = {}

    def hash_rows(fields, ids, cap):
        # host replica of field_offset_ids + hash_ids(mix=True) for
        # arbitrary (field, id) pairs (hash_field_rows_host wants the
        # full (B, 26) matrix)
        with np.errstate(over="ignore"):
            fid = (
                np.asarray(ids).astype(np.uint32)
                + np.asarray(fields).astype(np.uint32)
                * np.uint32(0x61C88647)
            )
        return hash_ids_host(fid, cap, mix=True)

    # ---- 1. exact parity on an all-hot working set ---------------------
    cap, dim, cache_rows, ids_per_field = 1 << 14, 8, 2048, 40
    rng = np.random.RandomState(7)
    cand = rng.randint(0, 1 << 22, size=(NUM_SPARSE, ids_per_field * 8))
    cand_rows = hash_rows(
        np.repeat(np.arange(NUM_SPARSE)[:, None], cand.shape[1], 1),
        cand, cap,
    )
    # collision-free subset: every (field, id) pair must own its flat
    # row alone, else flat trains two ids in one row while the tiered
    # store trains them apart and parity is (correctly) impossible
    seen = set()
    sel = np.zeros((NUM_SPARSE, ids_per_field), np.int32)
    for f in range(NUM_SPARSE):
        picked = 0
        for j in range(cand.shape[1]):
            row = int(cand_rows[f, j])
            if row not in seen:
                seen.add(row)
                sel[f, picked] = cand[f, j]
                picked += 1
                if picked == ids_per_field:
                    break
        assert picked == ids_per_field, "hash space too small for subset"

    def parity_batch_at(step):
        brng = np.random.RandomState(1000 + step)
        pick = brng.randint(0, ids_per_field, (parity_batch, NUM_SPARSE))
        return {
            "features": {
                "dense": brng.rand(parity_batch, 13).astype(np.float32),
                "sparse": sel[np.arange(NUM_SPARSE)[None, :], pick],
            },
            "labels": brng.randint(0, 2, parity_batch).astype(np.int32),
        }

    _, flat_tr = _trainer_for(
        "deepfm.deepfm_functional_api.custom_model",
        model_params=f"vocab_capacity={cap};embed_dim={dim}",
    )
    _, tier_tr = _trainer_for(
        "deepfm.deepfm_tiered.custom_model",
        model_params=(f"cache_rows={cache_rows};embed_dim={dim};"
                      f"cache_dtype='{cache_dtype}'"),
    )
    b0 = parity_batch_at(0)
    flat_state = flat_tr.init_state(jax.random.PRNGKey(0), b0["features"])
    tier_state = tier_tr.init_state(
        jax.random.PRNGKey(0),
        {
            "dense": b0["features"]["dense"],
            "slots": np.zeros((parity_batch, NUM_SPARSE), np.int32),
        },
    )
    flat_init = {
        name: np.array(
            flat_state.params["params"][name]["embedding"], np.float32
        )
        for name in ("fm_embedding", "fm_linear")
    }
    store = TieredStore(
        {"fm_embedding": dim, "fm_linear": 1}, NUM_SPARSE, cache_rows,
        cache_dtype=cache_dtype,
    )
    # admitted rows start at the flat model's init values, so the two
    # runs share their step-0 state exactly
    store.host.set_backfill(
        lambda plane, fields, ids: flat_init[plane][
            hash_rows(fields, ids, cap)
        ]
    )
    tier_tr.tiered_store = store

    max_loss_diff = 0.0
    for step in range(parity_steps):
        batch = parity_batch_at(step)
        flat_state, flat_loss = flat_tr.train_on_batch(flat_state, batch)
        tier_state, tier_loss = tier_tr.train_on_batch(
            tier_state,
            store.attach(
                {"features": dict(batch["features"]),
                 "labels": batch["labels"]}
            ),
        )
        max_loss_diff = max(
            max_loss_diff,
            abs(float(jax.device_get(flat_loss))
                - float(jax.device_get(tier_loss))),
        )

    probe = parity_batch_at(10_000)
    flat_pred = np.asarray(jax.device_get(
        flat_tr.predict_on_batch(flat_state, probe["features"])
    ))
    slots, _plan = store.prepare(probe["features"]["sparse"])
    tier_pred = np.asarray(jax.device_get(
        tier_tr.predict_on_batch(
            tier_state,
            {"dense": probe["features"]["dense"], "slots": slots},
        )
    ))
    # the trained rows themselves: flat row value vs tiered cache slot
    flat_emb = np.asarray(jax.device_get(
        flat_state.params["params"]["fm_embedding"]["embedding"]
    ))
    tier_emb = np.asarray(jax.device_get(
        tier_state.params["params"]["fm_embedding"]["embedding"]
    ))
    probe_rows = hash_rows(
        np.arange(NUM_SPARSE)[None, :], probe["features"]["sparse"], cap
    )
    row_diff = float(np.abs(
        flat_emb[probe_rows] - tier_emb[slots]
    ).max())
    pred_diff = float(np.abs(flat_pred - tier_pred).max())
    detail["parity"] = {
        "steps": parity_steps,
        "batch_size": parity_batch,
        "working_set_rows": int(NUM_SPARSE * ids_per_field),
        "cache_rows": cache_rows,
        "cache_dtype": cache_dtype,
        "max_abs_loss_diff": max_loss_diff,
        "max_abs_trained_row_diff": row_diff,
        # Train-path parity is the bitwise claim: per-step losses prove
        # the forward program, trained rows prove the backward.  Predict
        # compiles a SEPARATE program per model (different gather table
        # shapes -> different XLA fusion order), so its diff is allowed
        # to be a few ulp and is reported, not gated on.  The bitwise
        # claim is an FP32-cache contract: an int8 cache quantizes
        # admissions, so its diffs vs flat are reported, not gated.
        "parity_gated": cache_dtype == "float32",
        "exact": bool(max_loss_diff == 0.0 and row_diff == 0.0),
        "predict_max_abs_diff": pred_diff,
        "predict_within_few_ulp": bool(pred_diff <= 4 * np.finfo(np.float32).eps),
    }

    # ---- 2. zipfian cache efficacy (the STORE_SUMMARY config) ----------
    hit_rate, growth_rows = zipfian_summary()
    detail["zipfian"] = {
        "hit_rate": round(hit_rate, 4),
        "growth_rows": int(growth_rows),
    }

    # ---- 3. beyond-budget config the flat arena cannot run -------------
    budget_bytes = 4 << 20       # declared device-embedding budget
    big_dim, big_cache = 16, 4096
    # fp32 params + Adam m + v, both planes (dim + the dim-1 linear)
    bytes_per_row = (big_dim + 1) * 4 * 3
    flat_rows_wanted = 1 << 20   # the north-star flat config
    flat_rows_affordable = budget_bytes // bytes_per_row
    _, big_tr = _trainer_for(
        "deepfm.deepfm_tiered.custom_model",
        model_params=f"cache_rows={big_cache};embed_dim={big_dim}",
    )
    big_store = TieredStore(
        {"fm_embedding": big_dim, "fm_linear": 1}, NUM_SPARSE, big_cache
    )
    big_tr.tiered_store = big_store
    big_store.start()
    brng = np.random.RandomState(11)
    big_state = big_tr.init_state(
        jax.random.PRNGKey(0),
        {"dense": np.zeros((128, 13), np.float32),
         "slots": np.zeros((128, NUM_SPARSE), np.int32)},
    )
    growth_curve = []
    for _ in range(20):
        batch = {
            "features": {
                "dense": brng.rand(128, 13).astype(np.float32),
                # uniform over the raw id space: nearly every id is new,
                # the flat-killing regime (no head to cache)
                "sparse": brng.randint(
                    0, 1 << 22, (128, NUM_SPARSE)
                ).astype(np.int32),
            },
            "labels": brng.randint(0, 2, 128).astype(np.int32),
        }
        big_state, big_loss = big_tr.train_on_batch(
            big_state, big_store.attach(batch)
        )
        growth_curve.append(big_store.host.size)
    jax.device_get(big_loss)
    big_store.stop()
    big_stats = big_store.stats()
    detail["beyond_budget"] = {
        "device_embedding_budget_bytes": budget_bytes,
        "flat_rows_wanted": flat_rows_wanted,
        "flat_bytes_wanted": flat_rows_wanted * bytes_per_row,
        "flat_rows_affordable": int(flat_rows_affordable),
        "flat_cannot_run": bool(
            flat_rows_wanted * bytes_per_row > budget_bytes
        ),
        "tiered_device_bytes": big_cache * bytes_per_row,
        "tiered_fits_budget": bool(
            big_cache * bytes_per_row <= budget_bytes
        ),
        "vocab_rows_grown": big_stats["vocab_rows"],
        "vocab_exceeds_affordable_flat": bool(
            big_stats["vocab_rows"] > flat_rows_affordable
        ),
        "host_tier_bytes": big_stats["host_bytes"],
        "growth_curve_rows": growth_curve,
        "train_steps_run": len(growth_curve),
    }

    # ---- 4. equal-vocab throughput + cold-gather overlap ---------------
    tp_cap, tp_dim, tp_cache = 1 << 14, 16, 4096
    stream = zipfian_batches(
        steps=throughput_steps + 4, batch=throughput_batch
    )
    dense = np.random.RandomState(3).rand(
        throughput_batch, 13
    ).astype(np.float32)
    labels = np.random.RandomState(4).randint(
        0, 2, throughput_batch
    ).astype(np.int32)

    def batch_at(i, sparse_dtype=np.int32):
        return {
            "features": {
                "dense": dense,
                "sparse": stream[i].astype(sparse_dtype),
            },
            "labels": labels,
        }

    _, flat_tp = _trainer_for(
        "deepfm.deepfm_functional_api.custom_model",
        model_params=f"vocab_capacity={tp_cap};embed_dim={tp_dim}",
    )
    fstate = flat_tp.init_state(
        jax.random.PRNGKey(0), batch_at(0)["features"]
    )
    for i in range(4):           # warm-up: compile
        fstate, floss = flat_tp.train_on_batch(fstate, batch_at(i))
    jax.device_get(floss)
    t0 = _time.perf_counter()
    for i in range(4, 4 + throughput_steps):
        fstate, floss = flat_tp.train_on_batch(fstate, batch_at(i))
    jax.device_get(floss)
    flat_eps = throughput_steps * throughput_batch / (
        _time.perf_counter() - t0
    )

    _, tier_tp = _trainer_for(
        "deepfm.deepfm_tiered.custom_model",
        model_params=(f"cache_rows={tp_cache};embed_dim={tp_dim};"
                      f"cache_dtype='{cache_dtype}'"),
    )
    from elasticdl_tpu.common.profiler import PhaseTimer

    timer = PhaseTimer(flush_every=1 << 30)
    tp_store = TieredStore(
        {"fm_embedding": tp_dim, "fm_linear": 1}, NUM_SPARSE, tp_cache,
        phase_timer=timer, cache_dtype=cache_dtype,
    )
    tier_tp.tiered_store = tp_store
    tp_store.start()
    tstate = tier_tp.init_state(
        jax.random.PRNGKey(0),
        {"dense": dense,
         "slots": np.zeros((throughput_batch, NUM_SPARSE), np.int32)},
    )
    for i in range(4):
        tstate, tloss = tier_tp.train_on_batch(
            tstate, tp_store.attach(batch_at(i))
        )
    jax.device_get(tloss)
    t0 = _time.perf_counter()
    for i in range(4, 4 + throughput_steps):
        tstate, tloss = tier_tp.train_on_batch(
            tstate, tp_store.attach(batch_at(i))
        )
    jax.device_get(tloss)
    tier_s = _time.perf_counter() - t0
    tier_eps = throughput_steps * throughput_batch / tier_s
    tp_store.stop()
    tp_stats = tp_store.stats()
    detail["throughput"] = {
        "flat_vocab_capacity": tp_cap,
        "cache_rows": tp_cache,
        "embed_dim": tp_dim,
        "batch_size": throughput_batch,
        "steps": throughput_steps,
        "flat_examples_per_sec": round(flat_eps, 1),
        "tiered_examples_per_sec": round(tier_eps, 1),
        "tiered_vs_flat": round(tier_eps / max(flat_eps, 1e-9), 3),
        "hit_rate": round(tp_stats["hit_rate"], 4),
        "cold_gather_overlap_share": round(
            tp_stats["cold_gather_overlap_share"], 3
        ),
        "cold_gather_async_s": round(tp_stats["cold_gather_async_s"], 4),
        "cold_gather_sync_s": round(tp_stats["cold_gather_sync_s"], 4),
        "cold_gather_share_of_wall": round(
            (tp_stats["cold_gather_async_s"]
             + tp_stats["cold_gather_sync_s"]) / tier_s, 4
        ),
        "cache_dtype": cache_dtype,
    }

    # ---- 5. analytic device-cache bytes, fp32 vs int8 ------------------
    from elasticdl_tpu.store.cache import (
        cache_value_bytes_per_row,
        device_cache_bytes,
        device_cache_bytes_per_step,
    )

    ana_planes = {"fm_embedding": tp_dim, "fm_linear": 1}
    lookups = throughput_batch * NUM_SPARSE
    fp32_total = device_cache_bytes(ana_planes, tp_cache, "float32")
    int8_total = device_cache_bytes(ana_planes, tp_cache, "int8")
    emb_fp32 = cache_value_bytes_per_row(tp_dim, "float32")
    emb_int8 = cache_value_bytes_per_row(tp_dim, "int8")
    detail["device_cache_bytes"] = {
        "cache_dtype": cache_dtype,
        "planes": ana_planes,
        "cache_rows": tp_cache,
        "lookups_per_step": lookups,
        "fp32_bytes_at_capacity": fp32_total,
        "int8_bytes_at_capacity": int8_total,
        "fp32_bytes_per_step": device_cache_bytes_per_step(
            ana_planes, lookups, "float32"
        ),
        "int8_bytes_per_step": device_cache_bytes_per_step(
            ana_planes, lookups, "int8"
        ),
        "device_cache_bytes_per_step": device_cache_bytes_per_step(
            ana_planes, lookups, cache_dtype
        ),
        # Headline on the byte-dominant quantized embedding plane
        # (dim 16: 64 -> 20 bytes/row = 3.2x; equivalently 3.2x more
        # resident embedding rows at an equal byte budget).  The
        # aggregate is diluted by the dim-1 linear plane, whose fixed
        # 4-byte per-row scale nearly cancels its code savings.
        "embedding_plane_bytes_fp32": emb_fp32,
        "embedding_plane_bytes_int8": emb_int8,
        "embedding_plane_reduction": round(emb_fp32 / emb_int8, 3),
        "equal_budget_resident_rows_multiplier": round(
            emb_fp32 / emb_int8, 3
        ),
        "aggregate_reduction": round(fp32_total / int8_total, 3),
        "reduction_at_least_3x": bool(emb_fp32 / emb_int8 >= 3.0),
    }

    # ---- 6. K-step fused-block parity vs flat --------------------------
    # Both models run the SAME K-step lax.scan program shape
    # (train_on_batch_stack); the tiered side plans ONE union admission
    # block before the scan (prepare_block via the deferred path).  For
    # an fp32 cache the per-step losses must stay bitwise identical to
    # flat — sub-bench 1's contract extended to steps_per_execution>1.
    if fused_k > 1:
        fb_store = TieredStore(
            {"fm_embedding": dim, "fm_linear": 1}, NUM_SPARSE,
            cache_rows, cache_dtype=cache_dtype,
        )
        fb_store.host.set_backfill(
            lambda plane, fields, ids: flat_init[plane][
                hash_rows(fields, ids, cap)
            ]
        )
        fb_store.enable_deferred_prepare()
        tier_tr.tiered_store = fb_store
        fb_flat_state = flat_tr.init_state(
            jax.random.PRNGKey(0), b0["features"]
        )
        fb_tier_state = tier_tr.init_state(
            jax.random.PRNGKey(0),
            {
                "dense": b0["features"]["dense"],
                "slots": np.zeros((parity_batch, NUM_SPARSE), np.int32),
            },
        )
        fb_batches = [parity_batch_at(20_000 + k) for k in range(fused_k)]
        _, fb_flat_losses = flat_tr.train_on_batch_stack(
            fb_flat_state, fb_batches
        )
        _, fb_tier_losses = tier_tr.train_on_batch_stack(
            fb_tier_state,
            [fb_store.attach(
                {"features": dict(b["features"]), "labels": b["labels"]}
            ) for b in fb_batches],
        )
        fb_flat_losses = np.asarray(jax.device_get(fb_flat_losses))
        fb_tier_losses = np.asarray(jax.device_get(fb_tier_losses))
        fb_diff = float(np.abs(fb_flat_losses - fb_tier_losses).max())
        detail["fused_block"] = {
            "k": int(fused_k),
            "cache_dtype": cache_dtype,
            "block_plans": fb_store.stats()["block_plans"],
            "flat_losses": [float(x) for x in fb_flat_losses],
            "tiered_losses": [float(x) for x in fb_tier_losses],
            "max_abs_loss_diff": fb_diff,
            "parity_gated": cache_dtype == "float32",
            "exact": bool(fb_diff == 0.0),
        }

    # Registry-backed store-program ledger: the gather/admit programs
    # above registered their (dispatch-observed) compiles, so the bench
    # records the same compile/signature counts /varz would show.
    from elasticdl_tpu.common import programs as programs_lib

    detail["program_ledger"] = {
        name: rec
        for name, rec in programs_lib.default_program_registry()
        .ledger().items()
        if name.startswith("store_")
    }

    return {
        "bench": "tiered",
        "value": detail["throughput"]["tiered_examples_per_sec"],
        "unit": "examples/sec",
        "detail": detail,
    }


def _tiered_multichip_child(n_devices: int = 8,
                            cache_dtype: str = "float32",
                            steps: int = 6, seed: int = 0):
    """Child half of `bench_tiered_multichip` — assumes jax already sees
    `n_devices` devices (the parent re-execs us under a virtual CPU
    mesh).  Trains a tiered DeepFM whose cache tables row-shard over an
    n-way `model` mesh axis, then prints one JSON line with the
    per-chip embedding byte split (measured from the arrays'
    addressable shards, not inferred) and a checksum of the cache
    values for the parent's same-seed byte-stability check."""
    import zlib

    import jax

    import model_zoo.deepfm.deepfm_tiered as zoo
    from elasticdl_tpu.parallel import mesh as mesh_lib
    from elasticdl_tpu.worker.trainer import Trainer

    cache_rows, dim, batch, ids_per_field = 4096, 16, 128, 40
    mesh = mesh_lib.create_mesh(data=1, model=n_devices)
    model = zoo.custom_model(
        cache_rows=cache_rows, embed_dim=dim, cache_dtype=cache_dtype
    )
    tr = Trainer(model=model, optimizer=zoo.optimizer(),
                 loss_fn=zoo.loss,
                 param_sharding_fn=zoo.param_sharding, mesh=mesh)
    store = zoo.build_tiered_store()
    store.set_mesh_shards(n_devices)
    tr.tiered_store = store

    rng = np.random.RandomState(seed)
    ids = rng.randint(0, 1 << 22, (zoo.NUM_SPARSE, ids_per_field))

    def batch_at(i):
        brng = np.random.RandomState(seed * 1000 + i)
        pick = brng.randint(0, ids_per_field, (batch, zoo.NUM_SPARSE))
        return {
            "features": {
                "dense": brng.rand(batch, zoo.NUM_DENSE).astype(
                    np.float32
                ),
                "sparse": ids[np.arange(zoo.NUM_SPARSE)[None, :], pick],
            },
            "labels": brng.randint(0, 2, batch).astype(np.int32),
        }

    state = tr.init_state(
        jax.random.PRNGKey(seed),
        {"dense": np.zeros((batch, zoo.NUM_DENSE), np.float32),
         "slots": np.zeros((batch, zoo.NUM_SPARSE), np.int32)},
    )
    sub_plan_admits = []
    for i in range(steps):
        ab = store.attach(batch_at(i))
        plan = ab.get("__store_plan__")
        if plan is not None and plan.sub_plans is not None:
            sub_plan_admits.append(
                [int(sp["admit_slots"].size) for sp in plan.sub_plans]
            )
        state, loss = tr.train_on_batch(state, ab)
    jax.device_get(loss)

    # Per-chip bytes of every embedding-cache array — measured from
    # where XLA actually placed the shards.  In int8 mode the fp32
    # params are the zero gradient CARRIER (values live in the q8/scale
    # planes); in fp32 mode the params ARE the values.  The split is
    # reported so the int8 total isn't misread: the carrier is byte-wise
    # identical in both modes and cancels out of any comparison, while
    # the VALUE bytes shrink per the analytic model.
    def cache_arrays():
        for name in store.planes:
            is_value = cache_dtype == "float32"
            yield name, state.params["params"][name]["embedding"], is_value
        if cache_dtype == "int8":
            for name in store.planes:
                planes = state.model_state["quantized"][name]["embedding"]
                yield f"{name}.q8", planes["q8"], True
                yield f"{name}.scale", planes["scale"], True

    per_chip = {}
    per_chip_value = {}
    total = value_total = 0
    crc = 0
    for name, arr, is_value in cache_arrays():
        total += arr.nbytes
        value_total += arr.nbytes if is_value else 0
        for sh in arr.addressable_shards:
            dev = int(sh.device.id)
            nbytes = int(sh.data.nbytes)
            per_chip[dev] = per_chip.get(dev, 0) + nbytes
            if is_value:
                per_chip_value[dev] = per_chip_value.get(dev, 0) + nbytes
        crc = zlib.crc32(
            np.ascontiguousarray(jax.device_get(arr)).tobytes(), crc
        )
    print(json.dumps({
        "n_devices": n_devices,
        "cache_dtype": cache_dtype,
        "steps": steps,
        "cache_rows": cache_rows,
        "embed_dim": dim,
        "total_embedding_bytes": int(total),
        "value_plane_bytes": int(value_total),
        "carrier_bytes": int(total - value_total),
        "per_chip_embedding_bytes": [
            per_chip.get(d, 0) for d in range(n_devices)
        ],
        "per_chip_value_bytes": [
            per_chip_value.get(d, 0) for d in range(n_devices)
        ],
        "sub_plan_admits_per_step": sub_plan_admits,
        "final_loss": float(jax.device_get(loss)),
        "cache_values_crc32": int(crc & 0xFFFFFFFF),
    }))


def bench_tiered_multichip(n_devices: int = 8,
                           cache_dtype: str = "float32"):
    """Mesh-sharded tiered seam over a virtual n-device mesh (ISSUE
    18b): `python bench.py tiered-multichip [--cache_dtype int8]`.

    Self-provisioning like `__graft_entry__.dryrun_multichip`: when the
    host has fewer than n devices the measurement runs in a subprocess
    with `JAX_PLATFORMS=cpu` + `--xla_force_host_platform_device_count`
    — chips virtual, math on the CPU — so the
    per-chip BYTE split is exact while absolute step time is not
    TPU-representative.  Runs the child TWICE with the same seed and
    gates on identical cache-value checksums (byte-stability) and on
    per-chip embedding bytes == total/n on every chip (~linear
    shrink)."""
    import subprocess

    from elasticdl_tpu.common.virtual_mesh import cpu_mesh_env

    env = cpu_mesh_env(n_devices)
    code = (
        "import sys; sys.path.insert(0, {root!r})\n"
        "import bench\n"
        "bench._tiered_multichip_child({n}, cache_dtype={dt!r})\n"
    ).format(root=_ROOT, n=n_devices, dt=cache_dtype)
    runs = []
    for _ in range(2):
        proc = subprocess.run(
            [sys.executable, "-c", code], env=env,
            capture_output=True, text=True, check=True,
        )
        runs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    first, second = runs
    per_chip = first["per_chip_embedding_bytes"]
    total = first["total_embedding_bytes"]
    detail = {
        **first,
        "byte_stable_across_same_seed_runs": bool(
            first["cache_values_crc32"] == second["cache_values_crc32"]
            and per_chip == second["per_chip_embedding_bytes"]
        ),
        "per_chip_is_total_over_n": bool(
            all(b == total // n_devices for b in per_chip)
        ),
        "methodology": (
            f"virtual {n_devices}-device CPU mesh "
            "(--xla_force_host_platform_device_count)"
            ": per-chip bytes measured from addressable shards are "
            "exact; absolute step time is not TPU-representative"
        ),
    }
    return {
        "bench": "tiered-multichip",
        "value": max(per_chip),
        "unit": "per_chip_embedding_bytes",
        "detail": detail,
    }


def _maybe_attach_metrics(result):
    """--emit-metrics: append the unified registry's snapshot to the
    bench JSON, so a bench run doubles as an instrumentation check (the
    counters the run exercised — wire pack bytes, rpc totals — show up
    next to the bench numbers)."""
    from elasticdl_tpu.common import metrics

    if isinstance(result, dict):
        result["metrics_snapshot"] = metrics.default_registry().snapshot()
    return result


def main():
    argv = [a for a in sys.argv[1:] if a != "--emit-metrics"]
    emit_metrics = len(argv) != len(sys.argv) - 1
    # --cache_dtype {float32,int8} selects the tiered benches' device
    # hot-row cache plane layout (ISSUE 18a).
    cache_dtype = "float32"
    rest = []
    it = iter(argv)
    for a in it:
        if a == "--cache_dtype":
            cache_dtype = next(it, cache_dtype)
        elif a.startswith("--cache_dtype="):
            cache_dtype = a.split("=", 1)[1]
        else:
            rest.append(a)
    argv = rest
    which = argv[0] if argv else "full"
    which = which.lstrip("-")  # `--serving` and `serving` both work
    post = _maybe_attach_metrics if emit_metrics else (lambda r: r)
    if which == "all":
        for fn in (bench_deepfm, bench_mnist, bench_bert):
            print(json.dumps(post(fn())))
    else:
        fn = {"full": bench_full, "deepfm": bench_deepfm,
              "deepfm-int8": lambda: bench_deepfm(arena_dtype="int8"),
              "deepfm_int8": lambda: bench_deepfm(arena_dtype="int8"),
              "mnist": bench_mnist, "bert": bench_bert,
              "serving": bench_serving,
              "serving-fleet": bench_serving_fleet,
              "serving_fleet": bench_serving_fleet,
              "online": bench_online,
              "traffic": bench_traffic,
              "sparse-path": bench_sparse_path,
              "sparse_path": bench_sparse_path,
              "tiered": lambda: bench_tiered(cache_dtype=cache_dtype),
              "tiered-multichip": lambda: bench_tiered_multichip(
                  cache_dtype=cache_dtype),
              "tiered_multichip": lambda: bench_tiered_multichip(
                  cache_dtype=cache_dtype),
              "e2e": lambda: bench_deepfm_e2e()}[which]
        print(json.dumps(post(fn())))


if __name__ == "__main__":
    main()
