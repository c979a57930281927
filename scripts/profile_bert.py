"""On-chip profile of the BERT bench step (VERDICT r4 next-round item 1).

Captures a JAX profiler trace of the exact train step `bench.py bert`
times (BERT-base, 512-seq, bf16, batch 64 by default), then parses the
XPlane proto device plane ("XLA Ops" line) into a per-op time breakdown
grouped into categories (attention fwd/bwd, MLP matmuls, QKV/proj
matmuls, layernorm chains, optimizer/casts, embedding, gaps).  The
resulting table goes into docs/BERT_PROFILE.md so the MFU gap is
attributed, not hand-waved.

Usage:
    python scripts/profile_bert.py [--batch 64] [--steps 3] \
        [--out /tmp/bert_trace]

Must run with PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION=python (the
tensorboard_plugin_profile protobufs in this image predate protoc 3.19;
the script re-execs itself with the var set if needed).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

if os.environ.get("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION") != "python":
    os.environ["PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION"] = "python"
    os.execv(sys.executable, [sys.executable] + sys.argv)


def capture(batch_size: int, seq_len: int, steps: int, out_dir: str,
            model_params: str | None = None) -> str:
    import jax
    import numpy as np

    from elasticdl_tpu.common.virtual_mesh import enable_compile_cache

    enable_compile_cache()
    sys.path.insert(0, os.path.join(_ROOT, "model_zoo"))
    from bench import _trainer_for
    from elasticdl_tpu.parallel import mesh as mesh_lib

    spec, trainer = _trainer_for(
        "bert.bert_finetune.custom_model",
        model_params=model_params or (
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
    sharded = mesh_lib.shard_batch(batch, trainer.mesh)
    # warm: compile + first exec outside the trace
    state, loss = trainer.train_step(state, sharded)
    jax.device_get(loss)
    jax.profiler.start_trace(out_dir)
    for _ in range(steps):
        state, loss = trainer.train_step(state, sharded)
    jax.device_get(loss)
    jax.profiler.stop_trace()
    return out_dir


CATEGORIES = (
    # (category, name substrings) — first match wins; names are XLA
    # fusion/op names after optimization, so attribution leans on the
    # stable fragments jax embeds (jvp/transpose paths, custom_vjp names,
    # op types).
    ("attention_bwd", ("_flash_bwd", "transpose(_flash)")),
    ("attention_fwd_pallas", ("flash", "pallas")),
    ("attention_softmax_misc", ("softmax", "attention")),
    ("matmul_fusions", ("dot", "convolution", "einsum")),
    ("optimizer_adamw", ("adam", "optax", "apply_updates", "lamb")),
    ("embedding", ("gather", "scatter", "take", "dynamic_slice")),
    ("layernorm_elementwise", ("reduce", "fusion")),
    ("copies_transposes", ("copy", "transpose", "bitcast", "reshape")),
    ("infeed_outfeed", ("infeed", "outfeed", "copy-start", "copy-done")),
)


def categorize(name: str) -> str:
    low = name.lower()
    for cat, frags in CATEGORIES:
        if any(f in low for f in frags):
            return cat
    return "other"


def analyze(trace_dir: str, steps: int) -> dict:
    import glob
    import gzip  # noqa: F401  (trace.json.gz sidecar, unused here)

    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    paths = glob.glob(
        os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb")
    )
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    xs = xplane_pb2.XSpace()
    with open(sorted(paths)[-1], "rb") as f:
        xs.ParseFromString(f.read())
    per_op: dict[str, float] = defaultdict(float)
    per_cat: dict[str, float] = defaultdict(float)
    module_span_ps = 0.0
    device_busy_ps = 0.0
    for plane in xs.planes:
        if not plane.name.startswith("/device:"):
            continue
        meta = plane.event_metadata
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    module_span_ps += ev.duration_ps
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                name = meta[ev.metadata_id].name
                dur = ev.duration_ps
                device_busy_ps += dur
                per_op[name] += dur
                per_cat[categorize(name)] += dur
    to_ms = lambda ps: ps / 1e9  # noqa: E731
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:40]
    return {
        "steps": steps,
        "module_span_ms_per_step": to_ms(module_span_ps) / steps,
        "device_busy_ms_per_step": to_ms(device_busy_ps) / steps,
        "gap_ms_per_step": to_ms(module_span_ps - device_busy_ps) / steps,
        "per_category_ms_per_step": {
            k: round(to_ms(v) / steps, 3)
            for k, v in sorted(per_cat.items(), key=lambda kv: -kv[1])
        },
        "top_ops_ms_per_step": [
            {"name": n, "ms": round(to_ms(d) / steps, 3)} for n, d in top
        ],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--out", default="/tmp/bert_trace")
    ap.add_argument("--model_params", default=None)
    ap.add_argument(
        "--analyze-only", action="store_true",
        help="skip capture; parse an existing trace dir",
    )
    args = ap.parse_args()
    if not args.analyze_only:
        capture(args.batch, args.seq, args.steps, args.out,
                args.model_params)
    print(json.dumps(analyze(args.out, args.steps), indent=1))


if __name__ == "__main__":
    main()
