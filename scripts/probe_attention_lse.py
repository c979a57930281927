"""The streaming attention kernels ALONE, on the chip, by where the saved
log-sum-exp lies: `ops/flash_attention.py`'s `_stream_fwd` and `_stream_bwd`
as two jitted programs (what a rematerialised block holds between them
crosses the jit boundary, so it is an array in HBM in the layout it is saved
in), at the Ouro cell's call ((1, 8192), 16 | 16 heads of 128), the Laguna
cell's window call ((2, 8192), 64 | 8, a band of 512) and the SmallThinker
cell's two ((1, 16384), 28 | 4, no band and a band of 4,096).  For each
checkout named by `--root` (this one unless said):

- `saved`: the log-sum-exp as that checkout's forward saves it;
- `beside`: where that is a (B, H, L, 1) column (128 times its values in
  HBM: the parent of PR 60), the column squeezed to (B, H, L) after the
  forward and expanded again before the backward, the kernels untouched.

`--calls` traced calls of each program; the MEDIAN device ms of the forward
and of the backward kernel by name, and of everything else on the device in
the same call (the copies a layout costs beside the kernels, `delta`'s
reduce); the bytes the saved array takes in HBM as the chip tiles it; and a
digest of `out`, the log-sum-exp's values, `dq`, `dk`, `dv`: equal digests
are equal bits.

    git archive f6d18f0 | tar -x -C .proof/parent      # a parent beside
    chiprun -- python3 scripts/probe_attention_lse.py \
        --root .proof/parent --root .

No cell imports this file; on the CPU it runs the kernels interpreted at
`--length 512` and its times mean nothing.
"""

from __future__ import annotations

import argparse
import glob
import hashlib
import importlib
import os
import statistics
import sys
import tempfile

# (name, batch, length, query heads, K/V heads, head width, window)
CALLS = [
    ("ouro", 1, 8192, 16, 16, 128, None),
    ("laguna-window", 2, 8192, 64, 8, 128, 512),
    ("smallthinker-full", 1, 16384, 28, 4, 128, None),
    ("smallthinker-window", 1, 16384, 28, 4, 128, 4096),
]


def load(root):
    """`ops/flash_attention.py` of the checkout at `root`, and no other
    checkout's: the package is dropped from `sys.modules` first."""
    for name in [m for m in sys.modules if m.startswith("elasticdl_tpu")]:
        del sys.modules[name]
    sys.path.insert(0, os.path.abspath(root))
    try:
        return importlib.import_module("elasticdl_tpu.ops.flash_attention")
    finally:
        sys.path.pop(0)


def tiled_bytes(shape, itemsize: int = 4) -> int:
    """Bytes of an array in HBM, its two minor axes in whole (8, 128)
    tiles of 32-bit values (`model_zoo/common/decoder.py: tiled_bytes`)."""
    *lead, rows, columns = (1, 1) + tuple(shape)
    size = itemsize
    for n in lead:
        size *= n
    return size * (-(-rows // 8) * 8) * (-(-columns // 128) * 128)


def device_times(trace_dir: str) -> list:
    """[(name, device ms)] of every operation on the device, in order."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))[-1]
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                events += [(e.name, e.duration_ns * 1e-6) for e in line.events]
    return events


def traced(jax, program, args, calls: int):
    """(median ms of the `_attention_` kernel, median ms of all else on the
    device, the last call's three longest other operations) over `calls`
    traced calls of `program`."""
    jax.block_until_ready(program(*args))
    kernel, rest = [], []
    for _ in range(calls):
        with tempfile.TemporaryDirectory() as trace_dir:
            with jax.profiler.trace(trace_dir):
                jax.block_until_ready(program(*args))
            events = device_times(trace_dir)
        others = [
            (ms, name) for name, ms in events if "_attention_" not in name
        ]
        kernel.append(sum(ms for name, ms in events if "_attention_" in name))
        rest.append(sum(ms for ms, _ in others))
    longest = " ".join(
        f"{name.split(' ')[0][:24]}={ms:.3f}"
        for ms, name in sorted(others, reverse=True)[:3]
    )
    return statistics.median(kernel), statistics.median(rest), longest


def digest(jnp, arrays) -> str:
    import numpy as np

    sha = hashlib.sha256()
    for array in arrays:
        sha.update(np.asarray(array.astype(jnp.float32)).tobytes())
    return sha.hexdigest()[:12]


def probe(fa, root: str, calls: int, length, only) -> None:
    import jax
    import jax.numpy as jnp

    for name, batch, full, heads, kv_heads, dim, window in CALLS:
        if only and name not in only:
            continue
        size = length or full
        band = window if window is None or window < size else None
        keys = jax.random.split(jax.random.PRNGKey(len(name)), 4)
        q, g = (
            jax.random.normal(key, (batch, size, heads, dim), jnp.bfloat16)
            for key in keys[:2]
        )
        k, v = (
            jax.random.normal(key, (batch, size, kv_heads, dim), jnp.bfloat16)
            for key in keys[2:]
        )
        scale = float(dim ** -0.5)
        column = jax.eval_shape(
            lambda q, k, v: fa._stream_fwd(q, k, v, scale, band)[1][-1],
            q, k, v,
        ).shape[-1] == 1
        for layout in ("saved", "beside")[:1 + column]:
            beside = layout == "beside"

            def forward(q, k, v):
                out, residuals = fa._stream_fwd(q, k, v, scale, band)
                lse = residuals[-1]
                return out, lse[..., 0] if beside else lse

            def backward(q, k, v, out, lse, g):
                if beside:
                    lse = lse[..., None]
                return fa._stream_bwd(scale, band, (q, k, v, out, lse), g)

            try:
                out, lse = jax.jit(forward)(q, k, v)
                grads = jax.jit(backward)(q, k, v, out, lse, g)
                fwd = traced(jax, jax.jit(forward), (q, k, v), calls)
                bwd = traced(
                    jax, jax.jit(backward), (q, k, v, out, lse, g), calls
                )
            except Exception as error:  # what Mosaic refuses
                print(f"{root} {name} {layout}: {type(error).__name__}: "
                      f"{str(error)[:300]}")
                continue
            print(
                f"{root} {name} {layout}: lse {lse.shape} "
                f"{tiled_bytes(lse.shape) / 1e6:.2f} MB in HBM | fwd kernel "
                f"{fwd[0]:.3f} ms + {fwd[1]:.3f} beside | bwd kernel "
                f"{bwd[0]:.3f} ms + {bwd[1]:.3f} beside | both "
                f"{sum(fwd[:2]) + sum(bwd[:2]):.3f} ms | bits "
                f"{digest(jnp, (out, lse.reshape(batch, heads, size)))} "
                f"{digest(jnp, grads)} | beside: fwd {fwd[2]}; bwd {bwd[2]}",
                flush=True,
            )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--root", action="append", default=None)
    parser.add_argument("--calls", type=int, default=5)
    parser.add_argument("--length", type=int, default=None)
    parser.add_argument("--only", default="")
    args = parser.parse_args(argv)
    only = [name for name in args.only.split(",") if name]
    for root in args.root or ["."]:
        probe(load(root), root, args.calls, args.length, only)
    return 0


if __name__ == "__main__":
    sys.exit(main())
