"""The two delta-rule scans' kernels ALONE, on the chip: `ops/kda.py`'s
`kda_chunk_fwd` / `kda_chunk_bwd` at the Kimi-Linear cell's shape ((2, 8192,
32 heads of 128) with `qk_norm`) and `ops/gdn.py`'s `gdn_chunk_fwd` /
`gdn_chunk_bwd` at the Qwen3-Next cell's ((2, 8192, 16 key / 32 value heads
of 128) with `qk_norm`).  For each scan: `--calls` traced calls of the
gradient of a weighted sum of the output (one forward and one backward
kernel a call), the MEDIAN device time of each kernel read from the trace
by the kernel's name, and the kernels' error at bfloat16 operands against
the float32 token-by-token recurrence on the same rounded operands (two
chunks of two heads; keys drawn at random, keys all ALIGNED at beta 0.95,
keys that alternate in sign; g = 0, down to -1 and down to -20 a token),
each over `--seeds` seeds: the mean and the largest, so that a change to
the kernels' arithmetic is read beside the scatter the seeds give.

    chiprun -- python3 scripts/probe_scan_kernels.py
    chiprun -- python3 scripts/probe_scan_kernels.py --root .proof/parent
    chiprun -- python3 scripts/probe_scan_kernels.py --heads 4

`--root` takes the two modules from another checkout (a parent unpacked
beside this one); `--heads` sets how many (key) heads a grid step takes in
both scans where the head count divides.  A variant of a kernel's body is
probed by a script that imports this one, replaces the function in
`probe.kda_ops` / `probe.gdn_ops` and calls `time_scans` / `error_table`.
No cell imports this file; on the CPU it runs the kernels interpreted at
`--length 128` and its times mean nothing.
"""

from __future__ import annotations

import argparse
import glob
import os
import statistics
import sys
import tempfile

NORM = (1e-6, 128 ** -0.5)
KERNELS = ("kda_chunk_fwd", "kda_chunk_bwd", "gdn_chunk_fwd", "gdn_chunk_bwd")


def load(root):
    """(jax, jnp, ops/kda.py, ops/gdn.py) of the checkout at `root`."""
    sys.path.insert(0, os.path.abspath(root))
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops import gdn, kda

    return jax, jnp, kda, gdn


def set_heads(kda_ops, gdn_ops, heads: int):
    """`heads` heads a grid step in KDA, `heads` KEY heads in GDN (each
    with its value heads), where the counts divide."""
    kda_ops._HEADS = heads
    gdn_ops._groups = lambda key_heads, ratio, *_: (
        (heads, heads * ratio) if key_heads % heads == 0 else (1, ratio)
    )
    for module in (kda_ops, gdn_ops):
        module._forward_call.cache_clear()
        module._backward_call.cache_clear()


def operands(jax, jnp, scan, batch, length, seed=0):
    """The cell's operands of one scan, q and k raw (the op norms them)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    heads, key_heads = 32, 32 if scan == "kda" else 16
    qk = (batch, length, key_heads, 128)
    wide = (batch, length, heads, 128)
    g_shape = wide if scan == "kda" else wide[:3]
    return (
        jax.random.normal(keys[0], qk, jnp.bfloat16),
        jax.random.normal(keys[1], qk, jnp.bfloat16),
        jax.random.normal(keys[2], wide, jnp.bfloat16),
        -0.3 * jax.random.uniform(keys[3], g_shape),
        jax.nn.sigmoid(jax.random.normal(keys[4], wide[:3])),
    ), jax.random.normal(keys[5], wide, jnp.bfloat16)


def kernel_times(trace_dir: str) -> dict:
    """{kernel name: [device ms of each of its events]} of a trace."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))[-1]
    times = {name: [] for name in KERNELS}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for event in line.events:
                for name in KERNELS:
                    if name in event.name:
                        times[name].append(event.duration_ns * 1e-6)
    return times


def time_scans(jax, jnp, kda_ops, gdn_ops, calls: int, batch: int,
               length: int) -> dict:
    """{kernel name: median device ms} of both scans' two kernels."""
    medians = {}
    for scan, op in (("kda", kda_ops.kda), ("gdn", gdn_ops.gdn)):
        args, weight = operands(jax, jnp, scan, batch, length)

        def loss(*a):
            return (op(*a, qk_norm=NORM) * weight).astype(jnp.float32).sum()

        grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
        try:
            jax.block_until_ready(grads(*args))
        except Exception as error:  # what Mosaic refuses at this grouping
            print(f"{scan}: {type(error).__name__}: {str(error)[:300]}")
            continue
        with tempfile.TemporaryDirectory() as trace_dir:
            with jax.profiler.trace(trace_dir):
                for _ in range(calls):
                    jax.block_until_ready(grads(*args))
            for name, times in kernel_times(trace_dir).items():
                if name.startswith(scan) and times:
                    medians[name] = statistics.median(times)
    return medians


# ---- the error columns -----------------------------------------------------


def recurrence(jax, jnp, q, k, v, g, beta):
    """The delta rule token by token in float32: q, k, v (B, L, H, D), g
    (B, L, H, D), beta (B, L, H) -> o (B, L, H, D)."""
    def head(q, k, v, g, beta):
        def step(state, token):
            q_t, k_t, v_t, g_t, b_t = token
            state = state * jnp.exp(g_t)[:, None]
            state = state + b_t * k_t[:, None] * (
                v_t - (state * k_t[:, None]).sum(axis=0)
            )[None, :]
            return state, (state * q_t[:, None]).sum(axis=0)

        return jax.lax.scan(
            step, jnp.zeros((q.shape[1], v.shape[1]), jnp.float32),
            (q, k, v, g, beta),
        )[1]

    with jax.default_matmul_precision("highest"):
        return jax.vmap(jax.vmap(head, in_axes=1, out_axes=1))(
            q, k, v, g, beta
        )


def case_operands(jax, jnp, scan, keys_kind, g_min, seed):
    """Two chunks of two (key) heads of 128 in bfloat16, q and k unit
    rows (q scaled), as `tests/test_kda.py: inputs` draws them."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    ratio = 1 if scan == "kda" else 2
    qk = (1, 128, 2, 128)
    wide = (1, 128, 2 * ratio, 128)

    def normed(key, scale):
        x = jax.random.normal(key, qk)
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True) * scale

    q, k = normed(keys[0], 128 ** -0.5), normed(keys[1], 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], wide[:3]))
    if keys_kind != "random":
        k = jnp.broadcast_to(k[:, :1], k.shape)
        beta = jnp.full_like(beta, 0.95)
    if keys_kind == "opposite":
        k = k * jnp.where(jnp.arange(128) % 2 == 0, 1.0, -1.0)[
            None, :, None, None
        ]
    g_shape = wide if scan == "kda" else wide[:3]
    return (
        q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
        jax.random.normal(keys[2], wide).astype(jnp.bfloat16),
        g_min * jax.random.uniform(keys[3], g_shape), beta,
    ), jax.random.normal(keys[5], wide)


CASES = [
    ("random", 0.0), ("random", -1.0), ("random", -20.0),
    ("aligned", 0.0), ("opposite", 0.0),
]


def error_table(jax, jnp, kda_ops, gdn_ops, seeds: int) -> dict:
    """{(scan, keys, g_min): {"o" | "dq" | ...: (mean, max) over the
    seeds}}: |kernels - recurrence| / |recurrence|, the kernels on
    bfloat16 q, k, v and the recurrence in float32 on the same values."""
    names = ("o", "dq", "dk", "dv", "dg", "dbeta")
    table = {}
    for scan, op in (("kda", kda_ops._kda), ("gdn", gdn_ops._gdn)):
        ratio = 1 if scan == "kda" else 2

        def reference(q, k, v, g, beta):
            q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
            if scan == "gdn":
                q, k = (jnp.repeat(t, ratio, axis=2) for t in (q, k))
                g = jnp.broadcast_to(g[..., None], v.shape)
            return recurrence(jax, jnp, q, k, v, g, beta)

        def plain(q, k, v, g, beta, weight):
            return (reference(q, k, v, g, beta) * weight).sum()

        def through(q, k, v, g, beta, weight):
            return (op(q, k, v, g, beta).astype(jnp.float32) * weight).sum()

        def both(fn):
            def run(*a):
                return jax.grad(
                    lambda *x: fn(*x, a[5]), argnums=(0, 1, 2, 3, 4)
                )(*a[:5])
            return jax.jit(run)

        def outputs(q, k, v, g, beta):
            return (
                op(q, k, v, g, beta).astype(jnp.float32),
                reference(q, k, v, g, beta),
            )

        want_grads, got_grads = both(plain), both(through)
        outputs = jax.jit(outputs)
        for keys_kind, g_min in CASES:
            errors = {name: [] for name in names}
            for seed in range(seeds):
                args, weight = case_operands(
                    jax, jnp, scan, keys_kind, g_min, seed
                )
                got_o, want_o = outputs(*args)
                pairs = [(got_o, want_o)] + list(zip(
                    got_grads(*args, weight), want_grads(*args, weight)
                ))
                for name, (got, want) in zip(names, pairs):
                    got, want = (
                        t.astype(jnp.float32) for t in (got, want)
                    )
                    errors[name].append(float(
                        jnp.linalg.norm(got - want)
                        / (jnp.linalg.norm(want) + 1e-30)
                    ))
            table[(scan, keys_kind, g_min)] = {
                name: (statistics.mean(e), max(e))
                for name, e in errors.items()
            }
    return table


def print_errors(table: dict):
    print("errors against the float32 recurrence, bfloat16 operands, "
          "mean (max) over the seeds, x 1e-3:")
    for (scan, keys_kind, g_min), row in table.items():
        cells = "  ".join(
            f"{name} {1e3 * mean:.3f} ({1e3 * worst:.3f})"
            for name, (mean, worst) in row.items()
        )
        print(f"  {scan} {keys_kind:8s} g>={g_min:6.1f}: {cells}")


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--root", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".."
    ))
    parser.add_argument("--calls", type=int, default=5)
    parser.add_argument("--seeds", type=int, default=4)
    parser.add_argument("--heads", type=int, default=0)
    parser.add_argument("--batch", type=int, default=2)
    parser.add_argument("--length", type=int, default=8192)
    parser.add_argument("--skip-errors", action="store_true")
    parser.add_argument("--skip-times", action="store_true")
    args = parser.parse_args()
    jax, jnp, kda_ops, gdn_ops = load(args.root)
    device = jax.devices()[0]
    print(f"{os.path.abspath(args.root)} on {device.platform} "
          f"{device.device_kind}, heads a step "
          f"{args.heads or 'as committed'}", flush=True)
    if args.heads:
        set_heads(kda_ops, gdn_ops, args.heads)
    if not args.skip_times:
        medians = time_scans(
            jax, jnp, kda_ops, gdn_ops, args.calls, args.batch, args.length
        )
        print("  ".join(
            f"{name} {ms:.3f} ms" for name, ms in sorted(medians.items())
        ), flush=True)
    if not args.skip_errors:
        print_errors(error_table(jax, jnp, kda_ops, gdn_ops, args.seeds))


if __name__ == "__main__":
    main()
