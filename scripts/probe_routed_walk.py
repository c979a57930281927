"""`layers/moe.py: routed_walk` ALONE, on the chip: one routed layer's walk,
forward and backward (`jax.value_and_grad` of a weighted sum of its output
for the tokens, the two stacks and the slot weights), at five cells' shapes

    smallthinker  16,384 x 2,560, top-6,  8 held of 64,  experts 768 wide, reglu
    laguna        16,384 x 2,048, top-8,  32 held of 256, experts 512 wide, swiglu
    qwen3next     16,384 x 2,048, top-10, 32 held of 512, experts 512 wide, swiglu
    kimi          16,384 x 2,304, top-8,  32 held of 256, experts 1,024 wide, swiglu
    nemotron      16,384 x 2,688, top-6,  8 held of 128, experts 1,856 wide, relu2

(98,304 / 131,072 / 163,840 slots; `--shapes` also takes a shape spelt out,
`hidden:top_k:held:width:form`), each at `--shares` of the slots routed
to the held experts and under two routings: `even`, every held expert the
same load and each group an ascending pass over ALL the tokens (a balanced
router), and `one`, one expert holding nine tenths of the live rows as ONE
run of consecutive tokens (a collapsed router).  One program a shape (the
loads are data); `--calls` traced calls a case, and of them the device ms a
call: the whole, by the walk's scopes (`dispatch`, `experts`, `combine`; read
from the compiled text's `op_name`s as the cells' `scopes:` line is), and
the longest operations outside the grouped products.

    chiprun -- python3 scripts/probe_routed_walk.py
    chiprun -- python3 scripts/probe_routed_walk.py --root .proof/parent

`--root` takes `elasticdl_tpu` from another checkout (a parent unpacked
beside this one).  No cell imports this file; on the CPU it runs at
`--tokens 256` with no device plane to read, and says so.
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile

# name -> (hidden, top_k, held, experts' width, form)
SHAPES = {
    "smallthinker": (2560, 6, 8, 768, "reglu"),
    "laguna": (2048, 8, 32, 512, "swiglu"),
    "qwen3next": (2048, 10, 32, 512, "swiglu"),
    "kimi": (2304, 8, 32, 1024, "swiglu"),
    "nemotron": (2688, 6, 8, 1856, "relu2"),
}


def routing(np, tokens, top_k, held, share, kind, seed=0):
    """(order, group_sizes) of a routing that sends `share` of the slots to
    the held experts: `even` deals them out over a random choice of slots,
    `one` gives expert 0 nine tenths of them (at most a slot a token) as the
    first slot of consecutive tokens and deals the rest out."""
    rng = np.random.RandomState(seed)
    slots = tokens * top_k
    rows = int(share * slots)
    key = np.full((slots,), held, np.int32)
    hot = min(int(0.9 * rows), tokens) if kind == "one" else 0
    key[np.arange(hot) * top_k] = 0
    free = rng.permutation(np.flatnonzero(key == held))[:rows - hot]
    first = 1 if hot else 0   # the hot expert takes no dealt slot
    key[free] = first + np.arange(rows - hot) % (held - first)
    return (
        np.argsort(key, kind="stable").astype(np.int32),
        np.bincount(key, minlength=held + 1)[:held].astype(np.int32),
    )


def probe(root, shapes, shares, kinds, tokens, calls, top):
    sys.path.insert(0, os.path.abspath(root))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from elasticdl_tpu.common import profiler, programs
    from elasticdl_tpu.layers import moe

    print(f"root {os.path.abspath(root)}  device "
          f"{jax.devices()[0].device_kind}  tokens {tokens}  calls {calls}")
    for name in shapes:
        hidden, top_k, held, ffn, form = SHAPES.get(name) or [
            int(part) if part.isdigit() else part for part in name.split(":")
        ]
        keys = jax.random.split(jax.random.PRNGKey(0), 5)
        parts = moe.FORMS[form][1]
        operands = (
            jax.random.normal(keys[0], (tokens, hidden), jnp.bfloat16),
            0.02 * jax.random.normal(
                keys[1], (held, hidden, parts * ffn), jnp.bfloat16
            ),
            0.02 * jax.random.normal(
                keys[2], (held, ffn, hidden), jnp.bfloat16
            ),
            jax.random.uniform(keys[3], (tokens * top_k,), jnp.float32),
        )
        cotangent = jax.random.normal(keys[4], (tokens, hidden), jnp.float32)

        def loss(t, first, down, weights, order, sizes):
            out = moe.routed_walk(t, first, down, order, weights, sizes, form)
            return (out * cotangent).sum()

        order, sizes = routing(np, tokens, top_k, held, shares[0], "even")
        compiled = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1, 2, 3))
        ).lower(*operands, order, sizes).compile()
        table = programs.parse_scope_table(compiled.as_text())
        for kind in kinds:
            for share in shares:
                order, sizes = routing(np, tokens, top_k, held, share, kind)
                args = operands + (jnp.asarray(order), jnp.asarray(sizes))
                jax.block_until_ready(compiled(*args))
                with tempfile.TemporaryDirectory() as trace_dir:
                    with jax.profiler.trace(trace_dir):
                        for _ in range(calls):
                            jax.block_until_ready(compiled(*args))
                    seconds = profiler.xla_op_seconds(trace_dir)
                report(profiler, name, kind, share, int(sizes.sum()),
                       seconds, table, calls, top)


def report(profiler, name, kind, share, rows, seconds, table, calls, top):
    head = f"{name:14s} {kind:4s} {share:5.3f} rows {rows:6d}"
    if not seconds:
        print(f"{head}: no device plane (not a chip)")
        return
    by_scope = profiler.scope_summary(seconds, table, calls)
    whole = sum(row["ms_per_step"] for row in by_scope.values())
    scopes = "  ".join(
        f"{scope} {row['ms_per_step']:.2f}" for scope, row in by_scope.items()
    )
    print(f"{head}: {whole:7.2f} ms a call  |  {scopes}")
    longest = []
    for text, s in seconds.items():
        row = table.get(profiler.instruction_name(text))
        if row is not None and not row.container and "ragged" not in text:
            longest.append((s, text, row))
    for s, text, row in sorted(longest, reverse=True)[:top]:
        print(f"    {1e3 * s / calls:7.3f} ms  {row.entry or '-':9s} "
              f"{row.phase:8s} {text[:110]}")


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--root", default=".")
    parser.add_argument("--shapes", default=",".join(SHAPES))
    parser.add_argument("--shares", default="0.125,0.25,0.5")
    parser.add_argument("--kinds", default="even,one")
    parser.add_argument("--tokens", type=int, default=16384)
    parser.add_argument("--calls", type=int, default=3)
    parser.add_argument("--top", type=int, default=4,
                        help="longest operations printed a case")
    args = parser.parse_args(argv)
    probe(
        args.root, args.shapes.split(","),
        [float(s) for s in args.shares.split(",")], args.kinds.split(","),
        args.tokens, args.calls, args.top,
    )


if __name__ == "__main__":
    main()
