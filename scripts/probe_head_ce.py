"""The blocked cross-entropy ALONE, on the chip: `model_zoo/common/decoder.py:
blocked_nll` under `value_and_grad` of a mean of the weighed losses, at the
Ouro cell's head (32,768 rows x 2,048 x 49,152, the rows' weights NOT
uniform: an exit distribution's) and the GLM cell's (16,384 x 2,048 x 19,360,
TWO calls on one head, the second at 0.3 of the first's weight).  For each
checkout named by `--root` (this one unless said), one line a form:

- `loops`: a checkout whose `blocked_nll` takes no `weights` (the parent of
  PR 62): the rematerialised forward loop and its transposed backward, the
  weights applied to what it returns;
- `saved`: the one loop that makes the gradient's products beside the losses,
  the backward choosing on the device how many blocks it makes again: all
  or none (`decoder.blocks_again`);
- `saved-no-choice`: the same with the choice taken out (none, always),
  which is what the backward's loop is weighed against;
- `saved-fallback`: the same mean with a non-zero cotangent on the plain
  losses too, so every block is made again: what a silent fall would cost.

Each line: the MEDIAN device ms of `--calls` traced calls, the bytes the
compiled program holds at its peak (`memory_analysis()`: arguments +
outputs + temporaries), the `while`s and `conditional`s in the compiled
text, a digest of the value and the two gradients, and the call's longest
operations with how often each ran.

    git archive HEAD | tar -x -C .proof/parent         # a parent beside
    chiprun -- python3 scripts/probe_head_ce.py --root .proof/parent --root .

`--describe` compiles for a DESCRIBED v5e instead (no chip, no times: the
bytes and the loops only).  No cell imports this file; on the CPU it runs at
`--rows 256 --vocab 512` and its times mean nothing.
"""

from __future__ import annotations

import argparse
import collections
import glob
import hashlib
import importlib
import inspect
import os
import re
import statistics
import sys
import tempfile

HIDDEN = 2048
# (name, rows a call, vocabulary, the calls' weights in the loss)
SHAPES = [
    ("ouro", 32768, 49152, (1.0,)),
    ("glm", 16384, 19360, (1.0, 0.3)),
]


def load(root):
    """`model_zoo/common/decoder.py` of the checkout at `root`, and no
    other checkout's."""
    for name in [
        m for m in sys.modules
        if m.startswith(("elasticdl_tpu", "model_zoo"))
    ]:
        del sys.modules[name]
    sys.path.insert(0, os.path.abspath(root))
    try:
        return importlib.import_module("model_zoo.common.decoder")
    finally:
        sys.path.pop(0)


def device_events(trace_dir: str) -> list:
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
    """(median device ms a call, the last call's longest operations with
    their counts).  A `while`'s time is its body's, which the trace names
    too: the loops are left out of the sum and named in the list."""
    jax.block_until_ready(program(*args))
    totals = []
    for _ in range(calls):
        with tempfile.TemporaryDirectory() as trace_dir:
            with jax.profiler.trace(trace_dir):
                jax.block_until_ready(program(*args))
            events = device_events(trace_dir)
        totals.append(sum(
            ms for name, ms in events
            if not name.startswith(("%while", "%conditional"))
        ))
    by_name = collections.defaultdict(lambda: [0, 0.0])
    for name, ms in events:
        short = re.sub(r"\{[^}]*\}", "", name.split(" fusion(")[0])[:64]
        by_name[short][0] += 1
        by_name[short][1] += ms
    longest = "; ".join(
        f"{name} x{count} {ms:.2f}" for name, (count, ms) in
        sorted(by_name.items(), key=lambda kv: -kv[1][1])[:7]
    )
    return statistics.median(totals), longest


def digest(jnp, arrays) -> str:
    import numpy as np

    sha = hashlib.sha256()
    for array in arrays:
        sha.update(np.asarray(array.astype(jnp.float32)).tobytes())
    return sha.hexdigest()[:12]


def forms(decoder) -> dict:
    """{form: (the plain losses' weight in the loss, None for a checkout
    whose `blocked_nll` returns them alone; what stands in for
    `decoder.blocks_again`, None for itself)} of this checkout."""
    if "weights" not in inspect.signature(decoder.blocked_nll).parameters:
        return {"loops": (None, None)}
    return {
        "saved": (0.0, None),
        "saved-no-choice": (0.0, lambda uniform, blocks: 0),
        "saved-fallback": (1e-3, None),
    }


def mean_loss(decoder, jnp, call_weights, plain):
    """loss(hs, head, targets, weights): the calls' means of the weighed
    losses, each at its weight, `plain` of the plain losses beside them."""
    def loss(hs, head, targets, weights):
        total = 0.0
        for h, scale in zip(hs, call_weights):
            if plain is None:
                weighed = weights * decoder.blocked_nll(
                    h, head, targets, jnp.bfloat16
                )
            else:
                weighed, nll = decoder.blocked_nll(
                    h, head, targets, jnp.bfloat16, weights=weights
                )
                weighed = weighed + plain * nll
            total = total + scale * weighed.mean()
        return total
    return loss


def probe(decoder, root: str, args) -> None:
    import jax
    import jax.numpy as jnp

    if args.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        placed = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        ).devices[0])
    choice = getattr(decoder, "blocks_again", None)
    for name, rows, vocab, call_weights in SHAPES:
        if args.shapes and name not in args.shapes:
            continue
        rows, vocab = args.rows or rows, args.vocab or vocab
        keys = jax.random.split(jax.random.PRNGKey(len(name)), 4)
        shapes = (
            tuple(
                jax.ShapeDtypeStruct((rows, HIDDEN), jnp.bfloat16)
                for _ in call_weights
            ),
            jax.ShapeDtypeStruct((HIDDEN, vocab), jnp.float32),
            jax.ShapeDtypeStruct((rows,), jnp.int32),
            jax.ShapeDtypeStruct((rows,), jnp.float32),
        )
        for form, (plain, patched) in forms(decoder).items():
            loss = mean_loss(decoder, jnp, call_weights, plain)
            if patched is not None:
                decoder.blocks_again = patched
            program = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
            try:
                if args.describe:
                    compiled = program.lower(*jax.tree_util.tree_map(
                        lambda s: jax.ShapeDtypeStruct(
                            s.shape, s.dtype, sharding=placed
                        ), shapes,
                    )).compile()
                else:
                    compiled = program.lower(*shapes).compile()
            finally:
                decoder.blocks_again = choice
            text, memory = compiled.as_text(), compiled.memory_analysis()
            held = (
                memory.argument_size_in_bytes + memory.output_size_in_bytes
                + memory.temp_size_in_bytes - memory.alias_size_in_bytes
            )
            line = (
                f"{root} {name} rows={rows} vocab={vocab} {form}: "
                f"held={held / 1e9:.3f}e9 temp="
                f"{memory.temp_size_in_bytes / 1e9:.3f}e9 "
                f"whiles={len(re.findall(r' while[(]', text))} "
                f"conditionals={len(re.findall(r' conditional[(]', text))}"
            )
            if not args.describe:
                hs = tuple(
                    jax.random.normal(key, (rows, HIDDEN), jnp.bfloat16)
                    for key in keys[:len(call_weights)]
                )
                head = 0.02 * jax.random.normal(
                    keys[2], (HIDDEN, vocab), jnp.float32
                )
                targets = jax.random.randint(keys[3], (rows,), 0, vocab)
                weights = jax.nn.softmax(jax.random.normal(
                    keys[3], (4, rows // 4)
                ), axis=0).reshape(rows)
                operands = (hs, head, targets, weights)
                ms, longest = traced(jax, compiled, operands, args.calls)
                value, (d_hs, d_head) = compiled(*operands)
                line += (
                    f" ms={ms:.2f} value={float(value):.6f} digest="
                    f"{digest(jnp, (value, *d_hs, d_head))} | {longest}"
                )
            print(line, flush=True)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--root", action="append")
    parser.add_argument("--shapes", default="")
    parser.add_argument("--calls", type=int, default=5)
    parser.add_argument("--rows", type=int, default=0)
    parser.add_argument("--vocab", type=int, default=0)
    parser.add_argument("--describe", action="store_true")
    args = parser.parse_args()
    args.shapes = [s for s in args.shapes.split(",") if s]
    if args.describe:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        import jax

        jax.config.update("jax_enable_compilation_cache", False)
    for root in args.root or ["."]:
        probe(load(root), root, args)


if __name__ == "__main__":
    main()
