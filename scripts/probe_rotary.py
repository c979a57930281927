"""One Laguna attention layer ALONE, with the rotary turn written four ways:
`model_zoo/laguna/laguna.py: GatedGroupedAttention` under the zoo's remat
(`decoder.remat_block`), (2, 8192, 2048) bfloat16 in, value and gradient of
a mean of squares for the parameters and the input, at the cell's two
layers

    window   64 query heads over 8 K/V heads of 128, a band of 512 keys,
             the whole head turned
    full     48 query heads, YaRN over the first 64 of a head's 128 columns

and, for each, `decoder.rotary_turn` as

    halves     `ops/rotary.py: halves_turn`: float32 halves split apart and
               joined again (what every decoder ran until PR 66)
    kernel     `ops/rotary.py: rotary_turn`'s one-pass kernel
    roll       plain `jnp`: `jnp.roll` along a head's columns of the 4-D
               array, x * cos2 + roll(x) * sin2
    flat       plain `jnp`: two whole-row rolls and a select over the flat
               (B, L, H x D) view

`--describe` compiles each for a DESCRIBED v5e (no chip, no times) and
prints what the compiler estimates for the layer OUTSIDE its attention
kernels: the sum of the entry computation's `estimated_cycles` (a model,
not a time: it tells an order, `scripts/probe_head_ce.py`), the part of it
in products (convolution fusions), and the costliest operations that are no
product.  Without it, on the chip: `--calls` traced calls a form, and of
them the device ms a call by scope (`laguna/attn_*`, `laguna/gate`; read
from the compiled text's `op_name`s as the cells' `scopes:` line is), by
kernel name, and the longest operations that are no kernel.

    python3 scripts/probe_rotary.py --describe          # ~2 min, no chip
    chiprun -- python3 scripts/probe_rotary.py          # ~2 chip-minutes

No cell imports this file; on the CPU without `--describe` it runs at
`--length 256` with no device plane to read, and says so.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

LAYERS = {"window": ("sliding_attention", 64), "full": ("full_attention", 48)}
FORMS = ("halves", "kernel", "roll", "flat")


def roll_turn(jnp, tables):
    """x * cos2 + roll(x) * sin2 over the turned columns of the 4-D
    array."""
    def turn(x, inv_freq, factor=1.0, first=0):
        columns = 2 * inv_freq.shape[0]
        cos2, sin2 = (
            t[None, :, None] for t in
            tables(x.shape[1], columns, 0, inv_freq, factor)
        )
        turned = x[..., first:first + columns].astype(jnp.float32)
        half = jnp.arange(columns) < columns // 2
        partner = jnp.where(
            half, jnp.roll(turned, -(columns // 2), -1),
            jnp.roll(turned, columns // 2, -1),
        )
        return jnp.concatenate([
            x[..., :first],
            (turned * cos2 + partner * sin2).astype(x.dtype),
            x[..., first + columns:],
        ], axis=-1)

    return turn


def flat_turn(jnp, tables):
    """Two whole-row rolls and a select over (B, L, H x D)."""
    def turn(x, inv_freq, factor=1.0, first=0):
        batch, length, heads, dim = x.shape
        columns = 2 * inv_freq.shape[0]
        cos2, sin2 = (
            jnp.tile(t, (1, heads))[None] for t in
            tables(length, dim, first, inv_freq, factor)
        )
        flat = x.reshape(batch, length, -1).astype(jnp.float32)
        at = (jnp.arange(heads * dim) % dim - first) % columns
        partner = jnp.where(
            at < columns // 2, jnp.roll(flat, -(columns // 2), -1),
            jnp.roll(flat, columns // 2, -1),
        )
        return (flat * cos2 + partner * sin2).astype(x.dtype).reshape(x.shape)

    return turn


def turn_of(form):
    """What stands in `decoder.rotary_turn`'s place for `form`."""
    import jax.numpy as jnp

    from elasticdl_tpu.ops import rotary

    return {
        "halves": rotary.halves_turn, "kernel": rotary.rotary_turn,
        "roll": roll_turn(jnp, rotary._tables),
        "flat": flat_turn(jnp, rotary._tables),
    }[form]


def layer_program(kind, length, described=None):
    """(the jitted value-and-gradient of one layer, its abstract operands,
    on the device `described` where one is)."""
    import jax
    import jax.numpy as jnp

    from model_zoo.common import decoder
    from model_zoo.laguna import laguna as zoo

    with open(os.path.join(
        os.path.dirname(__file__), "..", "benchmarks", "configs",
        "laguna-xs.2.json",
    )) as f:
        config = json.load(f)
    group, heads = LAYERS[kind]
    layer = decoder.remat_block(zoo.GatedGroupedAttention)(
        config["hidden_size"], heads, config["num_key_value_heads"],
        config["head_dim"],
        config["sliding_window"] if kind == "window" else None,
        zoo.rope_of(config["rope_parameters"][group], config["head_dim"]),
        jnp.bfloat16,
    )

    def shaped(tree):
        return jax.tree.map(lambda t: jax.ShapeDtypeStruct(
            t.shape, t.dtype, sharding=described
        ), tree)

    x = jax.ShapeDtypeStruct((2, length, config["hidden_size"]), jnp.bfloat16)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)["params"]

    def loss(params, x):
        out, _ = layer.apply({"params": params}, x, mutable=True)
        return jnp.square(out.astype(jnp.float32)).mean()

    return (
        jax.jit(jax.value_and_grad(loss, argnums=(0, 1))),
        (shaped(params), shaped(x)),
    )


def entry_estimates(text):
    """[(estimated cycles, instruction, result's first array, end of its
    op_name, whether it is a product)] of the entry computation's
    instructions that carry an estimate."""
    entry = text[text.index("\nENTRY "):]
    rows = []
    for line in entry.split("\n"):
        found = re.match(
            r"\s*(?:ROOT )?%([\w.\-]+) = \(?(\w+\[[\d,]*\])", line
        )
        cycles = re.search(r'"estimated_cycles":"(\d+)"', line)
        if not found or not cycles:
            continue
        path = re.search(r'op_name="([^"]*)"', line)
        rows.append((
            int(cycles.group(1)), found.group(1), found.group(2),
            path.group(1)[-64:] if path else "-",
            "convolution_algorithm_config" in line,
        ))
    return rows


def describe(forms, kinds, length, top):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    from elasticdl_tpu.ops import flash_attention, rotary
    from model_zoo.common import decoder

    one_chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    ).devices[0])
    # the kernels ask the default backend (the CPU here) whether to run
    # interpreted: steer them to Mosaic for the described chip
    flash_attention.use_interpret = rotary.use_interpret = lambda: False
    for kind in kinds:
        for form in forms:
            decoder.rotary_turn = turn_of(form)
            program, operands = layer_program(kind, length, one_chip)
            text = program.lower(*operands).compile().as_text()
            rows = entry_estimates(text)
            whole = sum(row[0] for row in rows)
            products = sum(row[0] for row in rows if row[4])
            print(
                f"{kind:6s} {form:6s}: entry {whole / 1e6:7.2f}e6 estimated "
                f"cycles, products {products / 1e6:6.2f}e6, the rest "
                f"{(whole - products) / 1e6:6.2f}e6; custom calls "
                f"{text.count('custom_call_target=\"tpu_custom_call\"')}",
                flush=True,
            )
            rest = sorted((r for r in rows if not r[4]), reverse=True)
            for cycles, name, result, path, _ in rest[:top]:
                print(f"    {cycles / 1e6:6.2f}e6  {name:26s} {result:24s} "
                      f"{path}")


def on_chip(forms, kinds, length, calls, top):
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.common import profiler, programs
    from model_zoo.common import decoder

    print(f"device {jax.devices()[0].device_kind}  length {length}  "
          f"calls {calls}")
    for kind in kinds:
        for form in forms:
            decoder.rotary_turn = turn_of(form)
            program, shapes = layer_program(kind, length)
            keys = iter(jax.random.split(jax.random.PRNGKey(0), 64))
            operands = jax.tree.map(
                lambda t: 0.05 * jax.random.normal(
                    next(keys), t.shape, jnp.float32
                ).astype(t.dtype), shapes,
            )
            compiled = program.lower(*operands).compile()
            table = programs.parse_scope_table(compiled.as_text())
            value, _ = jax.block_until_ready(compiled(*operands))
            with tempfile.TemporaryDirectory() as trace_dir:
                with jax.profiler.trace(trace_dir):
                    for _ in range(calls):
                        jax.block_until_ready(compiled(*operands))
                seconds = profiler.xla_op_seconds(trace_dir)
            report(profiler, f"{kind:6s} {form:6s}", float(value), seconds,
                   table, calls, top)


def report(profiler, head, value, seconds, table, calls, top):
    if not seconds:
        print(f"{head}: value {value:.6g}; no device plane (not a chip)")
        return
    by_scope = profiler.scope_summary(seconds, table, calls)
    whole = sum(row["ms_per_step"] for row in by_scope.values())
    scopes = "  ".join(
        f"{scope} {row['ms_per_step']:.2f}" for scope, row in by_scope.items()
    )
    print(f"{head}: {whole:7.2f} ms a call  value {value:.6g}  |  {scopes}")
    kernels, longest = collections.defaultdict(float), []
    for text, s in seconds.items():
        row = table.get(profiler.instruction_name(text))
        if row is None or row.container:
            continue
        # a kernel's event (named for its kernel), not a fusion that reads
        # a kernel's result
        name = profiler.instruction_name(text).split(".")[0]
        if re.fullmatch(r"\w+_attention_\w+|rotary_turn\w*", name):
            kernels[name] += 1e3 * s / calls
        else:
            longest.append((s, text, row))
    print("    kernels: " + "  ".join(
        f"{name} {ms:.2f}" for name, ms in sorted(kernels.items())
    ))
    for s, text, row in sorted(longest, reverse=True)[:top]:
        print(f"    {1e3 * s / calls:7.3f} ms  {row.phase:8s} {text[:120]}")


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--describe", action="store_true")
    parser.add_argument("--forms", default=",".join(FORMS))
    parser.add_argument("--layers", default=",".join(LAYERS))
    parser.add_argument("--length", type=int, default=8192)
    parser.add_argument("--calls", type=int, default=3)
    parser.add_argument("--top", type=int, default=8,
                        help="costliest operations printed a case")
    args = parser.parse_args(argv)
    forms, kinds = args.forms.split(","), args.layers.split(",")
    if args.describe:
        describe(forms, kinds, args.length, args.top)
    else:
        on_chip(forms, kinds, args.length, args.calls, args.top)


if __name__ == "__main__":
    main()
