"""Records the small trace that test_trace_reduce.py reads.

    chiprun -- python benchmarks/tests/data/record_trace.py

Three steps of a two-op program (matmul, then a scatter-add into a small
table) with a 20 ms host sleep between steps, inside `bench:window`, each
step inside `bench:step`.  Prints the planes, lines and a few events so a
reader of the trace code can see how the chip names things, and leaves the
`.xplane.pb` under `chiprun_out/recorded_trace/`.
"""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData


def main() -> int:
    out = os.path.join("chiprun_out", "recorded_trace")
    shutil.rmtree(out, ignore_errors=True)
    print("devices", jax.devices(), flush=True)

    @jax.jit
    def step(table, x, ids):
        y = x @ x
        return table.at[ids].add(y[: ids.shape[0], :16]), y.sum()

    table = jnp.zeros((1 << 16, 16), jnp.float32)
    x = jnp.ones((512, 512), jnp.bfloat16)
    ids = jnp.arange(256, dtype=jnp.int32) * 7
    table, s = step(table, x, ids)
    s.block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench:window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench:step"):
                table, s = step(table, x, ids)
                s.block_until_ready()
            with jax.profiler.TraceAnnotation("bench:sleep"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "plugins/profile/*/*.xplane.pb"))[0]
    print(path, os.path.getsize(path), "bytes")
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events))
            for event in events[:6]:
                stats = {}
                try:
                    stats = {k: str(v)[:80] for k, v in event.stats}
                except Exception as exc:  # print what the API gives
                    stats = {"stats_error": repr(exc)}
                print("    EV", repr(event.name[:160]), event.start_ns,
                      event.duration_ns, stats)
    shutil.copy(path, os.path.join("chiprun_out", "small.xplane.pb"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
