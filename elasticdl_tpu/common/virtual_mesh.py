"""Process environment set-up: the virtual CPU device mesh and the
persistent compile cache.

Multi-chip code paths (DP psum, sharded embeddings, ring attention) are
exercised without TPUs by forcing jax onto a virtual n-device CPU mesh —
the CI strategy SURVEY.md §4 prescribes. This helper is the single place
that builds that environment; tests/conftest.py and the driver's
`dryrun_multichip` re-exec both use it so the flag-patching logic cannot
drift.

`enable_compile_cache` is the single place that decides where compiled
executables persist; every entry point that compiles (the
train / worker / serve mains, the scripts, conftest, `__graft_entry__`,
`chip_smoke.py`) calls it and none names a directory of its own.

Stdlib-only at import: must be importable before jax (env vars have to
be set before the backend initialises).
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def cpu_mesh_env(n_devices: int, base: dict | None = None) -> dict:
    """Return a copy of `base` (default os.environ) patched for an
    n-device virtual CPU mesh.

    Always *overrides* any existing device-count flag rather than keeping
    a stale (possibly smaller) value — a smaller inherited count would
    otherwise leave the child short of devices.
    """
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(rf"{_COUNT_FLAG}=\d+\s*", "", env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (flags + f" {_COUNT_FLAG}={n_devices}").strip()
    # The harness re-spawns workers that compile the same tiny programs
    # over and over; persisting anything that took half a second turns
    # all but the first compile into a disk read (jax's own default
    # threshold is 1s).  Where the cache lives is enable_compile_cache's
    # decision, in each process.
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
    return env


def is_cpu_mesh_env(n_devices: int) -> bool:
    """Whether THIS process's environment already is a virtual CPU mesh
    of at least `n_devices` — read from the environment alone, so a
    caller deciding whether to spawn one never initialises a backend (a
    parent that called `jax.devices()` on a TPU host would hold the
    chips its child needs)."""
    count = re.search(
        rf"{_COUNT_FLAG}=(\d+)", os.environ.get("XLA_FLAGS", "")
    )
    return (
        os.environ.get("JAX_PLATFORMS") == "cpu"
        and count is not None
        and int(count.group(1)) >= n_devices
    )


def apply_cpu_mesh_env(n_devices: int) -> None:
    """Patch os.environ in place (for conftest-style early setup)."""
    os.environ.update(cpu_mesh_env(n_devices))


def compile_cache_dir(flag_dir: str = "") -> str:
    """THE rule for where compiled executables persist:

    1. `JAX_COMPILATION_CACHE_DIR`, when set — the machine's owner chose
       the directory and nothing in this repo overrides it;
    2. else `flag_dir` (--compilation_cache_dir: the shared k8s volume);
    3. else `<checkout>/.jax_cache` (git-ignored).

    A fixed path on purpose: the directory is part of what makes a later
    process find an earlier one's executables, so it never depends on a
    temp dir, a user id, a pid or a clock."""
    return (
        os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or flag_dir
        or os.path.join(_CHECKOUT, ".jax_cache")
    )


def enable_compile_cache(flag_dir: str = "") -> str:
    """Point jax's persistent compilation cache at `compile_cache_dir`
    and return the directory.  Call before the process's first compile
    (jax binds the cache at first use)."""
    import jax

    cache = compile_cache_dir(flag_dir)
    jax.config.update("jax_compilation_cache_dir", cache)
    return cache
