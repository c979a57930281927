"""Test harness configuration.

Must run before anything imports jax: forces an 8-device virtual CPU mesh so
all multi-chip sharding paths (DP psum, sharded embeddings, ring attention)
execute in CI without TPUs — the strategy SURVEY.md §4 prescribes for the
rebuild (the reference's analogue is its in-process multi-role tests with a
mocked k8s layer).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from elasticdl_tpu.common.virtual_mesh import apply_cpu_mesh_env  # noqa: E402

apply_cpu_mesh_env(8)

# Shared persistent XLA-executable cache (subprocess workers spawned by
# cluster drills resolve the same directory in their mains): re-spawned
# processes read compiled executables from disk instead of recompiling
# identical programs.
from elasticdl_tpu.common.virtual_mesh import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()

import gc  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_programs_after_each_module():
    """A compiled CPU executable keeps its code mapped for as long as a
    jit cache holds it, and a test process that runs two hundred modules'
    worth of them reaches the kernel's limit on memory maps
    (`vm.max_map_count`, 65,530: a worker read 56,740 at the end of a
    whole run, and one over the limit dies of a segmentation fault inside
    the next compile).  No module reuses another's programs: the decoder
    models' shared cases (`tests/decoder_cases.py`) are collected in each
    model's own module, and what they build once (`seeded`, `computed`)
    is module-scoped, so one model's programs are dropped here before the
    next model's are built."""
    yield
    import jax

    jax.clear_caches()
    gc.collect()
