"""The one compile-cache rule (common/virtual_mesh.compile_cache_dir) and
the --compilation_cache_dir plumbing.

A relaunched worker that finds the train-step executable in the cache
skips the recompile — the dominant chunk of elastic recovery time
(SURVEY.md hard part 1's AOT mitigation)."""

import os

import jax
import pytest

from elasticdl_tpu.common import args as args_lib
from elasticdl_tpu.common.virtual_mesh import (
    compile_cache_dir,
    enable_compile_cache,
)
from elasticdl_tpu.master.main import Master

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_var_wins_over_flag_and_default(monkeypatch, restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    assert compile_cache_dir() == "/x"
    assert compile_cache_dir("/y") == "/x"
    assert enable_compile_cache("/y") == "/x"
    assert jax.config.jax_compilation_cache_dir == "/x"
    # no code path rewrites what the machine's owner set
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/x"


def test_flag_applies_when_env_unset(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache("/y") == "/y"
    assert jax.config.jax_compilation_cache_dir == "/y"
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ


def test_default_is_fixed_path_inside_checkout(
    monkeypatch, restore_cache_config
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    resolved = enable_compile_cache()
    # the directory is part of what makes a later process hit: what the
    # rule adds to the checkout is a constant — no temp dir, user, pid or
    # time
    assert resolved == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == resolved
    assert resolved == compile_cache_dir()


def test_flag_reaches_worker_pod_command(tmp_path):
    from elasticdl_tpu.data.record_io import write_tfrecords

    data_dir = tmp_path / "data"
    data_dir.mkdir()
    write_tfrecords(
        str(data_dir / "d.tfrecord"), (bytes(8) for _ in range(16))
    )
    cache = str(tmp_path / "xla-cache")
    args = args_lib.parse_master_args(
        [
            "--training_data", str(data_dir),
            "--compilation_cache_dir", cache,
            "--use_fake_k8s", "true",
        ]
    )
    master = Master(args)
    cmd = master._worker_command(worker_id=0)
    joined = " ".join(cmd)
    assert "--compilation_cache_dir" in joined and cache in joined


def test_relaunched_process_reuses_cached_executable(tmp_path):
    """Two fresh OS processes compile the same jitted step against the
    same cache dir; the second must hit the cache (observable via jax's
    cache-miss metric: zero misses on the warm run)."""
    import subprocess
    import sys

    cache = str(tmp_path / "xla-cache")
    prog = """
import sys
sys.path.insert(0, {root!r})
import jax; jax.config.update("jax_platforms", "cpu")
from elasticdl_tpu.common.virtual_mesh import enable_compile_cache
assert enable_compile_cache({cache!r}) == {cache!r}
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
import jax.numpy as jnp
from jax._src import monitoring
misses = []
monitoring.register_event_listener(
    lambda e, **kw: misses.append(e)
    if "cache_miss" in e else None
)
f = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())
f(jnp.ones((64, 64))).block_until_ready()
print("MISSES", sum(1 for e in misses if "cache_miss" in e))
"""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    outs = []
    for _ in range(2):
        res = subprocess.run(
            [sys.executable, "-c", prog.format(root=root, cache=cache)],
            capture_output=True, text=True, env=env, timeout=120,
        )
        assert res.returncode == 0, res.stderr
        outs.append(res.stdout)
    # cold: at least one compile-cache miss; warm: executable loaded
    # (miss-event count per compile varies by jax version — 0 is the
    # only number that proves the cache hit)
    assert "MISSES 0" not in outs[0], outs[0]
    assert "MISSES 0" in outs[1], outs[1]


def test_volume_parsing_and_pod_propagation(tmp_path):
    """--volume parses the reference syntax and the pod manager stamps
    the volumes into every worker PodSpec (the shared cache volume rides
    this path on a real cluster)."""
    from elasticdl_tpu.common.k8s_client import FakeK8sClient, parse_volumes
    from elasticdl_tpu.data.record_io import write_tfrecords

    assert parse_volumes("") == []
    vols = parse_volumes(
        "host_path=/mnt/cache,mount_path=/cache;"
        "claim_name=data-pvc,mount_path=/data"
    )
    assert vols == [
        {"host_path": "/mnt/cache", "mount_path": "/cache"},
        {"claim_name": "data-pvc", "mount_path": "/data"},
    ]
    import pytest

    with pytest.raises(ValueError, match="mount_path"):
        parse_volumes("host_path=/only")

    data_dir = tmp_path / "data"
    data_dir.mkdir()
    write_tfrecords(
        str(data_dir / "d.tfrecord"), (bytes(8) for _ in range(16))
    )
    args = args_lib.parse_master_args(
        [
            "--training_data", str(data_dir),
            "--volume", "host_path=/mnt/cache,mount_path=/cache",
            "--use_fake_k8s", "true",
        ]
    )
    k8s = FakeK8sClient()
    master = Master(args, k8s_client=k8s)
    master.pod_manager.start()
    worker_specs = [
        s for s in k8s.create_calls if s.pod_type == "worker"
    ]
    assert worker_specs
    for spec in worker_specs:
        assert spec.volumes == [
            {"host_path": "/mnt/cache", "mount_path": "/cache"}
        ]
    master.pod_manager.stop()
