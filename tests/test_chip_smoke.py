"""Bring-up guards that tier-1 can pin without a chip.  chip_smoke.py
itself only passes on a TPU (the driver runs it there): here, that it
REFUSES to pass anywhere else; that nothing is generated at import; that
process pods are pinned one chip each; that the native scanner's fallback
is loud."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_prints_preflight_and_fails_without_accelerator():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, env=env, timeout=300, cwd=REPO,
    )
    assert res.returncode != 0, res.stdout
    lines = res.stdout.strip().splitlines()
    assert len(lines) == 1 and lines[0].startswith(
        "[chip_smoke] preflight:"
    ), res.stdout
    assert "platform=cpu" in lines[0] and "compile_cache=" in lines[0]
    # no result object: nothing a driver could mistake for a pass
    assert '"ok"' not in res.stdout
    assert "no CPU continuation" in res.stderr


def test_importing_proto_with_stale_pb2_spawns_nothing(tmp_path):
    """The checked-in *_pb2.py are the artifacts.  A tree whose mtimes
    say the .proto is newer (any non-git copy) must import them as they
    are: no protoc, no generator script, no subprocess at all."""
    import shutil

    tree = tmp_path / "tree"
    shutil.copytree(
        os.path.join(REPO, "elasticdl_tpu", "proto"),
        tree / "elasticdl_tpu" / "proto",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    (tree / "elasticdl_tpu" / "__init__.py").write_text("")
    proto_dir = tree / "elasticdl_tpu" / "proto"
    for name in ("elasticdl_pb2.py", "serving_pb2.py"):
        os.utime(proto_dir / name, (1, 1))  # 1970: older than any .proto
    generated = ("elasticdl_pb2.py", "serving_pb2.py")
    before = {name: (proto_dir / name).read_bytes() for name in generated}
    prog = (
        "import subprocess, os, sys\n"
        "def refuse(*a, **k):\n"
        "    raise AssertionError(f'import spawned a process: {a} {k}')\n"
        "subprocess.Popen = subprocess.run = os.system = refuse\n"
        "from elasticdl_tpu.proto import elasticdl_pb2, serving_pb2\n"
        "assert elasticdl_pb2.Task and serving_pb2.PredictRequest\n"
    )
    res = subprocess.run(
        [sys.executable, "-c", prog],
        capture_output=True, text=True, cwd=tree, timeout=120,
        env=dict(os.environ, PYTHONPATH=str(tree)),
    )
    assert res.returncode == 0, res.stderr
    for name in generated:
        assert (proto_dir / name).read_bytes() == before[name], (
            f"{name} was rewritten by the import"
        )


def test_process_pods_are_pinned_to_one_chip_each(monkeypatch):
    """On a TPU host every worker child must own exactly one chip (a
    child with the parent's environment would claim them all).  The chip
    list is faked; what is pinned is the environment each child sees."""
    import json
    import time

    import pytest

    from elasticdl_tpu.common import k8s_client
    from elasticdl_tpu.common.constants import PodType
    from elasticdl_tpu.common.k8s_client import PodSpec, ProcessK8sClient

    monkeypatch.setattr(k8s_client, "host_tpu_chips", lambda: [0, 1, 2, 3])
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    dump_env = [
        sys.executable, "-c",
        "import os, json, sys, time\n"
        "print(json.dumps({k: v for k, v in os.environ.items()\n"
        "    if k.startswith(('TPU_', 'CLOUD_TPU'))}), flush=True)\n"
        "time.sleep(600)\n",  # hold the chip until killed
    ]
    k8s = ProcessK8sClient()
    try:
        for i in range(4):
            k8s.create_pod(PodSpec(
                name=f"w{i}", pod_type=PodType.WORKER, worker_id=i,
                command=dump_env,
            ))
        k8s.create_pod(PodSpec(
            name="master", pod_type=PodType.MASTER, command=dump_env,
        ))
        with pytest.raises(RuntimeError, match="no free TPU chip"):
            k8s.create_pod(PodSpec(
                name="w4", pod_type=PodType.WORKER, worker_id=4,
                command=dump_env,
            ))

        def env_of(name):
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                out = k8s.pod_output(name)
                if out.strip():
                    return json.loads(out.splitlines()[0])
                time.sleep(0.05)
            raise AssertionError(f"{name} printed nothing")

        envs = [env_of(f"w{i}") for i in range(4)]
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
        assert [e["CLOUD_TPU_TASK_ID"] for e in envs] == ["0", "1", "2", "3"]
        for e in envs:
            assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
            assert e["TPU_PROCESS_BOUNDS"] == "2,2,1"
            assert len(e["TPU_PROCESS_ADDRESSES"].split(",")) == 4
        assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
        # the master never needs a chip and keeps the host's environment
        assert "TPU_VISIBLE_CHIPS" not in env_of("master")

        # a dead worker's chip goes to its replacement
        k8s.kill_pod("w2")
        k8s.procs["w2"].wait(timeout=30)
        k8s.create_pod(PodSpec(
            name="w5", pod_type=PodType.SERVING, worker_id=5,
            command=dump_env,
        ))
        replica = env_of("w5")
        assert replica["TPU_VISIBLE_CHIPS"] == "2"
        assert replica["TPU_PROCESS_BOUNDS"] == "1,1,1"  # stands alone
    finally:
        k8s.stop()


def test_native_scanner_fallback_is_logged_once_at_error(monkeypatch):
    """A missing librecordio.so costs an order of magnitude of reader
    throughput: the fallback must be said once, loudly, not taken in
    silence."""
    import logging

    from elasticdl_tpu.data import native_io

    records = []
    handler = logging.Handler()
    handler.emit = records.append
    native_io.logger.addHandler(handler)
    try:
        monkeypatch.setattr(native_io, "_lib", None)
        monkeypatch.setattr(native_io, "_fallback_logged", False)
        monkeypatch.setattr(native_io, "_SO_PATH", "/nonexistent/lib.so")
        monkeypatch.setattr(native_io, "_try_build", lambda: None)
        assert not native_io.available()
        assert not native_io.available()
    finally:
        native_io.logger.removeHandler(handler)
    assert [r.levelno for r in records] == [logging.ERROR]
    assert "pure-Python" in records[0].getMessage()
