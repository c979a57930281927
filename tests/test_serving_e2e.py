"""Serving acceptance: an exported/checkpointed zoo model serves gRPC
predict traffic end-to-end on CPU — mixed-size concurrent requests
micro-batched into precompiled buckets (no recompiles), a mid-traffic
checkpoint hot-swap with zero failed requests, and corrupt/fault-injected
reloads rejected while serving continues on the previous params."""

import os
import threading
import time

import grpc
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.common import faults
from elasticdl_tpu.common.faults import FaultRegistry, FaultSpec
from elasticdl_tpu.common.model_handler import get_model_spec
from elasticdl_tpu.common.resilience import default_policy
from elasticdl_tpu.common.save_utils import CheckpointSaver
from elasticdl_tpu.proto import serving_pb2 as spb
from elasticdl_tpu.proto.service import ServingStub
from elasticdl_tpu.serving.batcher import DynamicBatcher
from elasticdl_tpu.serving.engine import ServingEngine
from elasticdl_tpu.serving.reloader import CheckpointReloader
from elasticdl_tpu.serving.server import (
    ServingServer,
    from_tensor_proto,
    make_predict_request,
)
from elasticdl_tpu.worker.trainer import TrainState

MODEL_DEF = "mnist.mnist_functional_api.custom_model"
BUCKETS = (2, 8)
# closed-loop clients of the mixed-traffic test, at most max(BUCKETS)
# rows a request
CLIENTS = 6
# a bound on a hang, never a term of an outcome
HANG_BOUND_S = 60.0


class _Stack:
    """One serving deployment over a live checkpoint dir."""

    def __init__(self, tmp_path):
        self.spec = get_model_spec("model_zoo", MODEL_DEF)
        self.sample = np.random.RandomState(0).rand(2, 784).astype(
            np.float32
        )
        variables = dict(
            self.spec.model.init(jax.random.PRNGKey(0), self.sample)
        )
        self.params = {"params": variables.pop("params")}
        self.model_state = variables
        self.ckpt_dir = str(tmp_path / "ckpts")
        self.saver = CheckpointSaver(self.ckpt_dir, async_save=False)
        self.save_step(1)
        self.engine = ServingEngine.from_checkpoint(
            self.ckpt_dir, self.spec, self.sample, buckets=BUCKETS
        )
        # The queue admits every client's largest request at once.  At
        # the default bound (4 x 8 = 32 rows) the six clients' 48 could
        # not all wait out a stall (the swap's restore holds the CPU
        # backend's execution lock), and which request was shed
        # OVERLOADED was a matter of timing: 1 request in ~1,900.
        self.batcher = DynamicBatcher(
            self.engine, max_latency_s=0.005,
            max_queue_rows=CLIENTS * max(BUCKETS),
        )
        self.reloader = CheckpointReloader(
            self.engine, self.ckpt_dir, poll_interval_s=0.05
        )
        self.server = ServingServer(self.engine, self.batcher,
                                    self.reloader)
        port = self.server.start(0)
        self.channel = grpc.insecure_channel(f"localhost:{port}")
        self.stub = ServingStub(self.channel, retry_policy=default_policy())

    def save_step(self, step, scale=1.0):
        params = jax.tree.map(lambda a: a * scale, self.params)
        state = TrainState(
            step=jnp.asarray(step, jnp.int32), params=params,
            opt_state=self.spec.optimizer.init(params),
            model_state=self.model_state,
        )
        self.saver.save(state, force=True)
        self.saver.wait_until_finished()

    def wait_for(self, predicate, timeout=HANG_BOUND_S):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if predicate():
                return True
            time.sleep(0.05)
        return False

    def close(self):
        self.channel.close()
        self.server.stop()
        self.saver.close()


@pytest.fixture(scope="module")
def stack(tmp_path_factory):
    s = _Stack(tmp_path_factory.mktemp("serving_e2e"))
    yield s
    s.close()


def test_mixed_concurrent_traffic_with_midstream_hot_swap(stack):
    """The headline guarantee: concurrent clients sending mixed batch
    sizes through gRPC, a checkpoint swap landing mid-traffic — every
    request succeeds, no bucket recompiles, and responses attribute
    their model step."""
    results, errors, lock = [], [], threading.Lock()
    # Clients send at least 12 requests each, then KEEP sending until
    # the post-swap generation has been OBSERVED in a response: no
    # deadline decides what they saw.  `stop` is set only when the
    # reloader never adopts step 2, and then the test fails on that.
    saw_swap = threading.Event()
    stop = threading.Event()

    def client(seed):
        rng = np.random.RandomState(seed)
        sent = 0
        while not stop.is_set():
            sent += 1
            rows = int(rng.choice([1, 2, 3, 5, 8]))
            x = rng.rand(rows, 784).astype(np.float32)
            try:
                resp = stack.stub.predict(make_predict_request(x))
            except Exception as exc:
                with lock:
                    errors.append(repr(exc))
                return
            preds = (
                from_tensor_proto(resp.predictions)
                if resp.code == spb.SERVING_OK else None
            )
            with lock:
                results.append((resp.code, resp.model_step, rows, preds))
            if resp.code == spb.SERVING_OK and resp.model_step == 2:
                saw_swap.set()
            if sent >= 12 and saw_swap.is_set():
                return

    threads = [
        threading.Thread(target=client, args=(i,), daemon=True)
        for i in range(CLIENTS)
    ]
    for t in threads:
        t.start()
    # land a new checkpoint while traffic is in flight
    stack.save_step(2, scale=2.0)
    swapped = stack.wait_for(lambda: stack.engine.step == 2)
    if not swapped:
        stop.set()
    for t in threads:
        t.join(HANG_BOUND_S)
    assert swapped, (
        f"the reloader never adopted step 2 (still serving step "
        f"{stack.engine.step}; last error: {stack.reloader.last_error})"
    )
    assert not any(t.is_alive() for t in threads)
    assert not errors

    codes = [code for code, _, _, _ in results]
    assert codes == [spb.SERVING_OK] * len(codes)  # ZERO failed requests
    for _, step, rows, preds in results:
        assert step in (1, 2)  # every response names its generation
        assert preds.shape == (rows, 10)
    assert {step for _, step, _, _ in results} >= {2}
    # the no-recompile property across sizes AND across the swap
    assert stack.engine.compile_count <= len(BUCKETS)
    assert stack.engine.swap_count == 1


def test_health_reports_serving_state(stack):
    health = stack.stub.health(spb.HealthRequest())
    assert health.serving
    assert list(health.buckets) == list(BUCKETS)
    assert health.compile_count <= len(BUCKETS)
    assert health.model_step == 2
    metrics = {m.name: m.value for m in health.metrics}
    assert metrics["ok_rows"] > 0
    assert 0.0 < metrics["batch_fill_ratio"] <= 1.0
    assert metrics["latency_p99_s"] > 0.0


def test_corrupt_checkpoint_rejected_serving_continues(stack):
    """Bit-flip the newest step on disk: the manifest gate rejects it,
    the engine keeps serving the previous generation, and the bad step
    is never retried."""
    served_before = stack.engine.step
    rejected_before = stack.reloader.rejected_count
    # Hold the poll loop off step 3 until the bit-flip has landed: the
    # reloader's never-retry set doubles as a gate, otherwise a poll
    # between save and corruption adopts the still-intact step and the
    # rejection never happens (a 50ms poll vs a few-ms corruption
    # window — loses under load).
    stack.reloader._rejected_steps.add(3)
    stack.save_step(3, scale=3.0)
    victim = None
    step_dir = os.path.join(stack.ckpt_dir, "3")
    for root, _, files in os.walk(step_dir):
        for name in files:
            path = os.path.join(root, name)
            if os.path.getsize(path) > 100:
                victim = path
                break
        if victim:
            break
    assert victim, f"no corruptible file under {step_dir}"
    with open(victim, "r+b") as f:
        f.seek(40)
        f.write(b"\xde\xad\xbe\xef")
    stack.reloader._rejected_steps.discard(3)  # release the gate
    assert stack.wait_for(
        lambda: stack.reloader.rejected_count > rejected_before
    )
    assert stack.engine.step == served_before
    # the bit flip may land in an array shard (caught by the manifest
    # integrity gate) or in checkpoint metadata (caught earlier, inside
    # the orbax read) depending on directory walk order — either way the
    # reload must record WHY it rejected the step
    assert stack.reloader.last_error
    resp = stack.stub.predict(
        make_predict_request(stack.sample)
    )
    assert resp.code == spb.SERVING_OK
    assert resp.model_step == served_before
    # the rejection is terminal for that step: no retry loop
    count_after = stack.reloader.rejected_count
    time.sleep(0.3)
    assert stack.reloader.rejected_count == count_after


def test_fault_injected_reload_keeps_old_params(stack):
    """Seeded injection at POINT_SERVING_RELOAD (the satellite contract):
    the reload attempt fails mid-flight, the server keeps answering on
    the params it already has."""
    served_before = stack.engine.step
    rejected_before = stack.reloader.rejected_count
    faults.install(FaultRegistry(
        [FaultSpec(faults.POINT_SERVING_RELOAD, 0, "raise")]
    ))
    try:
        stack.save_step(5, scale=5.0)
        assert stack.wait_for(
            lambda: stack.reloader.rejected_count > rejected_before
        )
        assert stack.engine.step == served_before
        resp = stack.stub.predict(make_predict_request(stack.sample))
        assert resp.code == spb.SERVING_OK
        assert resp.model_step == served_before
    finally:
        faults.uninstall()
    # with the registry gone, a FRESH step reloads fine (step 5 was
    # terminally rejected, step 6 proves the reloader recovered)
    stack.save_step(6, scale=6.0)
    assert stack.wait_for(lambda: stack.engine.step == 6)
    resp = stack.stub.predict(make_predict_request(stack.sample))
    assert resp.code == spb.SERVING_OK
    assert resp.model_step == 6
    assert stack.engine.compile_count <= len(BUCKETS)


def test_invalid_wire_request_gets_in_band_error(stack):
    request = spb.PredictRequest()
    named = request.inputs.add()
    named.name = "features"
    named.tensor.dtype = "float32"
    named.tensor.shape.extend([1, 784])
    named.tensor.data = b"short"  # truncated payload
    resp = stack.stub.predict(request)
    assert resp.code == spb.SERVING_INVALID
    assert "bytes" in resp.error


def test_cli_serve_builds_stack_from_export(tmp_path):
    """`elasticdl serve --export_dir ...` wiring: parser -> api
    assembly -> in-process predict round trip."""
    from elasticdl_tpu.client.api import build_serving_server
    from elasticdl_tpu.client.main import _build_parser
    from elasticdl_tpu.common.export import export_model
    from elasticdl_tpu.proto.service import InProcessServingClient

    spec = get_model_spec("model_zoo", MODEL_DEF)
    x = np.random.RandomState(3).rand(2, 784).astype(np.float32)
    variables = dict(spec.model.init(jax.random.PRNGKey(0), x))
    params = {"params": variables.pop("params")}
    state = TrainState(
        step=jnp.asarray(4, jnp.int32), params=params,
        opt_state=spec.optimizer.init(params), model_state=variables,
    )
    export_dir = str(tmp_path / "export")
    export_model(state, spec, export_dir, sample_features=x)

    args = _build_parser().parse_args([
        "serve",
        "--model_zoo", "model_zoo",
        "--model_def", MODEL_DEF,
        "--export_dir", export_dir,
        "--batch_buckets", "2,4",
        "--max_batch_latency_ms", "2",
    ])
    server = build_serving_server(args)
    try:
        client = InProcessServingClient(server.servicer)
        resp = client.predict(make_predict_request(x))
        assert resp.code == spb.SERVING_OK
        assert resp.model_step == 4
        assert from_tensor_proto(resp.predictions).shape == (2, 10)
        health = client.health(spb.HealthRequest())
        assert list(health.buckets) == [2, 4]
        assert health.compile_count <= 2
    finally:
        server._batcher.shutdown()
