"""Kubernetes client abstraction + in-memory fake.

Parity: reference python/common/k8s_client.py (SURVEY.md C4): the master
creates/watches/deletes worker pods directly through the Kubernetes API (no
operator/CRD).  The fake records calls and lets tests inject synthetic pod
events — the reference's own test strategy for failure handling
(SURVEY.md §4.3).

The real client is gated: the `kubernetes` package is not installed in this
environment, so `K8sClient` raises with instructions at construction unless
it is.  TPU-specific concern carried in pod specs: workers are provisioned
per TPU *slice* (a preempted host kills the slice's ICI collectives, so the
restart unit is the slice — SURVEY.md §7 hard part 3).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from elasticdl_tpu.common.constants import PodStatus, PodType
from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger(__name__)

# (pod_name, phase, pod_address, exit_code) — address is "" until the
# cluster layer knows the pod's reachable IP (real k8s can emit RUNNING
# before the IP is assigned; workers self-report via keep_alive to close
# that gap); exit_code is the container's exit status when phase is
# terminal (None when unknown), letting the pod manager tell intentional
# self-restarts from crashes.
EventCallback = Callable[[str, str, str, Optional[int]], None]


@dataclass
class PodSpec:
    name: str
    pod_type: str  # "worker" | "master"
    worker_id: int = -1
    image: str = ""
    command: List[str] = field(default_factory=list)
    resources: Dict[str, str] = field(default_factory=dict)
    priority_class: str = ""
    labels: Dict[str, str] = field(default_factory=dict)
    # parsed --volume entries (parse_volumes): each a dict with
    # "mount_path" plus one of "host_path" / "claim_name"
    volumes: List[Dict[str, str]] = field(default_factory=list)


def parse_volumes(volume: str) -> List[Dict[str, str]]:
    """Parse the --volume flag (reference syntax, SURVEY.md C21):
    `host_path=/a,mount_path=/b` or `claim_name=pvc,mount_path=/b`;
    multiple volumes separated by `;`.  The shared --compilation_cache_dir
    volume rides this flag like any other mount."""
    out: List[Dict[str, str]] = []
    for part in (volume or "").split(";"):
        part = part.strip()
        if not part:
            continue
        entry: Dict[str, str] = {}
        for kv in part.split(","):
            kv = kv.strip()
            if not kv:
                continue
            if "=" not in kv:
                raise ValueError(
                    f"--volume entry {kv!r} is not key=value "
                    "(expected host_path=/a,mount_path=/b or "
                    "claim_name=pvc,mount_path=/b)"
                )
            key, _, value = kv.partition("=")
            key, value = key.strip(), value.strip()
            if key not in ("host_path", "claim_name", "mount_path"):
                raise ValueError(
                    f"--volume key {key!r} not supported (host_path, "
                    "claim_name, mount_path)"
                )
            if not value:
                raise ValueError(f"--volume key {key!r} has empty value")
            entry[key] = value
        if "host_path" in entry and "claim_name" in entry:
            raise ValueError(
                f"--volume entry {part!r} sets both host_path and "
                "claim_name; pick one source"
            )
        if "mount_path" not in entry or not (
            "host_path" in entry or "claim_name" in entry
        ):
            raise ValueError(
                f"--volume entry {part!r} needs mount_path plus "
                "host_path or claim_name"
            )
        out.append(entry)
    return out


class AbstractK8sClient:
    def create_pod(self, spec: PodSpec) -> None:
        raise NotImplementedError

    def create_service(
        self, name: str, selector: Dict[str, str], port: int
    ) -> None:
        """Expose pods matching `selector` at DNS name `name`:`port` —
        worker pods reach the master via `{job_name}-master:{port}`, which
        only resolves if a Service fronts the master pod."""
        raise NotImplementedError

    def delete_pod(self, name: str) -> None:
        raise NotImplementedError

    def get_pod_phase(self, name: str) -> str:
        raise NotImplementedError

    def start_watch(self, callback: EventCallback) -> None:
        raise NotImplementedError

    def list_pods(self) -> List[Tuple[str, int, str, str]]:
        """Existing pods of this job as (pod_name, worker_id, phase,
        address).  A replacement master pod calls this to ADOPT live
        workers instead of double-launching them (master fault
        tolerance)."""
        return []

    def get_pod_labels(self, name: str) -> Dict[str, str]:
        """Labels stamped on the pod at creation (k8s metadata).  Used by
        a replacement master to recover exact slice-group identity during
        adoption; clients without label storage may return {} (the pod
        manager falls back to packed groups)."""
        return {}

    def master_host(self, job_name: str) -> str:
        """Hostname worker pods use to reach the master.  Real clusters
        resolve the master Service's DNS name; process-backed local
        clusters are loopback."""
        return f"{job_name}-master"


class FakeK8sClient(AbstractK8sClient):
    """In-memory cluster: pods transition Pending -> Running on create;
    tests drive failures/preemptions via `emit`."""

    def __init__(self):
        self._lock = threading.Lock()
        self.pods: Dict[str, PodSpec] = {}
        self.phases: Dict[str, str] = {}
        self.create_calls: List[PodSpec] = []
        self.delete_calls: List[str] = []
        self._callback: Optional[EventCallback] = None

    def create_pod(self, spec: PodSpec) -> None:
        with self._lock:
            self.pods[spec.name] = spec
            self.phases[spec.name] = PodStatus.PENDING
            self.create_calls.append(spec)
        self._emit(spec.name, PodStatus.PENDING)
        with self._lock:
            self.phases[spec.name] = PodStatus.RUNNING
        # Fabricated per-pod address, mirroring pod.status.pod_ip.
        self._emit(spec.name, PodStatus.RUNNING, self._pod_address(spec))

    @staticmethod
    def _pod_address(spec: PodSpec) -> str:
        """One formula for the fabricated pod IP — create_pod events and
        list_pods (master adoption) must agree on it."""
        return f"10.0.0.{spec.worker_id + 1}"

    def create_service(
        self, name: str, selector: Dict[str, str], port: int
    ) -> None:
        with self._lock:
            self.services = getattr(self, "services", {})
            self.services[name] = {"selector": selector, "port": port}

    def delete_pod(self, name: str) -> None:
        with self._lock:
            self.delete_calls.append(name)
            if name not in self.pods:
                return
            self.phases[name] = PodStatus.DELETED
        self._emit(name, PodStatus.DELETED)

    def get_pod_phase(self, name: str) -> str:
        with self._lock:
            return self.phases.get(name, PodStatus.UNKNOWN)

    def get_pod_labels(self, name: str):
        with self._lock:
            spec = self.pods.get(name)
            return dict(spec.labels) if spec is not None else {}

    def list_pods(self):
        with self._lock:
            return [
                (
                    name,
                    spec.worker_id,
                    self.phases.get(name, PodStatus.UNKNOWN),
                    self._pod_address(spec),
                )
                for name, spec in self.pods.items()
                if spec.pod_type == PodType.WORKER
            ]

    def start_watch(self, callback: EventCallback) -> None:
        self._callback = callback

    # ---- test hooks ----------------------------------------------------

    def emit(self, pod_name: str, phase: str, address: str = "",
             exit_code=None):
        """Inject a synthetic pod event (e.g. preemption -> FAILED)."""
        with self._lock:
            self.phases[pod_name] = phase
        self._emit(pod_name, phase, address, exit_code)

    def _emit(self, name: str, phase: str, address: str = "",
              exit_code=None):
        if self._callback is not None:
            self._callback(name, phase, address, exit_code)


# libtpu's default port for the runtime-to-runtime channel between the
# processes of one host; chip c's process listens on base + c.
_TPU_PROCESS_BASE_PORT = 8476


def host_tpu_chips() -> List[int]:
    """The TPU chips this host exposes, as libtpu numbers them: the
    numeric entries of /dev/vfio (read off a v5e host).  Listing /dev
    keeps the caller off any backend — the master must never hold a
    chip its workers need."""
    import os

    try:
        names = os.listdir("/dev/vfio")
    except OSError:
        return []
    return sorted(int(n) for n in names if n.isdigit())


class ProcessK8sClient(AbstractK8sClient):
    """Local 'cluster' whose pods are OS subprocesses.

    The e2e equivalent of the reference's minikube CI jobs (SURVEY.md
    §4.4) without Kubernetes: `create_pod` spawns the pod command as a
    child process, a monitor thread maps process exit to pod phases
    (rc==0 -> Succeeded, else Failed), and `delete_pod` terminates the
    child.  Every pod's address is loopback, so the full cluster path —
    master entry point, worker entry point, rendezvous-served coordinator
    address, jax.distributed bootstrap — runs unmodified on one machine."""

    def __init__(self, extra_env: Optional[Dict[str, str]] = None):
        self._lock = threading.Lock()
        self.pods: Dict[str, PodSpec] = {}
        self.procs: Dict[str, "subprocess.Popen"] = {}
        self.phases: Dict[str, str] = {}
        self.create_calls: List[PodSpec] = []
        self._output: Dict[str, List[bytes]] = {}
        self._chip_of: Dict[str, int] = {}   # pod name -> pinned TPU chip
        self._extra_env = dict(extra_env or {})
        self._callback: Optional[EventCallback] = None
        self._stop = threading.Event()
        self._monitor: Optional[threading.Thread] = None

    def master_host(self, job_name: str) -> str:
        return "127.0.0.1"

    def _pin_chip(self, spec: PodSpec, env: Dict[str, str]) -> None:
        """Give a worker or serving child exactly ONE of the host's TPU
        chips.  A chip belongs to one process at a time: a child left
        with the parent's environment would try to own every chip and
        the second one to start would fail or hang.  No-op on a host
        without chips (the CPU harness) and for the master pod."""
        if spec.pod_type not in (PodType.WORKER, PodType.SERVING):
            return
        chips = host_tpu_chips()
        if not chips:
            return
        with self._lock:
            # a pinned pod whose process is not registered yet is being
            # created by another thread: its chip is taken
            held = {
                chip for name, chip in self._chip_of.items()
                if name != spec.name and (
                    name not in self.procs
                    or self.procs[name].poll() is None
                )
            }
            free = [c for c in chips if c not in held]
            if not free:
                raise RuntimeError(
                    f"no free TPU chip for pod {spec.name}: chips {chips} "
                    f"are held by {sorted(self._chip_of)}"
                )
            chip = self._chip_of[spec.name] = free[0]
        env["TPU_VISIBLE_CHIPS"] = str(chip)
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = "1,1,1"
        grid = env.get("TPU_CHIPS_PER_HOST_BOUNDS")
        if spec.pod_type == PodType.WORKER and grid:
            # One process per chip: the process grid IS the host's chip
            # grid, and the workers' runtimes find each other on
            # loopback to form the one mesh jax.distributed then spans.
            # The job must run as many workers as the host has chips.
            env["TPU_PROCESS_BOUNDS"] = grid
            env["TPU_PROCESS_ADDRESSES"] = ",".join(
                f"localhost:{_TPU_PROCESS_BASE_PORT + c}" for c in chips
            )
            env["TPU_PROCESS_PORT"] = str(_TPU_PROCESS_BASE_PORT + chip)
            env["CLOUD_TPU_TASK_ID"] = str(chips.index(chip))
        else:
            env["TPU_PROCESS_BOUNDS"] = "1,1,1"  # a replica stands alone
        logger.info("Pod %s pinned to TPU chip %d", spec.name, chip)

    def create_pod(self, spec: PodSpec) -> None:
        import os
        import subprocess

        env = dict(os.environ)
        env.update(self._extra_env)
        self._pin_chip(spec, env)
        with self._lock:
            self.pods[spec.name] = spec
            self.create_calls.append(spec)
            self.phases[spec.name] = PodStatus.PENDING
        self._emit(spec.name, PodStatus.PENDING)
        proc = subprocess.Popen(
            spec.command,
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
        )
        # Drain continuously: a child that fills an unread 64KB pipe blocks
        # on write() and wedges — indistinguishable from a real hang.
        chunks: List[bytes] = []

        def drain():
            for line in proc.stdout:
                chunks.append(line)

        threading.Thread(target=drain, daemon=True).start()
        with self._lock:
            self.procs[spec.name] = proc
            self._output[spec.name] = chunks
            self.phases[spec.name] = PodStatus.RUNNING
        self._emit(spec.name, PodStatus.RUNNING, "127.0.0.1")

    def delete_pod(self, name: str) -> None:
        with self._lock:
            proc = self.procs.get(name)
            self.phases[name] = PodStatus.DELETED
        if proc is not None and proc.poll() is None:
            proc.terminate()
        # Emit as soon as deletion is INITIATED (real k8s delivers the
        # deletionTimestamp event immediately too): the membership bump
        # then reaches surviving ranks before the condemned process — which
        # handles SIGTERM by finishing its current task — has left, so
        # survivors re-mesh gracefully at the next task boundary instead
        # of wedging in a collective against a vanished peer.
        self._emit(name, PodStatus.DELETED)
        if proc is not None and proc.poll() is None:
            try:
                proc.wait(timeout=15)
            except Exception:
                proc.kill()

    def kill_pod(self, name: str) -> None:
        """Hard preemption (test hook): SIGKILL, then the monitor reports
        the death as FAILED exactly like a spot reclaim."""
        with self._lock:
            proc = self.procs.get(name)
        if proc is not None and proc.poll() is None:
            proc.kill()

    def get_pod_phase(self, name: str) -> str:
        with self._lock:
            return self.phases.get(name, PodStatus.UNKNOWN)

    def get_pod_labels(self, name: str):
        with self._lock:
            spec = self.pods.get(name)
            return dict(spec.labels) if spec is not None else {}

    def list_pods(self):
        with self._lock:
            return [
                (
                    name,
                    spec.worker_id,
                    self.phases.get(name, PodStatus.UNKNOWN),
                    "127.0.0.1",
                )
                for name, spec in self.pods.items()
                if spec.pod_type == PodType.WORKER
            ]

    def start_watch(self, callback: EventCallback) -> None:
        self._callback = callback
        self._monitor = threading.Thread(target=self._watch_loop, daemon=True)
        self._monitor.start()

    def stop(self) -> None:
        self._stop.set()
        with self._lock:
            procs = list(self.procs.values())
        for proc in procs:
            if proc.poll() is None:
                proc.kill()

    def pod_output(self, name: str) -> str:
        with self._lock:
            chunks = list(self._output.get(name, ()))
        return b"".join(chunks).decode(errors="replace")

    def _watch_loop(self):
        import time as _time

        while not self._stop.is_set():
            with self._lock:
                snapshot = [
                    (name, proc)
                    for name, proc in self.procs.items()
                    if self.phases.get(name) == PodStatus.RUNNING
                ]
            for name, proc in snapshot:
                rc = proc.poll()
                if rc is None:
                    continue
                phase = (
                    PodStatus.SUCCEEDED if rc == 0 else PodStatus.FAILED
                )
                with self._lock:
                    # delete_pod may have won the race; keep its verdict.
                    if self.phases.get(name) != PodStatus.RUNNING:
                        continue
                    self.phases[name] = phase
                self._emit(name, phase, exit_code=rc)
            _time.sleep(0.1)

    def _emit(self, name: str, phase: str, address: str = "",
              exit_code=None):
        if self._callback is not None:
            self._callback(name, phase, address, exit_code)


class K8sClient(AbstractK8sClient):
    """Real Kubernetes client (pod create/watch/delete in a namespace)."""

    def __init__(self, namespace: str = "default", job_name: str = "job"):
        try:
            from kubernetes import client, config, watch  # noqa: F401
        except ImportError as exc:
            raise ImportError(
                "The `kubernetes` package is required for cluster mode; "
                "install it in the job image (local/test modes use "
                "FakeK8sClient)."
            ) from exc
        from kubernetes import client, config, watch

        try:
            config.load_incluster_config()
        except Exception:
            config.load_kube_config()
        self._core = client.CoreV1Api()
        self._watch = watch.Watch()
        self._namespace = namespace
        self._job_name = job_name
        self._callback: Optional[EventCallback] = None
        self._client_mod = client

    def create_pod(self, spec: PodSpec) -> None:
        client = self._client_mod
        volumes, mounts = [], []
        for i, entry in enumerate(spec.volumes):
            vol_name = f"vol-{i}"
            if "claim_name" in entry:
                source = dict(
                    persistent_volume_claim=(
                        client.V1PersistentVolumeClaimVolumeSource(
                            claim_name=entry["claim_name"]
                        )
                    )
                )
            else:
                source = dict(
                    host_path=client.V1HostPathVolumeSource(
                        path=entry["host_path"],
                        type="DirectoryOrCreate",
                    )
                )
            volumes.append(client.V1Volume(name=vol_name, **source))
            mounts.append(
                client.V1VolumeMount(
                    name=vol_name, mount_path=entry["mount_path"]
                )
            )
        container = client.V1Container(
            name="main",
            image=spec.image,
            command=spec.command,
            resources=client.V1ResourceRequirements(
                requests=spec.resources or None
            ),
            volume_mounts=mounts or None,
        )
        pod = client.V1Pod(
            metadata=client.V1ObjectMeta(
                name=spec.name,
                labels={
                    "elasticdl-job": self._job_name,
                    "elasticdl-type": spec.pod_type,
                    "elasticdl-worker-id": str(spec.worker_id),
                    **spec.labels,
                },
            ),
            spec=client.V1PodSpec(
                containers=[container],
                restart_policy="Never",
                priority_class_name=spec.priority_class or None,
                volumes=volumes or None,
            ),
        )
        self._core.create_namespaced_pod(self._namespace, pod)

    def create_service(
        self, name: str, selector: Dict[str, str], port: int
    ) -> None:
        client = self._client_mod
        service = client.V1Service(
            metadata=client.V1ObjectMeta(
                name=name, labels={"elasticdl-job": self._job_name}
            ),
            spec=client.V1ServiceSpec(
                selector=selector,
                ports=[client.V1ServicePort(port=port, target_port=port)],
            ),
        )
        self._core.create_namespaced_service(self._namespace, service)

    def delete_pod(self, name: str) -> None:
        self._core.delete_namespaced_pod(name, self._namespace)

    def get_pod_phase(self, name: str) -> str:
        pod = self._core.read_namespaced_pod(name, self._namespace)
        return pod.status.phase

    def get_pod_labels(self, name: str):
        # served from the last list_pods response when possible: adoption
        # calls list_pods first, then labels per pod — without the cache
        # that is N+1 sequential apiserver round-trips per failover
        cached = getattr(self, "_labels_cache", {}).get(name)
        if cached is not None:
            return dict(cached)
        pod = self._core.read_namespaced_pod(name, self._namespace)
        return dict(pod.metadata.labels or {})

    def list_pods(self):
        pods = self._core.list_namespaced_pod(
            self._namespace,
            label_selector=(
                f"elasticdl-job={self._job_name},elasticdl-type=worker"
            ),
        )
        out = []
        self._labels_cache = {}
        for pod in pods.items:
            try:
                worker_id = int(
                    pod.metadata.labels.get("elasticdl-worker-id", -1)
                )
            except (TypeError, ValueError):
                worker_id = -1
            self._labels_cache[pod.metadata.name] = dict(
                pod.metadata.labels or {}
            )
            out.append(
                (
                    pod.metadata.name,
                    worker_id,
                    pod.status.phase,
                    pod.status.pod_ip or "",
                )
            )
        return out

    def start_watch(self, callback: EventCallback) -> None:
        self._callback = callback
        thread = threading.Thread(target=self._watch_loop, daemon=True)
        thread.start()

    def _watch_loop(self):
        import time as _time

        backoff = 1.0
        while True:
            try:
                for event in self._watch.stream(
                    self._core.list_namespaced_pod,
                    self._namespace,
                    label_selector=f"elasticdl-job={self._job_name}",
                ):
                    backoff = 1.0  # healthy stream: reset
                    pod = event["object"]
                    phase = pod.status.phase
                    if event["type"] == "DELETED":
                        phase = PodStatus.DELETED
                    exit_code = None
                    try:
                        for cs in pod.status.container_statuses or []:
                            if cs.state and cs.state.terminated:
                                exit_code = cs.state.terminated.exit_code
                    except AttributeError:
                        pass
                    self._callback(
                        pod.metadata.name, phase,
                        pod.status.pod_ip or "", exit_code,
                    )
            except Exception as exc:
                logger.warning(
                    "k8s watch reconnecting in %.0fs after: %s", backoff, exc
                )
                _time.sleep(backoff)
                backoff = min(backoff * 2, 60.0)
