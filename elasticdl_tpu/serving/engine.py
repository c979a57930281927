"""Bucketed jit inference engine: the execution layer of online serving.

Design (docs/SERVING.md):

- **Precompiled buckets.**  Requests arrive at arbitrary batch sizes; XLA
  wants static shapes.  The engine compiles the forward pass once per
  configured bucket size at startup and pads every batch up to the
  nearest bucket, so no request ever triggers a compile on the hot path.
  `compile_count` counts traces of the jitted forward — the e2e test
  pins it `<= len(buckets)` to prove the no-recompile property.
- **Export mode.**  The forward is traced under
  `mesh_lib.export_mode()`, the same switch the SavedModel exporter
  uses: mesh-manual ops (ring attention, GPipe schedule, Pallas flash)
  fall back to their single-device lax formulations, so any zoo model —
  including ones trained with pipeline/sequence parallelism — serves on
  a plain CPU/TPU device with the identical param tree.
- **Atomic hot swap.**  `swap()` replaces the variables reference under
  a lock after validating tree structure/shape/dtype against the
  current set.  In-flight batches keep executing against the reference
  they already read — zero dropped requests across a reload (the
  reloader's contract, serving/reloader.py).
- **Serialized device execution.**  All device work funnels through
  `run_device_serialized` (worker/trainer.py): the virtual multi-device
  CPU backend used in tests corrupts state under concurrent execution,
  and real deployments lose nothing — a single accelerator executes one
  program at a time anyway.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np

from elasticdl_tpu.common import metrics as metrics_lib
from elasticdl_tpu.common import programs
from elasticdl_tpu.common.export import (
    SINGLE_FEATURE_KEY,
    feature_meta,
    load_exported,
    read_export_meta,
)
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.layers.step_metrics import STEP_METRICS
from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.worker.trainer import (
    model_has_train_kwarg,
    run_device_serialized,
)

logger = get_logger(__name__)

DEFAULT_BUCKETS = (1, 4, 16, 64)


def _zeros_features(feature_spec: Dict[str, dict], rows: int) -> dict:
    return {
        name: np.zeros((rows, *leaf["shape"]), np.dtype(leaf["dtype"]))
        for name, leaf in feature_spec.items()
    }


def packed_leaf_spec(leaf: dict) -> Optional[dict]:
    """The uint24-packed wire variant of an integer id feature leaf, or
    None when the leaf has no packed form.  An int32/int64 feature of
    per-row shape (F,) may instead arrive as (F, 3) uint8 little-endian
    triples (data/wire.py pack_int_to_uint24) — 3 bytes/id on the
    request payload instead of 4.  Zoo models on the CTR record format
    auto-unpack inside the jitted forward (deepfm sparse_ids), so the
    engine only needs to ACCEPT the shape; it never converts."""
    if np.dtype(leaf["dtype"]) not in (np.dtype(np.int32),
                                       np.dtype(np.int64)):
        return None
    return {"shape": [*leaf["shape"], 3], "dtype": "uint8"}


def packed_feature_spec(feature_spec: Dict[str, dict]) -> Dict[str, dict]:
    """The signature a bandwidth-conscious Predict client should send:
    every integer id feature in its uint24-packed form, everything else
    native.  Serialize ids with data/wire.py `pack_int_to_uint24`."""
    return {
        name: packed_leaf_spec(leaf) or dict(leaf)
        for name, leaf in feature_spec.items()
    }


class ServingEngine:
    """Executes a model's forward pass over precompiled batch buckets.

    `feature_spec` is the export-meta signature ({name: {shape, dtype}},
    common/export.py); features passed to `predict` are always a dict
    keyed by it — models whose feed yields a bare array use the single
    reserved key (SINGLE_FEATURE_KEY) and the engine unpacks it before
    `model.apply`.
    """

    def __init__(
        self,
        model,
        variables: Dict[str, Any],
        step: int,
        feature_spec: Dict[str, dict],
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        precompile: bool = True,
        state_template: Any = None,
        produced_unix_s: Optional[float] = None,
        pad_to_bucket: bool = True,
    ):
        if not buckets or any(b <= 0 for b in buckets):
            raise ValueError(f"buckets must be positive: {buckets}")
        self._model = model
        self._variables = variables
        self._step = int(step)
        # wall time the producer stamped into the checkpoint manifest
        # (None for exports / pre-freshness checkpoints); rides the
        # Health RPC so the master can trace end-to-end staleness
        self._produced_unix_s = produced_unix_s
        self._feature_spec = dict(feature_spec)
        self._buckets = tuple(sorted(set(int(b) for b in buckets)))
        self._single = set(self._feature_spec) == {SINGLE_FEATURE_KEY}
        # storm-drill seam: disabling bucket padding makes every distinct
        # request size a fresh trace, driving the registered program past
        # its signature budget (tests only — production always pads)
        self._pad_to_bucket = bool(pad_to_bucket)
        self._has_train = model_has_train_kwarg(model)
        self._lock = threading.Lock()
        # phase-timing clock (docs/OBSERVABILITY.md "Request tracing");
        # public so deterministic tests can inject a fake
        self.clock = time.perf_counter
        # Per-instance registry (common/metrics.py): compile/swap counts
        # live ONLY here; the properties below and the Health RPC read
        # the same series the /metrics exposition renders.
        self.metrics_registry = metrics_lib.MetricsRegistry()
        self._compiles = self.metrics_registry.counter(
            "serving_engine_compiles_total",
            "traces of the jitted forward (== distinct compiled buckets)",
        )
        self._swaps = self.metrics_registry.counter(
            "serving_engine_swaps_total",
            "hot swaps of the served variables (checkpoint reloads)",
        )
        self.metrics_registry.gauge_fn(
            "serving_model_step", lambda: self.step,
            "training step of the currently served variables",
        )
        # kept for the reloader: the abstract TrainState this engine's
        # checkpoint restores into (None for export-loaded engines)
        self.state_template = state_template

        def forward(variables, feats):
            # trace-time side effect: runs once per compile, never on the
            # hot path — this IS the compile counter
            self._compiles.inc()
            x = feats[SINGLE_FEATURE_KEY] if self._single else feats
            kwargs = {"train": False} if self._has_train else {}
            with mesh_lib.export_mode():
                return self._model.apply(variables, x, **kwargs)

        # Registered program (common/programs.py): every bucket trace is
        # a recorded compile in the process-wide ledger, and the bucket
        # count IS the declared signature budget — one more distinct
        # shape than the buckets within the storm window means requests
        # are missing the buckets (a recompile storm).
        self._forward = programs.registered_jit(
            "serving_forward", forward,
            signature_budget=len(self._buckets),
        )
        if precompile:
            self.warmup()

    # ---- construction ---------------------------------------------------

    @classmethod
    def from_export(
        cls,
        export_dir: str,
        spec,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        sample_features: Any = None,
        precompile: bool = True,
    ) -> "ServingEngine":
        """Load a `params.msgpack` export (common/export.py).

        The serving signature comes from export_meta.json; passing
        `sample_features` additionally cross-checks the export's feature
        keys against the model actually being served (load_exported's
        drift guard)."""
        meta = read_export_meta(export_dir)
        feature_spec = meta.get("features")
        if feature_spec is None:
            if sample_features is None:
                raise ValueError(
                    f"export at {export_dir} predates feature signatures "
                    "(no 'features' in export_meta.json) — pass "
                    "sample_features to describe the model's inputs"
                )
            feature_spec = feature_meta(sample_features)
        elif sample_features is not None:
            # cross-check the served model's signature against the
            # export's BEFORE tracing model.init with it — a drifted
            # sample would otherwise fail inside the model with an
            # unrelated shape/attribute error
            load_exported(
                export_dir, template=None,
                expected_features=list(feature_meta(sample_features)),
                check_only=True,
            )
        sample = _zeros_features(feature_spec, rows=1)
        x = sample[SINGLE_FEATURE_KEY] \
            if set(feature_spec) == {SINGLE_FEATURE_KEY} else sample
        kwargs = {"train": False} if model_has_train_kwarg(spec.model) \
            else {}
        init_shapes = jax.eval_shape(
            lambda: spec.model.init(jax.random.PRNGKey(0), x, **kwargs)
        )
        init_shapes = dict(init_shapes)
        # the last train step's scalars (layers/moe.py): nothing a
        # forward reads, and an export from before the model sowed any
        # has none
        init_shapes.pop(STEP_METRICS, None)
        template = {
            "params": {"params": init_shapes.pop("params")},
            "model_state": init_shapes,
        }
        loaded = load_exported(
            export_dir, template,
            expected_features=list(feature_spec),
        )
        variables = {**loaded["params"], **loaded["model_state"]}
        return cls(
            spec.model, variables, step=int(meta.get("step", 0)),
            feature_spec=feature_spec, buckets=buckets,
            precompile=precompile,
        )

    @classmethod
    def from_checkpoint(
        cls,
        checkpoint_dir: str,
        spec,
        sample_features: Any,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        step: Optional[int] = None,
        precompile: bool = True,
        arena_convert: bool = False,
    ) -> "ServingEngine":
        """Serve straight from a training checkpoint directory
        (manifest-verified via CheckpointSaver; the optimizer state is
        restored as part of the TrainState and discarded).

        `arena_convert=True` lets a checkpoint whose arena storage
        dtype differs from the configured model's migrate on restore —
        e.g. serve an int8-trained checkpoint through an fp32 config
        (the export direction) or vice versa; without it a mismatch
        raises `ArenaDtypeMismatch` (save_utils)."""
        from elasticdl_tpu.common.save_utils import CheckpointSaver

        template = build_state_template(spec, sample_features)
        saver = CheckpointSaver(checkpoint_dir, async_save=False)
        try:
            if step is None:
                step = saver.latest_step()
            if step is None:
                raise ValueError(
                    f"no checkpoints found in {checkpoint_dir}"
                )
            restored = run_device_serialized(
                lambda: saver.restore_step(
                    step, template, arena_convert=arena_convert
                )
            )
            if restored is None:
                raise ValueError(
                    f"checkpoint step {step} in {checkpoint_dir} failed "
                    "integrity verification or does not exist"
                )
            produced = saver.produced_meta(step) or {}
        finally:
            saver.close()
        variables = {**restored.params, **restored.model_state}
        return cls(
            spec.model, variables, step=int(step),
            feature_spec=feature_meta(sample_features), buckets=buckets,
            precompile=precompile, state_template=template,
            produced_unix_s=produced.get("produced_unix_s"),
        )

    # ---- introspection --------------------------------------------------

    @property
    def buckets(self) -> Tuple[int, ...]:
        return self._buckets

    @property
    def max_bucket(self) -> int:
        return self._buckets[-1]

    @property
    def feature_spec(self) -> Dict[str, dict]:
        return dict(self._feature_spec)

    @property
    def compile_count(self) -> int:
        return int(self._compiles.value())

    @property
    def swap_count(self) -> int:
        return int(self._swaps.value())

    @property
    def step(self) -> int:
        with self._lock:
            return self._step

    @property
    def produced_unix_s(self) -> Optional[float]:
        """Producer wall-time stamp of the served checkpoint, or None."""
        with self._lock:
            return self._produced_unix_s

    def bucket_for(self, rows: int) -> Optional[int]:
        for b in self._buckets:
            if b >= rows:
                return b
        return None

    def validate(self, features: Dict[str, np.ndarray]) -> Optional[str]:
        """None when `features` matches the serving signature, else a
        client-facing error string (SERVING_INVALID).  Integer id
        features are accepted in EITHER the native form or the
        uint24-packed wire form (`packed_feature_spec`) — per feature,
        so a client may pack only its large id planes."""
        if not isinstance(features, dict):
            return "features must be a dict of named arrays"
        if set(features) != set(self._feature_spec):
            return (
                f"feature keys {sorted(map(str, features))} do not match "
                f"the model signature {sorted(self._feature_spec)}"
            )
        rows = None
        for name, leaf in self._feature_spec.items():
            arr = np.asarray(features[name])
            packed = packed_leaf_spec(leaf)

            def matches(spec):
                return (
                    arr.dtype == np.dtype(spec["dtype"])
                    and arr.ndim == 1 + len(spec["shape"])
                    and list(arr.shape[1:]) == list(spec["shape"])
                )

            if not matches(leaf) and not (packed and matches(packed)):
                accepted = (
                    f"(rows, {', '.join(map(str, leaf['shape']))}) "
                    f"{leaf['dtype']}"
                )
                if packed:
                    accepted += (
                        f" or uint24-packed (rows, "
                        f"{', '.join(map(str, packed['shape']))}) uint8"
                    )
                return (
                    f"feature '{name}' has shape {arr.shape} dtype "
                    f"{arr.dtype}, expected {accepted}"
                )
            if rows is None:
                rows = arr.shape[0]
            elif arr.shape[0] != rows:
                return (
                    "feature row counts disagree: "
                    f"'{name}' has {arr.shape[0]}, others have {rows}"
                )
        if not rows:
            return "empty request (0 rows)"
        return None

    # ---- execution ------------------------------------------------------

    def warmup(self) -> None:
        """Compile every bucket up front so no request pays a compile."""
        for b in self._buckets:
            self.predict(_zeros_features(self._feature_spec, b), b)
        logger.info(
            "serving engine warm: buckets=%s compiles=%d",
            self._buckets, self.compile_count,
        )

    def predict(
        self, features: Dict[str, np.ndarray], rows: int,
        phase_out: Optional[Dict[str, float]] = None,
    ) -> Tuple[np.ndarray, int]:
        """Run the forward pass on `rows` leading rows of `features`,
        padding up to the nearest bucket; returns (predictions, step).
        When `phase_out` is given it receives the engine-side phase
        durations {"pad", "compute", "unpack"} in seconds — the batcher
        folds them into per-request spans and the
        `serving_request_phase_seconds{phase}` histogram.

        Oversized batches are the batcher's job to split; this raises."""
        bucket = self.bucket_for(rows)
        if bucket is None:
            raise ValueError(
                f"batch of {rows} rows exceeds largest bucket "
                f"{self.max_bucket}"
            )
        if not self._pad_to_bucket:
            bucket = rows
        t0 = self.clock()
        padded = {}
        for name, arr in features.items():
            arr = np.asarray(arr)
            if arr.shape[0] != bucket:
                pad = np.zeros(
                    (bucket - arr.shape[0],) + arr.shape[1:], arr.dtype
                )
                arr = np.concatenate([arr, pad], axis=0)
            padded[name] = arr
        with self._lock:
            variables, step = self._variables, self._step
        t1 = self.clock()
        out = run_device_serialized(self._forward, variables, padded)
        t2 = self.clock()
        # host transfer + row slice: the dequant/unpack leg of the span
        result = np.asarray(out)[:rows]
        if phase_out is not None:
            t3 = self.clock()
            phase_out["pad"] = max(0.0, t1 - t0)
            phase_out["compute"] = max(0.0, t2 - t1)
            phase_out["unpack"] = max(0.0, t3 - t2)
        return result, step

    # ---- hot reload -----------------------------------------------------

    def swap(self, variables: Dict[str, Any], step: int,
             produced_unix_s: Optional[float] = None) -> None:
        """Atomically replace the served variables.  The new tree must
        match the current one in structure/shape/dtype — the jitted
        buckets were compiled against those avals, and a mismatch would
        force a recompile (or worse, wrong results) mid-traffic.
        `produced_unix_s` is the manifest's producer stamp (freshness
        tracing); None keeps no stamp for the new generation."""
        new_shapes = jax.eval_shape(lambda t: t, variables)
        # Check-and-set under one lock hold: reading self._variables for
        # the shape check outside it would let two concurrent swaps
        # validate against the same old tree (GL-LOCK).  eval_shape is
        # abstract — no device work happens in the critical section.
        with self._lock:
            old_shapes = jax.eval_shape(lambda t: t, self._variables)
            if old_shapes != new_shapes:
                raise ValueError(
                    "swap rejected: new variables do not match the "
                    "served tree (structure/shape/dtype drift); restart "
                    "serving with the new model instead of hot-swapping"
                )
            self._variables = variables
            self._step = int(step)
            self._produced_unix_s = produced_unix_s
        self._swaps.inc()
        logger.info("serving engine swapped to step %d", step)


def build_state_template(spec, sample_features) -> Any:
    """Abstract TrainState (ShapeDtypeStructs, no device work) matching
    what training checkpoints of this model contain — the restore target
    for checkpoint-backed serving and hot reload."""
    import jax.numpy as jnp

    from elasticdl_tpu.worker.trainer import TrainState

    features = jax.tree.map(np.asarray, sample_features)
    kwargs = {"train": False} if model_has_train_kwarg(spec.model) else {}

    def make():
        variables = dict(
            spec.model.init(jax.random.PRNGKey(0), features, **kwargs)
        )
        params = {"params": variables.pop("params")}
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=spec.optimizer.init(params),
            model_state=variables,
        )

    return jax.eval_shape(make)
