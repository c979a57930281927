"""Checkpoint save/restore via Orbax.

Parity: reference python/common/save_utils.py `CheckpointSaver`
(SURVEY.md C9, §3.6): versioned checkpoint directories, keep-max rotation,
restore-on-relaunch.  TPU-native differences: Orbax writes sharded arrays
from the mesh directly (async) — the reference's per-PS-shard serialization
has no equivalent because there are no PS processes; preemption-aware
save-on-signal hooks into the pod manager instead of the PS.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from typing import Any, Dict, FrozenSet, Optional

from elasticdl_tpu.common import events, faults
from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger(__name__)

# ---- step pinning ---------------------------------------------------------
#
# The keep-last-K sweep and the serving hot-reload race: the trainer's
# saver rotates old steps out while a reloader (its OWN CheckpointSaver
# on the same directory) is mid-restore on one of them.  Orbax's
# built-in max_to_keep cannot see the reloader, so rotation is owned
# here instead (max_to_keep=None + an explicit sweep) and gated on a
# PROCESS-WIDE pin registry keyed by the checkpoint directory: the
# reloader pins the step for the duration of verify/restore/swap, and
# the sweep skips pinned steps (they fall on the next sweep after
# unpin).  Refcounted — overlapping pinners (N serving replicas
# reloading the same step) each hold their own pin.

_PIN_LOCK = threading.Lock()
_PINNED: Dict[str, Dict[int, int]] = {}   # abs dir -> step -> refcount


def pin_step(checkpoint_dir: str, step: int) -> None:
    """Protect `step` from the keep-last-K sweep until unpinned."""
    key = os.path.abspath(checkpoint_dir)
    step = int(step)
    with _PIN_LOCK:
        dir_pins = _PINNED.setdefault(key, {})
        dir_pins[step] = dir_pins.get(step, 0) + 1


def unpin_step(checkpoint_dir: str, step: int) -> None:
    key = os.path.abspath(checkpoint_dir)
    step = int(step)
    with _PIN_LOCK:
        dir_pins = _PINNED.get(key)
        if not dir_pins or step not in dir_pins:
            return
        dir_pins[step] -= 1
        if dir_pins[step] <= 0:
            del dir_pins[step]
        if not dir_pins:
            del _PINNED[key]


def pinned_steps(checkpoint_dir: str) -> FrozenSet[int]:
    with _PIN_LOCK:
        return frozenset(_PINNED.get(os.path.abspath(checkpoint_dir), ()))


def _file_digest(path: str) -> Dict[str, Any]:
    sha = hashlib.sha256()
    size = 0
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            sha.update(chunk)
            size += len(chunk)
    return {"sha256": sha.hexdigest(), "size": size}


def _step_files(step_dir: str):
    """Relative paths of every regular file under a step directory, in a
    stable order."""
    out = []
    for root, _dirs, files in os.walk(step_dir):
        for name in files:
            full = os.path.join(root, name)
            out.append(os.path.relpath(full, step_dir))
    return sorted(out)


def _swap_tree_keys(node, old: str, new: str):
    """Recursively rename dict keys `old` -> `new` through the mixed
    containers a TrainState template is made of (dicts, flax struct
    dataclasses, optax NamedTuple states, lists/tuples).  Raises on a
    collision (a subtree already holding BOTH names) — the shim must
    never silently merge two distinct params."""
    if isinstance(node, dict):
        if old in node and new in node:
            raise ValueError(
                f"cannot rename {old!r} -> {new!r}: both keys present"
            )
        return {
            (new if k == old else k): _swap_tree_keys(v, old, new)
            for k, v in node.items()
        }
    if hasattr(node, "_fields"):          # NamedTuple (optax states)
        return type(node)(
            *(_swap_tree_keys(v, old, new) for v in node)
        )
    if hasattr(node, "__dataclass_fields__"):   # flax struct (TrainState)
        import dataclasses

        return type(node)(
            **{
                f.name: _swap_tree_keys(getattr(node, f.name), old, new)
                for f in dataclasses.fields(node)
            }
        )
    if isinstance(node, (list, tuple)):
        return type(node)(_swap_tree_keys(v, old, new) for v in node)
    return node


class ArenaDtypeMismatch(ValueError):
    """A checkpoint's arena storage dtype differs from the configured
    model's and no conversion was requested.  Raised INSTEAD of the jax
    aval/structure crash the raw restore would produce, with the two
    dtypes and the fix in the message."""


def _state_arena_dtype(state) -> str:
    """"int8" when a (possibly abstract) train state carries a
    "quantized" collection, else "float32".  Structure-only."""
    model_state = getattr(state, "model_state", None)
    if isinstance(model_state, dict) and model_state.get("quantized"):
        return "int8"
    return "float32"


def _arena_meta_of(state) -> Dict[str, Any]:
    """Manifest metadata for the arena storage mode: the dtype plus, in
    int8 mode, each quantized plane's path/rows/dim/scale shape — enough
    to synthesize a restore template for dtype conversion without the
    model that wrote the checkpoint."""
    if _state_arena_dtype(state) == "float32":
        return {"arena_dtype": "float32", "planes": {}}
    from elasticdl_tpu.layers.arena import is_quantized_planes

    planes: Dict[str, Any] = {}

    def walk(node, path):
        if is_quantized_planes(node):
            planes["/".join(path)] = {
                "rows": int(node["q8"].shape[0]),
                "dim": int(node["q8"].shape[1]),
                "scale_shape": [int(s) for s in node["scale"].shape],
            }
            return
        for k in node:
            walk(node[k], path + (k,))

    walk(state.model_state["quantized"], ())
    return {"arena_dtype": "int8", "planes": planes}


def _planes_template_from_meta(meta: Dict[str, Any], params: Any):
    """Rebuild the abstract "quantized" collection recorded in a
    manifest: nested {path: {"q8", "scale"}} ShapeDtypeStructs.  Each
    plane reuses the sharding of the params leaf at the same path (the
    carrier has the q8 plane's exact shape), so a sharded restore lands
    the planes where the table lives."""
    import jax
    import jax.numpy as jnp

    quant: Dict[str, Any] = {}
    for dotted, info in meta.get("planes", {}).items():
        keys = dotted.split("/")
        sharding = None
        leaf = params.get("params", {})
        try:
            for k in keys:
                leaf = leaf[k]
            sharding = getattr(leaf, "sharding", None)
        except (KeyError, TypeError):
            leaf = None
        node = quant
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        rows, dim = int(info["rows"]), int(info["dim"])
        node[keys[-1]] = {
            "q8": jax.ShapeDtypeStruct(
                (rows, dim), jnp.int8, sharding=sharding
            ),
            "scale": jax.ShapeDtypeStruct(
                tuple(info.get("scale_shape", (rows, 1))), jnp.float32,
                sharding=sharding,
            ),
        }
    return quant


def _replace_state(state, params, model_state):
    if hasattr(state, "replace"):
        return state.replace(params=params, model_state=model_state)
    out = dict(state)
    out["params"] = params
    out["model_state"] = model_state
    return out


def _tree_has_key(node, key: str) -> bool:
    if isinstance(node, dict):
        return key in node or any(
            _tree_has_key(v, key) for v in node.values()
        )
    if hasattr(node, "_fields"):
        return any(_tree_has_key(v, key) for v in node)
    if hasattr(node, "__dataclass_fields__"):
        import dataclasses

        return any(
            _tree_has_key(getattr(node, f.name), key)
            for f in dataclasses.fields(node)
        )
    if isinstance(node, (list, tuple)):
        return any(_tree_has_key(v, key) for v in node)
    return False


def read_produced_meta(checkpoint_dir: str,
                       step: int) -> Optional[Dict[str, Any]]:
    """Read a manifest's producer freshness stamp without a saver (the
    master's FreshnessTracker watches a directory a trainer writes)."""
    path = os.path.join(
        os.path.abspath(checkpoint_dir), ".manifests", f"{int(step)}.json"
    )
    try:
        with open(path) as f:
            return json.load(f).get("produced")
    except (OSError, ValueError):
        return None


class CheckpointSaver:
    def __init__(
        self,
        checkpoint_dir: str,
        keep_max: int = 3,
        async_save: bool = True,
        clock=time.time,
    ):
        import orbax.checkpoint as ocp

        # injectable for deterministic freshness stamps under fake
        # clocks (docs/OBSERVABILITY.md "Metric history & SLOs")
        self._clock = clock

        self._dir = os.path.abspath(checkpoint_dir)
        os.makedirs(self._dir, exist_ok=True)
        # Per-step checksum manifests live in a side directory (never
        # inside the step dir: Orbax owns that layout) so restores can
        # detect truncated/corrupted checkpoints and fall back.
        self._manifest_dir = os.path.join(self._dir, ".manifests")
        os.makedirs(self._manifest_dir, exist_ok=True)
        self._async_save = bool(async_save)
        # Rotation is owned HERE, not by orbax (max_to_keep=None): the
        # sweep in _refresh_manifests keeps the newest `keep_max`
        # finalized steps, prunes manifests and tiered sidecars in
        # lockstep, and honors the pin registry above so a step a
        # reloader is mid-swap on is never deleted under it.
        self._keep_max = int(keep_max) if keep_max else None
        self._mngr = ocp.CheckpointManager(
            self._dir,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=None,
                enable_async_checkpointing=async_save,
            ),
        )
        # arena storage metadata per saved step, cached at save() time
        # (manifests are written later, after async finalize, with no
        # access to the state)
        self._arena_meta: Dict[int, Dict[str, Any]] = {}
        # producer freshness stamp per saved step, same cached-at-save
        # pattern — the train-to-serve staleness trace starts here
        self._produced_meta: Dict[int, Dict[str, Any]] = {}
        # tiered embedding store (elasticdl_tpu/store): when attached,
        # save() writes a sidecar (host planes + vocab + cache map) next
        # to each step and restores load it back into the store
        self._tiered_store = None
        self._tiered_meta: Dict[int, Dict[str, Any]] = {}

    def attach_tiered_store(self, store) -> None:
        """Couple a TieredStore to this saver: each save() writes the
        store's sidecar for the step, and each restore re-adopts the
        sidecar matching the restored step."""
        self._tiered_store = store

    def restore_raw(self, step: int):
        """Restore a step WITHOUT a template — the stored tree as orbax
        recorded it (dicts/lists of host arrays).  The tiered<->flat
        migration helpers path-match against this."""
        import orbax.checkpoint as ocp

        return self._mngr.restore(step, args=ocp.args.StandardRestore())

    def _save_tiered_sidecar(self, step: int, state) -> None:
        if self._tiered_store is None:
            return
        from elasticdl_tpu.store import checkpoint as store_ckpt

        try:
            store_ckpt.save_sidecar(self._dir, step,
                                    self._tiered_store, state)
            store = self._tiered_store
            self._tiered_meta[step] = {
                "cache_rows": int(store.cache_rows),
                "vocab_rows": int(store.host.size),
                "host_dtype": store.host.host_dtype,
                "cache_dtype": getattr(store, "cache_dtype", "float32"),
                "planes": {
                    name: int(dim) for name, dim in store.planes.items()
                },
            }
        except Exception:
            logger.exception("tiered sidecar save failed")

    def _load_tiered_sidecar(self, step: int) -> None:
        if self._tiered_store is None:
            return
        from elasticdl_tpu.store import checkpoint as store_ckpt

        if not store_ckpt.has_sidecar(self._dir, step):
            # A flat checkpoint restored into a tiered run: legitimate
            # (migration path) — the store keeps its current (usually
            # fresh) host state and lazily backfills.
            logger.info(
                "checkpoint step %d has no tiered sidecar; store state "
                "not restored", step,
            )
            return
        sidecar = store_ckpt.load_sidecar(self._dir, step)
        # convert=True: when the sidecar's plane dtype differs from the
        # running store's, the device cache VALUES restore through this
        # saver's template (arena_convert handles the int8<->fp32 plane
        # migration on the TrainState), so the residency map is safe to
        # adopt across the dtype change — the strict dtype gate is for
        # callers restoring bookkeeping WITHOUT the values.
        self._tiered_store.load_sidecar_state(
            sidecar.host_state, sidecar.row_of, sidecar.score,
            cache_dtype=sidecar.cache_dtype, convert=True,
        )
        logger.info(
            "tiered store sidecar restored for step %d "
            "(vocab_rows=%d cache_dtype=%s)", step,
            sidecar.meta.get("vocab_rows", -1), sidecar.cache_dtype,
        )

    def save(self, state, force: bool = False) -> bool:
        import orbax.checkpoint as ocp

        try:
            faults.fire(faults.POINT_CHECKPOINT_WRITE)
        except faults.InjectedFault as exc:
            # A failed periodic save is survivable by design: the next
            # crossing saves again, and restores fall back to the last
            # committed step.  Only injected faults take this path — real
            # Orbax errors still propagate.
            logger.warning("checkpoint save skipped (%s)", exc)
            return False
        step = int(state.step)
        if self._async_save:
            import jax

            if jax.default_backend() == "cpu":
                # Orbax's async save snapshots device buffers to host
                # before the background write, but on the CPU backend
                # that snapshot can be a zero-copy VIEW of the live
                # buffer — the next donating train step rewrites it in
                # place and the "step N" checkpoint silently captures
                # step N+1 values (same aliasing family as
                # parallel/collectives.host_snapshot).  Copy eagerly;
                # on accelerators the D2H transfer orbax performs is
                # already an owning copy, so no gate needed there.
                from elasticdl_tpu.parallel.collectives import (
                    host_snapshot,
                )

                state = host_snapshot(state)
        try:
            self._arena_meta[step] = _arena_meta_of(state)
        except Exception:
            logger.exception("arena metadata capture failed")
        self._produced_meta[step] = {
            "model_step": step,
            "produced_unix_s": round(float(self._clock()), 6),
        }
        # Sidecar BEFORE the (async) orbax save: the cache-value read
        # must precede the next donating train step.
        self._save_tiered_sidecar(step, state)
        saved = self._mngr.save(
            step, args=ocp.args.StandardSave(state), force=force
        )
        if saved:
            logger.info("Checkpoint saved at step %d", step)
            events.emit(events.CHECKPOINT_SAVED, step=step)
        # Manifests cover FINALIZED steps only (async saves commit
        # later); anything committed by now — including earlier async
        # saves — gets its manifest here.
        self._refresh_manifests()
        return saved

    # ---- integrity manifests -------------------------------------------

    def _manifest_path(self, step: int) -> str:
        return os.path.join(self._manifest_dir, f"{step}.json")

    def _step_dir(self, step: int) -> str:
        return os.path.join(self._dir, str(step))

    def _sweep_old_steps(self) -> None:
        """Keep-last-K over FINALIZED steps: delete everything older than
        the newest `keep_max`, except steps pinned by an in-flight
        reloader swap (those rotate out on the first sweep after
        unpin)."""
        if self._keep_max is None:
            return
        steps = sorted(self._mngr.all_steps())
        excess = steps[:-self._keep_max] if self._keep_max else steps
        if not excess:
            return
        pinned = pinned_steps(self._dir)
        for step in excess:
            if step in pinned:
                logger.info(
                    "keep-last-%d sweep deferring step %d (pinned by an "
                    "in-flight reload)", self._keep_max, step,
                )
                continue
            self._mngr.delete(step)

    def _refresh_manifests(self) -> None:
        """Rotate old steps out (keep-last-K, pin-aware), then write
        missing manifests for surviving finalized steps and prune
        manifests + tiered sidecars of rotated-away steps — base dir and
        `.tiered/<step>/` always move in lockstep.  Best-effort:
        integrity metadata must never fail a save."""
        try:
            self._sweep_old_steps()
            steps = set(self._mngr.all_steps())
            for step in steps:
                path = self._manifest_path(step)
                if os.path.exists(path):
                    continue
                self._write_manifest(step)
            for name in os.listdir(self._manifest_dir):
                stem, ext = os.path.splitext(name)
                if ext == ".json" and stem.isdigit() \
                        and int(stem) not in steps:
                    os.remove(os.path.join(self._manifest_dir, name))
            if self._tiered_store is not None:
                from elasticdl_tpu.store import checkpoint as store_ckpt

                store_ckpt.prune_sidecars(self._dir, steps)
        except Exception:
            logger.exception("checkpoint manifest refresh failed")

    def _write_manifest(self, step: int) -> None:
        step_dir = self._step_dir(step)
        if not os.path.isdir(step_dir):
            return
        manifest = {
            "step": step,
            "files": {
                rel: _file_digest(os.path.join(step_dir, rel))
                for rel in _step_files(step_dir)
            },
        }
        # arena storage mode, when this process saved the step (absent
        # for steps written before the quantized arena existed — those
        # are all float32)
        if step in self._arena_meta:
            manifest["arena"] = self._arena_meta[step]
        # producer model_step + wall time (absent for steps written by a
        # pre-freshness trainer); the reloader carries it through the
        # serving swap so every replica knows the age of its model
        if step in self._produced_meta:
            manifest["produced"] = self._produced_meta[step]
        # tiered store layout (cache size, planes, vocab at save time) —
        # what the serving side needs to know BEFORE loading the sidecar
        if step in self._tiered_meta:
            manifest["tiered"] = self._tiered_meta[step]
        path = self._manifest_path(step)
        # temp file + os.replace: readers only ever see a complete
        # manifest, even across a crash mid-write.  The temp name is
        # per-process: every SPMD rank writes the (identical) manifest
        # of a step into this shared directory, and with one shared
        # temp name the second rank's replace found it already moved.
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    def verify_step(self, step: int) -> bool:
        """Check a step's files against its manifest.  True when intact
        or when no manifest exists (pre-manifest checkpoints stay
        restorable); False on any missing/truncated/altered file."""
        path = self._manifest_path(step)
        if not os.path.exists(path):
            return True
        try:
            with open(path) as f:
                manifest = json.load(f)
        except (OSError, ValueError):
            return True  # unreadable manifest != corrupt checkpoint
        step_dir = self._step_dir(step)
        for rel, want in manifest.get("files", {}).items():
            full = os.path.join(step_dir, rel)
            if not os.path.isfile(full):
                logger.warning(
                    "checkpoint step %d: missing file %s", step, rel
                )
                return False
            got = _file_digest(full)
            if got["size"] != want.get("size") \
                    or got["sha256"] != want.get("sha256"):
                logger.warning(
                    "checkpoint step %d: checksum mismatch in %s "
                    "(%d bytes vs %d expected)",
                    step, rel, got["size"], want.get("size", -1),
                )
                return False
        return True

    def reload(self) -> None:
        """Re-scan the checkpoint directory for steps written by ANOTHER
        process (serving hot-reload watches a directory a trainer writes
        to; Orbax caches its step listing per manager)."""
        if hasattr(self._mngr, "reload"):
            self._mngr.reload()

    # ---- freshness -----------------------------------------------------

    def produced_meta(self, step: int) -> Optional[Dict[str, Any]]:
        """The {model_step, produced_unix_s} stamp a manifest recorded
        for `step`, or None (pre-freshness checkpoints)."""
        return read_produced_meta(self._dir, step)

    # ---- arena dtype compatibility -------------------------------------

    def _manifest_arena_meta(self, step: int) -> Optional[Dict[str, Any]]:
        try:
            with open(self._manifest_path(step)) as f:
                return json.load(f).get("arena")
        except (OSError, ValueError):
            return None

    def _checkpoint_arena_dtype(self, step: int) -> str:
        """The arena storage mode a checkpointed step was written with:
        from the manifest when recorded, else from the stored tree's
        structure (a "quantized" subtree means int8), else float32 —
        every pre-quantization checkpoint is fp32."""
        meta = self._manifest_arena_meta(step)
        if meta:
            return meta.get("arena_dtype", "float32")
        try:
            stored = self._mngr.item_metadata(step)
            stored = getattr(stored, "tree", stored)
            if stored is not None and _tree_has_key(stored, "quantized"):
                return "int8"
        except Exception:
            pass
        return "float32"

    def _arena_compat(self, step: int, abstract, arena_convert: bool):
        """Reconcile the checkpoint's arena dtype with the template's.

        Same dtype -> (abstract, None).  Different dtype without
        `arena_convert` -> ArenaDtypeMismatch (a clear error instead of
        the jax structure crash the raw restore would hit).  With
        `arena_convert`, returns (source template matching the
        CHECKPOINT's layout, post-restore converter into the CONFIGURED
        layout) — both directions, via layers/arena.py's tree
        converters; the carrier param shares the fp32 table's
        name/shape, so adam moments survive either way."""
        want = _state_arena_dtype(abstract)
        have = self._checkpoint_arena_dtype(step)
        if have == want:
            return abstract, None
        if not arena_convert:
            raise ArenaDtypeMismatch(
                f"checkpoint step {step} stores {have} arena rows but the "
                f"configured model expects {want}: pass "
                "arena_convert=True to migrate on restore, or set "
                f"--arena_dtype {have} to match the checkpoint"
            )
        from elasticdl_tpu.layers.arena import (
            dequantize_arena_tree,
            quantize_arena_tree,
        )

        if have == "float32":  # fp32 checkpoint -> quantized config
            quant_template = abstract.model_state["quantized"]
            source = _replace_state(
                abstract,
                abstract.params,
                {
                    k: v for k, v in abstract.model_state.items()
                    if k != "quantized"
                },
            )

            def convert(restored):
                inner, quant = quantize_arena_tree(
                    restored.params["params"], quant_template
                )
                params = dict(restored.params)
                params["params"] = inner
                model_state = dict(restored.model_state)
                model_state["quantized"] = quant
                logger.info(
                    "checkpoint step %d: quantized fp32 arena rows to "
                    "int8 on restore", step,
                )
                return _replace_state(restored, params, model_state)

            return source, convert

        # quantized checkpoint -> fp32 config (serving export path)
        meta = self._manifest_arena_meta(step)
        if not meta or not meta.get("planes"):
            raise ArenaDtypeMismatch(
                f"checkpoint step {step} stores int8 arena rows but its "
                "manifest records no plane shapes; cannot synthesize the "
                "conversion template — restore with --arena_dtype int8 "
                "instead"
            )
        quant_template = _planes_template_from_meta(meta, abstract.params)
        source = _replace_state(
            abstract,
            abstract.params,
            {**abstract.model_state, "quantized": quant_template},
        )

        def convert(restored):
            inner = dequantize_arena_tree(
                restored.params["params"],
                restored.model_state["quantized"],
            )
            params = dict(restored.params)
            params["params"] = inner
            model_state = {
                k: v for k, v in restored.model_state.items()
                if k != "quantized"
            }
            logger.info(
                "checkpoint step %d: dequantized int8 arena rows to "
                "fp32 on restore", step,
            )
            return _replace_state(restored, params, model_state)

        return source, convert

    def latest_step(self) -> Optional[int]:
        return self._mngr.latest_step()

    def all_steps(self):
        return list(self._mngr.all_steps())

    def restore_step(
        self, step: int, template: Any, arena_convert: bool = False
    ) -> Optional[Any]:
        """Restore a SPECIFIC checkpointed step into `template`'s
        shardings (eval-at-version: score the model the master asked
        about, not whatever the leasing worker currently holds).

        `arena_convert=True` migrates across arena storage dtypes
        (fp32 checkpoint -> int8 config and back); without it a dtype
        mismatch raises `ArenaDtypeMismatch`."""
        import jax
        import orbax.checkpoint as ocp

        if step not in self._mngr.all_steps():
            return None
        if not self.verify_step(step):
            logger.warning(
                "checkpoint step %d failed integrity check; not restoring",
                step,
            )
            return None
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=getattr(x, "sharding", None)
            )
            if hasattr(x, "shape")
            else x,
            template,
        )
        abstract, convert = self._arena_compat(step, abstract, arena_convert)
        restored = self._restore_with_shims(step, abstract)
        if convert is not None:
            restored = convert(restored)
        self._load_tiered_sidecar(step)
        logger.info("Restored checkpoint step %d (eval-at-version)", step)
        events.emit(events.CHECKPOINT_RESTORED, step=step)
        return restored

    def _restore_with_shims(self, step: int, abstract: Any) -> Any:
        """`_restore_renamed`, and where that fails on a template whose
        model sows step metrics (`layers/step_metrics.py: STEP_METRICS`,
        the LAST step's scalars, not trained state), once more without them: a
        checkpoint written before the model sowed any (a narrow-row
        embedding sows its share of distinct rows since PR 32) restores
        with the collection as zeros, as `init` leaves it."""
        import jax
        import jax.numpy as jnp

        from elasticdl_tpu.layers.step_metrics import STEP_METRICS

        model_state = getattr(abstract, "model_state", None)
        try:
            return self._restore_renamed(step, abstract)
        except Exception as first:
            if not isinstance(model_state, dict) or (
                STEP_METRICS not in model_state
            ):
                raise
            rest = {
                k: v for k, v in model_state.items() if k != STEP_METRICS
            }
            try:
                restored = self._restore_renamed(
                    step, abstract.replace(model_state=rest)
                )
            except Exception:
                raise first from None   # the first failure is the real one
        logger.info(
            "checkpoint step %d holds no step metrics; zeros restored",
            step,
        )
        sown = jax.tree.map(
            lambda x: jnp.zeros(x.shape, x.dtype, device=x.sharding),
            model_state[STEP_METRICS],
        )
        return restored.replace(
            model_state={**restored.model_state, STEP_METRICS: sown}
        )

    def _restore_renamed(self, step: int, abstract: Any) -> Any:
        """StandardRestore, with a legacy-key migration fallback: round 4
        renamed the GPipe stack param `stack` -> `gpipe_stack` (ADVICE
        r4) — a pre-rename checkpoint restores by renaming the keys in
        the TEMPLATE (everywhere: params AND the optimizer's mirrored
        moment trees), then renaming them back in the restored tree, so
        old pipelined checkpoints load without manual surgery."""
        import orbax.checkpoint as ocp

        try:
            return self._mngr.restore(
                step, args=ocp.args.StandardRestore(abstract)
            )
        except Exception:
            # Retry with the legacy template ONLY when the stored tree
            # really has the old key layout — re-running restore after an
            # unrelated failure (corrupt files, dtype mismatch, transient
            # FS error) would bury the real error under a phantom
            # key-migration failure.
            if not _tree_has_key(abstract, "gpipe_stack"):
                raise
            try:
                stored = self._mngr.item_metadata(step)
                # TreeMetadata wraps the key layout in `.tree`
                stored = getattr(stored, "tree", stored)
            except Exception:
                stored = None
            if stored is not None and not (
                _tree_has_key(stored, "stack")
                and not _tree_has_key(stored, "gpipe_stack")
            ):
                raise
            legacy = _swap_tree_keys(abstract, "gpipe_stack", "stack")
            restored = self._mngr.restore(
                step, args=ocp.args.StandardRestore(legacy)
            )
            logger.info(
                "Restored checkpoint step %d via legacy GPipe key shim "
                "(stack -> gpipe_stack)", step,
            )
            return _swap_tree_keys(restored, "stack", "gpipe_stack")

    def maybe_restore(
        self, template: Any, arena_convert: bool = False
    ) -> Optional[Any]:
        """Restore the newest INTACT checkpoint into the sharding/
        structure of `template` (an abstract or concrete train state).

        A latest step that is truncated/corrupt (manifest mismatch) or
        fails to restore falls back to the previous good step — a torn
        write must cost one checkpoint interval of progress, never the
        job.  When every step fails to restore, the last restore error
        re-raises (callers must not silently train from scratch when
        checkpoints exist but are all broken).

        An arena storage dtype mismatch (checkpoint int8 vs configured
        fp32 or vice versa) raises `ArenaDtypeMismatch` IMMEDIATELY —
        older steps would mismatch the same way, and silently training
        from scratch over a dtype flag is the worst outcome.  Pass
        `arena_convert=True` to migrate instead."""
        import jax

        steps = sorted(self._mngr.all_steps(), reverse=True)
        if not steps:
            return None
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=getattr(x, "sharding", None)
            )
            if hasattr(x, "shape")
            else x,
            template,
        )
        last_exc: Optional[Exception] = None
        for step in steps:
            if not self.verify_step(step):
                logger.warning(
                    "checkpoint step %d corrupt; falling back to the "
                    "previous good step", step,
                )
                continue
            try:
                step_abstract, convert = self._arena_compat(
                    step, abstract, arena_convert
                )
                restored = self._restore_with_shims(step, step_abstract)
                if convert is not None:
                    restored = convert(restored)
            except ArenaDtypeMismatch:
                raise
            except Exception as exc:
                last_exc = exc
                logger.warning(
                    "checkpoint step %d failed to restore (%s); falling "
                    "back to the previous good step", step, exc,
                )
                continue
            self._load_tiered_sidecar(step)
            logger.info("Restored checkpoint step %d", step)
            events.emit(events.CHECKPOINT_RESTORED, step=step)
            return restored
        if last_exc is not None:
            raise last_exc
        return None

    def wait_until_finished(self):
        self._mngr.wait_until_finished()
        # async saves finalized by now become manifest-covered
        self._refresh_manifests()

    def close(self):
        self._mngr.close()
