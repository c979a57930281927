"""Model ownership and intra-process synchronization.

The reference kept every worker on ONE shared model: PS mode served all
workers from one parameter store (SURVEY.md C10, call stack §3.3);
AllReduce mode kept replicas in lockstep via Horovod (C15, §3.4).  The
TPU-native analogue inside one process is a single `ModelOwner`: one
Trainer + one TrainState shared by every worker thread, updates serialized
under a lock.  Semantically this is the reference's *async PS* — each
worker computes gradients against the params as of its own step start, and
applies them atomically — with staleness bounded by the number of threads
instead of by network latency.

Cross-process synchronization (cluster mode) is NOT this file's job: that
is SPMD over a global mesh (worker/spmd.py), where consistency holds by
construction because every process executes the same collective step.
"""

from __future__ import annotations

import threading
from typing import Optional

import jax
import numpy as np

from elasticdl_tpu.common import profiler
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.layers.step_metrics import STEP_METRICS

logger = get_logger(__name__)


class ModelOwner:
    """Owns one model replica: trainer + state + update lock + checkpoints.

    Workers never touch TrainState directly; everything flows through the
    owner so N workers sharing one owner train one model (the property the
    reference's whole PS/AllReduce machinery exists to provide).
    """

    def __init__(
        self,
        trainer,
        seed: int = 0,
        checkpoint_saver=None,
        checkpoint_steps: int = 0,
    ):
        from elasticdl_tpu.worker.trainer import run_device_serialized

        self.trainer = trainer
        self.lock = threading.RLock()
        self.state = None
        self.sample_features = None
        # serialized: owners are constructed on the pod-relaunch path
        # while sibling workers are mid-step, and the key creation is a
        # device op (see trainer._CPU_EXEC_LOCK)
        self._rng = run_device_serialized(
            lambda: jax.random.PRNGKey(seed)
        )
        self.checkpoint_saver = checkpoint_saver
        self.checkpoint_steps = checkpoint_steps

    # ---- state lifecycle ----------------------------------------------

    def ensure_state(self, batch) -> None:
        with self.lock:
            if self.sample_features is None:
                # one host row, kept for export signatures (SavedModel
                # needs the feature structure/shapes/dtypes)
                self.sample_features = jax.tree.map(
                    lambda a: np.asarray(a[:1]), batch["features"]
                )
            if self.state is not None:
                return
            # start-up spans (profiler.STARTUP_PHASES): the state's
            # shapes are the first batch's, so they lie in the first task
            timer = profiler.process_phase_timer()
            with timer.phase("init_state"):
                self.state = self.trainer.init_state(
                    self._rng, batch["features"]
                )
            if self.checkpoint_saver is not None:
                with timer.phase("restore"):
                    restored = self.checkpoint_saver.maybe_restore(
                        self.state
                    )
                if restored is not None:
                    self.state = restored
                    logger.info("Restored state from checkpoint")

    def has_trained_state(self) -> bool:
        """True if the owner holds (or can restore) non-random params."""
        from elasticdl_tpu.worker.trainer import run_device_serialized

        with self.lock:
            if self.state is not None and run_device_serialized(
                lambda: int(self.state.step)
            ) > 0:
                return True
            return (
                self.checkpoint_saver is not None
                and self.checkpoint_saver.latest_step() is not None
            )

    @property
    def step(self) -> int:
        from elasticdl_tpu.worker.trainer import run_device_serialized

        with self.lock:
            if self.state is None:
                return 0
            # serialized device->host fetch: a transfer racing another
            # thread's step execution corrupts the CPU backend
            return run_device_serialized(lambda: int(self.state.step))

    # ---- serialized model operations ----------------------------------

    def train_batch(self, batch):
        with self.lock:
            self.ensure_state(batch)
            self.state, loss = self.trainer.train_on_batch(
                self.state, batch
            )
            self._maybe_checkpoint()
            return loss

    def fetch_loss(self, loss):
        """`fetch_loss` of this owner's state, serialized with its steps.
        A model that sows no step metrics takes the plain loss fetch,
        outside the lock as it always was."""
        from elasticdl_tpu.worker.trainer import run_device_serialized

        with self.lock:
            if self.state is not None and (
                STEP_METRICS in self.state.model_state
            ):
                # fetched under the lock: the next step donates the
                # state's buffers
                return run_device_serialized(fetch_loss, self.state, loss)
        return run_device_serialized(fetch_loss, None, loss)

    def stage_batch(self, batch):
        """Start batch's host->device transfer (Trainer.stage_batch) and
        return the placed batch for a later train_batch call — the
        double-buffering hook prefetch_batches' device_stage calls.
        ensure_state runs FIRST, on the host batch: its export-signature
        snapshot and init want host arrays, and init_state must precede
        any same-shaped device work anyway."""
        with self.lock:
            self.ensure_state(batch)
            return self.trainer.stage_batch(batch)

    def predict_batch(self, batch, state=None):
        """Forward pass; `state` overrides the owner's current state (eval
        at a restored version)."""
        with self.lock:
            self.ensure_state(batch)
            use = self.state if state is None else state
            return self.trainer.predict_on_batch(use, batch["features"])

    def save(self, force: bool = False) -> None:
        with self.lock:
            if self.checkpoint_saver is not None and self.state is not None:
                self.checkpoint_saver.save(self.state, force=force)

    def save_and_flush(self) -> None:
        """Synchronous final checkpoint (preemption hook)."""
        self.save(force=True)
        if self.checkpoint_saver is not None:
            self.checkpoint_saver.wait_until_finished()

    def _maybe_checkpoint(self) -> None:
        if (
            self.checkpoint_saver is not None
            and self.checkpoint_steps
            and self.state is not None
            and int(self.state.step) % self.checkpoint_steps == 0
        ):
            self.checkpoint_saver.save(self.state)

    def snapshot(self):
        """Donation-safe copy of the current state (see snapshot_state)."""
        with self.lock:
            return snapshot_state(self.state)

    def state_for_eval(self, requested_version: int):
        """Resolve the state an eval task should score (SURVEY.md §3.5:
        the reference evaluated the model at the task's version, pulled
        from the PS — here the checkpoint store is the version archive).

        Returns (state, actual_version): the checkpointed state at the
        requested version when it is retrievable, else the current state
        labeled with its TRUE step so the master never aggregates metrics
        under a version the model isn't at.
        """
        with self.lock:
            return state_at_version(
                self.state, self.checkpoint_saver, requested_version
            )

    # ---- elastic re-mesh ----------------------------------------------

    def remesh(self, mesh) -> None:
        """Point the trainer at a new mesh and re-place existing state."""
        with self.lock:
            self.trainer.set_mesh(mesh)
            if self.state is not None:
                self.state = self.trainer.replace_state(self.state)


def fetch_loss(state, loss):
    """(the loss as a float, {path: float} of the last step's
    STEP_METRICS): a train task's ONE device fetch.  The scalars a model
    sows there ride in `state.model_state`, so they cost a task no second
    sync and a step none at all.  The threaded loop calls it through its
    `ModelOwner`, the SPMD loop, which holds its own state, directly."""
    sown = None if state is None else state.model_state.get(STEP_METRICS)
    if sown is None:
        return float(np.asarray(loss)), {}
    loss, sown = jax.device_get((loss, sown))
    return float(loss), {
        "/".join(str(getattr(k, "key", k)) for k in path): float(leaf)
        for path, leaf in jax.tree_util.tree_leaves_with_path(sown)
    }


def snapshot_state(state):
    """Donation-safe FORWARD-ONLY copy of a TrainState.

    The train step donates its input state (donate_argnums), so a caller
    that captures the LIVE state object and keeps using it across batches
    — an eval task scoring one consistent version while another worker
    thread keeps training — would read buffers the next train step has
    already donated (XLA: "Buffer has been deleted or donated", which on
    the multi-device CPU backend also wedges the whole device queue).
    Copying under the owner's lock orders the copy before any later
    donation.

    Only step/params/model_state are copied — everything a forward pass
    reads.  opt_state (2x param memory under Adam) keeps the live
    reference: eval/export never touch it, and copying it would roughly
    triple the snapshot's memory cost.  Do NOT train on a snapshot."""
    if state is None:
        return None
    import jax.numpy as jnp

    def copy_tree(tree):
        return jax.tree.map(
            lambda a: jnp.copy(a) if isinstance(a, jax.Array) else a, tree
        )

    return state.replace(
        step=copy_tree(state.step),
        params=copy_tree(state.params),
        model_state=copy_tree(state.model_state),
    )


def state_at_version(state, checkpoint_saver, requested_version: int):
    """Shared eval-at-version resolution (thread/SPMD workers).

    (state, actual_version) where actual_version is what the metrics must
    be labeled with.  The returned state is always safe to hold across
    batches: either a fresh restore or a donation-safe snapshot of the
    live state (see snapshot_state)."""
    current = -1 if state is None else int(state.step)
    if requested_version < 0 or requested_version == current:
        return snapshot_state(state), current
    if checkpoint_saver is not None and state is not None:
        restored = checkpoint_saver.restore_step(requested_version, state)
        if restored is not None:
            return restored, requested_version
    logger.info(
        "Eval at version %d not retrievable (current step %d, no "
        "checkpoint); evaluating current state",
        requested_version, current,
    )
    return snapshot_state(state), current
