"""The XLA-compiled training engine.

This replaces three reference components at once (SURVEY.md §7 design
mapping):

- the worker's eager `tf.GradientTape` step (C7),
- the parameter-server optimizer application, Python and Go/Eigen
  (C10/C16/C17) — Optax inside the jitted step; XLA *is* the native
  kernel,
- Horovod's dense-gradient AllReduce (C15) — gradient reduction over the
  mesh `data` axis is inserted by XLA from the NamedShardings.

One `jit`-compiled function owns forward + backward + optimizer update;
params/opt state live replicated (or sharded) on the mesh, the batch is
split along `data`.  bfloat16 compute keeps the MXU fed; params stay f32.
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax.sharding import NamedSharding, PartitionSpec as P

from elasticdl_tpu.common import programs
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.layers import moe as moe_layers
from elasticdl_tpu.layers.arena import fold_quantized_updates
from elasticdl_tpu.parallel import mesh as mesh_lib

logger = get_logger(__name__)

# Process-wide device-execution serialization for the CPU backend.  The
# virtual multi-device CPU platform (xla_force_host_platform_device_count)
# can deadlock when two THREADS dispatch multi-device programs
# concurrently: each program's collectives rendezvous over the same
# device threads, and once interleaved neither completes — observed as a
# permanently wedged `jax.Array._value` that then blocks every later
# fetch in the process.  Serializing dispatch+completion removes the
# interleaving.  On TPU the hardware queue order is the serialization and
# this lock is never taken.
_CPU_EXEC_LOCK = threading.Lock()


def run_device_serialized(fn, *args):
    """Call fn(*args); on the CPU backend, hold the process-wide execution
    lock and block until the result is ready (see _CPU_EXEC_LOCK)."""
    if jax.default_backend() != "cpu":
        return fn(*args)
    with _CPU_EXEC_LOCK:
        return jax.block_until_ready(fn(*args))


def model_has_train_kwarg(model) -> bool:
    """Whether the model's __call__ takes the zoo contract's `train`
    kwarg (BatchNorm/dropout models).  Shared by the Trainer and the
    SavedModel export so train-time eval and serving stay in lockstep."""
    import inspect

    try:
        return "train" in inspect.signature(type(model).__call__).parameters
    except (TypeError, ValueError):
        return False


# Collections a layer sows into for the TRAIN step only: mutable there,
# read once, never part of the persistent `model_state`.  Everything in
# AUX_LOSS is an auxiliary objective, already scaled where it was sown
# (MoE load balancing, a multi-token-prediction loss); "intermediates"
# is flax's own scratch collection.
_EPHEMERAL = (moe_layers.AUX_LOSS, "intermediates")


def _sown_aux_loss(sown) -> jnp.ndarray:
    """Sum of every value sown into AUX_LOSS anywhere in the module
    tree.  Zero when nothing was sown — models without auxiliary
    objectives are unaffected."""
    return sum(
        (jnp.asarray(leaf, jnp.float32) for leaf in jax.tree.leaves(sown)),
        jnp.zeros((), jnp.float32),
    )


def split_variables(variables):
    """`model.init`'s variables as ({"params": ...}, model_state): the
    optimizer sees only the former; what `init` sowed into the ephemeral
    collections is dropped, so a step never adds init's values to its
    own, and STEP_METRICS (the LAST step's scalars) holds zeros, no step
    having run.  Called INSIDE the init program: nothing it returns then
    depends on the forward that `init` traced, so the compiler drops that
    forward (a decoder's at 16,384 tokens, kernels and all: half of the
    init program's compile time and of its executable)."""
    variables = dict(variables)
    params = {"params": variables.pop("params")}
    for collection in _EPHEMERAL:
        variables.pop(collection, None)
    if moe_layers.STEP_METRICS in variables:
        variables[moe_layers.STEP_METRICS] = jax.tree.map(
            jnp.zeros_like, variables[moe_layers.STEP_METRICS]
        )
    return params, variables


class TrainState(struct.PyTreeNode):
    step: jnp.ndarray
    params: Any          # trainable variables ({"params": ...})
    opt_state: Any
    model_state: Any = struct.field(default_factory=dict)  # batch_stats etc.


class Trainer:
    """Builds and owns the jitted train/eval steps for one model.

    model_fn: flax Module (or any object with .init/.apply) — the zoo's
              `custom_model()`
    loss_fn:  (labels, predictions) -> scalar  — the zoo's `loss`
    optimizer: optax.GradientTransformation    — the zoo's `optimizer()`
    """

    # Step-phase attribution hook (common/profiler.PhaseTimer).  Class
    # default so trainers built by tests (or through __new__ scaffolding)
    # run untimed; the worker runtime assigns the process-wide timer.
    # Trainer-level because BOTH worker loops (threaded and SPMD) end up
    # here: h2d_stage covers stage_batch, compute covers the train
    # dispatch (including CPU-backend lock wait — attributing contention
    # to compute is deliberate: it IS time the step spent not overlapped).
    # On an asynchronous backend `compute` is ENQUEUE time: the device's
    # work shows in the loop's `task_sync`, and a long `compute` means
    # the device's queue was full.
    phase_timer = None

    # Tiered embedding store (elasticdl_tpu/store).  When set, batches
    # carry a `__store_plan__` admission plan the trainer must execute
    # against the state BEFORE the step that consumes the batch's slots.
    # Class default so __new__-built trainers (tests) stay flat.
    tiered_store = None

    def _timed(self, phase_name: str, fn, *args):
        timer = self.phase_timer
        if timer is None:
            return fn(*args)
        with timer.phase(phase_name):
            return fn(*args)

    def __init__(
        self,
        model,
        optimizer,
        loss_fn: Callable,
        mesh=None,
        use_bf16: bool = False,
        param_sharding_fn: Optional[Callable] = None,
    ):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.mesh = mesh if mesh is not None else mesh_lib.create_mesh()
        self.use_bf16 = use_bf16
        self._param_sharding_fn = param_sharding_fn
        self._repl = mesh_lib.replicated(self.mesh)
        self._data = mesh_lib.data_sharding(self.mesh)
        # Models with train-time behavior (BatchNorm, dropout) take a
        # `train` kwarg per the zoo contract; plain models need not.
        self._has_train_kwarg = model_has_train_kwarg(model)
        self._build_steps()

    def set_mesh(self, mesh):
        """Elastic re-mesh: subsequent batches/state placements target the
        new mesh.  The jitted steps need no rebuild — they are polymorphic
        over input shardings."""
        self.mesh = mesh
        self._repl = mesh_lib.replicated(mesh)
        self._data = mesh_lib.data_sharding(mesh)

    def replace_state(self, state: "TrainState") -> "TrainState":
        """Re-place existing state onto the current mesh (single-process
        resharding; multi-host restores from checkpoint instead).  The
        device->host copy and re-placement are one serialized device
        operation: a remesh racing another thread's step execution
        corrupts the CPU backend (see _CPU_EXEC_LOCK)."""

        def _replace():
            # Safe asarray: the view is consumed by device_put inside the
            # same serialized device operation, so no donating step can
            # rewrite the buffer while it is live.
            host_state = jax.tree.map(  # graftlint: disable=GL-DONATE
                lambda x: np.asarray(x) if hasattr(x, "shape") else x, state
            )
            return jax.device_put(host_state, self.state_sharding(state))

        return run_device_serialized(_replace)

    # ---- state ---------------------------------------------------------

    def init_state(self, rng, sample_features) -> TrainState:
        return run_device_serialized(
            self._init_state_impl, rng, sample_features
        )

    def _init_state_impl(self, rng, sample_features) -> TrainState:
        mesh_lib.set_current_mesh(self.mesh)
        kwargs = {"train": False} if self._has_train_kwarg else {}
        # Split trainable ("params") from mutable model state (e.g.
        # batch_stats); the optimizer sees only the former.  The init is
        # ONE program, not an op-by-op walk of the model's forward: a
        # decoder's eager init at 16,384 tokens dispatched (and compiled)
        # every operation of its blocks on the way to its parameters.
        params, model_state = programs.registered_jit(
            "worker_init_state",
            lambda rng, features: split_variables(self.model.init(
                rng, self._cast(features), **kwargs
            )),
        )(rng, jax.tree.map(np.asarray, sample_features))
        state = TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=self.optimizer.init(params),
            model_state=model_state,
        )
        return jax.device_put(state, self.state_sharding(state))

    def init_state_global(self, rng, sample_features) -> TrainState:
        """Multi-process SPMD init: the whole init (model.init + optimizer
        init) runs as ONE jitted program with `out_shardings` over the
        global mesh, so every process participates in the same computation
        and the resulting state is identical across ranks by construction
        (no host-side broadcast needed — the reference's AllReduce mode had
        to broadcast variables from rank 0 instead, SURVEY.md §3.4)."""
        mesh_lib.set_current_mesh(self.mesh)
        kwargs = {"train": False} if self._has_train_kwarg else {}
        features = jax.tree.map(np.asarray, sample_features)

        def make():
            params, variables = split_variables(
                self.model.init(rng, self._cast(features), **kwargs)
            )
            return TrainState(
                step=jnp.zeros((), jnp.int32),
                params=params,
                opt_state=self.optimizer.init(params),
                model_state=variables,
            )

        shapes = jax.eval_shape(make)
        shardings = self.state_sharding(shapes)
        return run_device_serialized(
            programs.registered_jit(
                "worker_init_state", make, out_shardings=shardings
            )
        )

    def state_sharding(self, state):
        """Sharding tree for the train state: replicated by default;
        `param_sharding_fn(path, value) -> PartitionSpec` overrides (used
        by sharded embedding tables / tensor parallelism)."""
        if self._param_sharding_fn is None:
            return jax.tree.map(lambda _: self._repl, state)

        def spec_for(path, leaf):
            spec = self._param_sharding_fn(path, leaf)
            return NamedSharding(self.mesh, spec if spec is not None else P())

        # model_state replicates EXCEPT the "quantized" collection: its
        # int8/scale planes mirror arena tables and must row-shard with
        # them (the path contains "embedding", so the same sharding fn
        # applies).
        model_state_sh = {
            key: (
                jax.tree_util.tree_map_with_path(spec_for, sub)
                if key == "quantized"
                else jax.tree.map(lambda _: self._repl, sub)
            )
            for key, sub in state.model_state.items()
        }

        params_sh = jax.tree_util.tree_map_with_path(spec_for, state.params)
        # Optax states embed per-param moment trees with the SAME pytree
        # structure as params (mu/nu in Adam, trace in momentum, ...);
        # shard those like the params and replicate everything else
        # (counts, scalars).  Structure matching — not shape matching —
        # so same-shaped params with different specs stay distinct.
        param_treedef = jax.tree.structure(state.params)

        def is_param_like(subtree):
            try:
                return jax.tree.structure(subtree) == param_treedef
            except Exception:
                return False

        def shard_subtree(subtree):
            if is_param_like(subtree):
                return params_sh
            return jax.tree.map(lambda _: self._repl, subtree)

        opt_sh = jax.tree.map(
            shard_subtree, state.opt_state, is_leaf=is_param_like
        )
        return TrainState(
            step=self._repl,
            params=params_sh,
            opt_state=opt_sh,
            model_state=model_state_sh,
        )

    def _cast(self, features):
        if not self.use_bf16:
            return features
        return jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
            else x,
            features,
        )

    # ---- steps ---------------------------------------------------------

    def _build_steps(self):
        def loss_of(params, model_state, features, labels):
            variables = {**params, **model_state}
            kwargs = {"train": True} if self._has_train_kwarg else {}
            # The ephemeral collections are always mutable in the TRAIN
            # step so layer-sown auxiliary objectives (MoE load balancing,
            # a second prediction loss) reach the loss; they never enter
            # the persistent model_state.  What a model keeps THERE it
            # updates in place: a layer's own buffers, and the last
            # step's STEP_METRICS scalars, which so ride beside the loss
            # to the worker's one fetch a task (`step_metrics`).
            mutable = list(model_state.keys()) + list(_EPHEMERAL)
            preds, updates = self.model.apply(
                variables, self._cast(features), mutable=mutable, **kwargs
            )
            updates = dict(updates)
            sown = updates.pop(moe_layers.AUX_LOSS, {})
            updates.pop("intermediates", None)
            new_model_state = updates if updates else model_state
            loss = jnp.asarray(
                self.loss_fn(labels, preds.astype(jnp.float32)), jnp.float32
            )
            loss = loss + _sown_aux_loss(sown)
            return loss, new_model_state

        def train_step(state: TrainState, batch):
            (loss, new_model_state), grads = jax.value_and_grad(
                loss_of, has_aux=True
            )(
                state.params, state.model_state,
                batch["features"], batch["labels"],
            )
            # one of profiler.DEVICE_SCOPES: the update's device time is
            # told from the backward's by this name
            with jax.named_scope("train/optimizer"):
                updates, opt_state = self.optimizer.update(
                    grads, state.opt_state, state.params
                )
                params = optax.apply_updates(state.params, updates)
                # Quantized arenas: fold the carrier's delta back into
                # the int8 planes with stochastic rounding and zero the
                # carrier.  Trace-time no-op when no "quantized"
                # collection exists, so the fp32 path stays bit-identical
                # (layers/arena.py).
                params, new_model_state = fold_quantized_updates(
                    params, new_model_state, state.step
                )
            return (
                TrainState(
                    step=state.step + 1,
                    params=params,
                    opt_state=opt_state,
                    model_state=new_model_state,
                ),
                loss,
            )

        def eval_step(state: TrainState, features):
            variables = {**state.params, **state.model_state}
            kwargs = {"train": False} if self._has_train_kwarg else {}
            preds = self.model.apply(
                variables, self._cast(features), **kwargs
            )
            return preds.astype(jnp.float32)

        def train_step_many(state: TrainState, stacked):
            # K serially-dependent train steps in ONE dispatched program
            # (lax.scan over a (K, B, ...) batch stack).  This is
            # `steps_per_execution`: per-dispatch overhead is paid once
            # per K steps, and XLA overlaps the scan's iterations'
            # transfers and compute.
            return jax.lax.scan(train_step, state, stacked)

        # Shardings: batch split on `data`; XLA inserts the gradient
        # all-reduce from the sharding propagation (no explicit psum).
        self.train_step = programs.registered_jit(
            "worker_train_step", train_step, donate_argnums=(0,)
        )
        self.train_step_many = programs.registered_jit(
            "worker_train_step_many", train_step_many, donate_argnums=(0,)
        )
        self.eval_step = programs.registered_jit(
            "worker_eval_step", eval_step
        )

    # ---- host-side helpers --------------------------------------------

    def stage_batch(self, batch: Dict[str, np.ndarray]):
        """Start `batch`'s host->device transfer NOW; return the placed
        batch (an overlap handle) for a later train_on_batch call.

        Double buffering's second half: device_put is asynchronous on
        real backends, so staging batch k+1 while batch k executes hides
        the transfer behind compute.  train_on_batch re-shards the
        staged result, which is a no-op for an array already placed with
        the same sharding — staged and unstaged batches flow through the
        same path.  Must be called from the ONE thread that drives the
        device (prefetch_batches stages on the consumer thread): on the
        CPU backend the transfer rides inside the serialized region
        (_CPU_EXEC_LOCK), on TPU it's a plain async enqueue."""
        mesh_lib.set_current_mesh(self.mesh)
        # A store admission plan (or, in deferred multi-worker mode, the
        # raw sparse batch awaiting planning) is host bookkeeping, not
        # batch data — pop it around the shard (tree_map would treat it
        # as a leaf and try to device_put it), reattach on a copy after.
        carried = {
            k: batch[k]
            for k in ("__store_plan__", "__store_sparse__")
            if k in batch
        }
        if carried:
            batch = {k: v for k, v in batch.items() if k not in carried}
        staged = self._timed(
            "h2d_stage", run_device_serialized,
            mesh_lib.shard_batch, batch, self.mesh,
        )
        if carried:
            staged = dict(staged)
            staged.update(carried)
        return staged

    def train_on_batch(self, state, batch: Dict[str, np.ndarray]):
        mesh_lib.set_current_mesh(self.mesh)  # for mesh-aware model code

        # Tiered store: execute the batch's admission plan first — every
        # slot the step is about to gather must be cache-resident, and
        # evicted rows must be read out before their slots are reused.
        plan = batch.get("__store_plan__")
        if plan is not None:
            batch = {k: v for k, v in batch.items() if k != "__store_plan__"}
            if self.tiered_store is not None:
                state = self.tiered_store.apply_plan(state, plan)

        # Deferred multi-worker mode: the feed shipped the raw sparse
        # batch instead of a plan.  prepare+apply run back to back HERE,
        # inside the step-serialized region (ModelOwner's lock), so plans
        # are produced in exactly the order steps execute — the strict
        # batch-order invariant holds with any number of feed producers.
        pending = batch.get("__store_sparse__")
        if pending is not None:
            batch = {
                k: v for k, v in batch.items() if k != "__store_sparse__"
            }
            if self.tiered_store is not None:
                sparse, ranked = pending
                slots, plan = self.tiered_store.prepare(sparse, ranked=ranked)
                features = dict(batch["features"])
                features["slots"] = slots
                batch = dict(batch)
                batch["features"] = features
                state = self.tiered_store.apply_plan(state, plan)

        # The batch transfer rides inside the serialized region: a
        # device_put racing another thread's step execution corrupts the
        # virtual multi-device CPU backend (see _CPU_EXEC_LOCK).
        def _step():
            sharded = mesh_lib.shard_batch(batch, self.mesh)
            return self.train_step(state, sharded)

        state, loss = self._timed("compute", run_device_serialized, _step)
        return state, loss

    def train_on_batch_stack(self, state, batches):
        """One dispatch covering len(batches) train steps (jitted
        lax.scan).  Returns (state, losses) with losses shaped (K,).
        Batches must share shapes (the data service's static-shape
        contract guarantees it)."""
        from elasticdl_tpu.data.wire import is_packed_dedup

        mesh_lib.set_current_mesh(self.mesh)

        # Tiered store under steps_per_execution > 1 (ISSUE 18c): the K
        # steps run as ONE uninterruptible scan, so admissions are
        # planned once over the UNION of all K batches' rows and applied
        # before the block — every step sees its rows resident, folds
        # land once per block.  Eager per-batch plans are rejected: plan
        # k+1's evictions could reuse a slot batch k still reads, with
        # no apply point between the fused steps (client/api.py forces
        # deferred planning for this reason).
        if any("__store_plan__" in b for b in batches):
            raise ValueError(
                "eager per-batch store plans cannot cover a fused "
                "multi-step block — use TieredStore.enable_deferred_"
                "prepare() so the raw sparse batches arrive here and "
                "one union plan covers the whole block"
            )
        if any("__store_sparse__" in b for b in batches):
            pendings = [b.get("__store_sparse__") for b in batches]
            batches = [
                {k: v for k, v in b.items() if k != "__store_sparse__"}
                for b in batches
            ]
            if self.tiered_store is not None:
                if any(p is None for p in pendings):
                    raise ValueError(
                        "mixed store-prepared and raw batches in one "
                        "fused block"
                    )
                slots_list, plan = self.tiered_store.prepare_block(
                    [sparse for sparse, _ranked in pendings]
                )
                for b, slots in zip(batches, slots_list):
                    features = dict(b["features"])
                    features["slots"] = slots
                    b["features"] = features
                state = self.tiered_store.apply_plan(state, plan)

        stacked = self._timed(
            "pack",
            lambda: jax.tree.map(lambda *xs: np.stack(xs), *batches),
        )
        sharding = mesh_lib.stacked_data_sharding(self.mesh)
        repl = mesh_lib.replicated(self.mesh)

        def put(x):
            if is_packed_dedup(x):
                # only inverse8 is batch-major under the (K, ...) stack;
                # the side planes replicate (see mesh.shard_batch)
                return {
                    k: jax.device_put(
                        v, sharding if k == "inverse8" else repl
                    )
                    for k, v in x.items()
                }
            return jax.device_put(x, sharding)

        def _step():
            placed = jax.tree.map(put, stacked, is_leaf=is_packed_dedup)
            return self.train_step_many(state, placed)

        return self._timed("compute", run_device_serialized, _step)

    def train_on_global_batch_stack(self, state, global_stacked):
        """K-step scan on an already-assembled global (K, B, ...) stack
        (mesh.make_global_batch_stack_from_local) — the multi-process
        steps_per_execution hot path.  Returns (state, losses (K,))."""
        mesh_lib.set_current_mesh(self.mesh)
        return self._timed(
            "compute", run_device_serialized,
            self.train_step_many, state, global_stacked,
        )

    def train_on_global_batch(self, state, global_batch):
        """Train step on a batch already assembled into global arrays
        (mesh.make_global_batch) — the multi-process SPMD hot path."""
        mesh_lib.set_current_mesh(self.mesh)
        return self._timed(
            "compute", run_device_serialized,
            self.train_step, state, global_batch,
        )

    def predict_on_global_batch(self, state, global_features):
        """Forward pass on global arrays; returns the still-global (data-
        sharded) predictions — callers allgather if they need host values."""
        mesh_lib.set_current_mesh(self.mesh)
        return run_device_serialized(self.eval_step, state, global_features)

    def predict_on_batch(self, state, features):
        from elasticdl_tpu.data.wire import is_packed_dedup

        mesh_lib.set_current_mesh(self.mesh)
        repl = mesh_lib.replicated(self.mesh)

        def put(x):
            if is_packed_dedup(x):
                # same placement rule as mesh.shard_batch: only inverse8
                # is batch-major; the side planes replicate
                return {
                    k: jax.device_put(
                        v, self._data if k == "inverse8" else repl
                    )
                    for k, v in x.items()
                }
            return jax.device_put(x, self._data)

        def _step():
            placed = jax.tree.map(put, features, is_leaf=is_packed_dedup)
            return np.asarray(self.eval_step(state, placed))

        return run_device_serialized(_step)

    # ---- elastic prewarm ----------------------------------------------

    def prewarm_for_device_counts(
        self, sample_batch, device_counts, rng=None, block: bool = False,
    ):
        """Populate the persistent compile cache with this model's
        train-step executables for EXPECTED post-failure mesh sizes
        (SURVEY §7 hard part 1's named mitigation): a remesh after a
        preemption then restores with a disk-cache read (measured ~5x
        faster than the cold compile) instead of a fresh XLA compile.

        Runs host-side only — states are abstract ShapeDtypeStructs; no
        device memory is touched.  Data-parallel-default meshes only
        (the elastic unit shrinks along `data`); counts not dividing the
        fixed axes are skipped.  Compiles in a daemon thread unless
        `block` (tests).  Requires identical XLA flags in the restarted
        process for the cache key to match — true for pod relaunches,
        which re-serialize the same argv/env.

        ELASTICDL_FORCE_PREWARM=1 overrides the starved-host core-count
        guard (used by the warm-recovery drill, whose 1-core CI box
        would otherwise never exercise the prewarm path it asserts).
        """
        import os
        import threading

        force = os.environ.get("ELASTICDL_FORCE_PREWARM") == "1"
        if not force and not block and (os.cpu_count() or 1) < 4:
            # A background XLA compile on a starved host (1-2 cores —
            # CI boxes) competes with the training loop for the SAME
            # cores and can stall it past the wedge-watchdog grace
            # (observed in the cluster drills: a 25s prewarm compile got
            # the rank shot as wedged).  Real TPU hosts have 100+ vCPUs;
            # skip only where the background work would do net harm.
            logger.info(
                "prewarm skipped: %s cores is too few to compile in the "
                "background without starving the training loop",
                os.cpu_count(),
            )
            return None
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        features = jax.tree.map(np.asarray, sample_batch["features"])

        def work():
            for count in device_counts:
                try:
                    self._prewarm_one(count, features, sample_batch, rng)
                except Exception as exc:  # advisory path, never fatal
                    logger.info(
                        "prewarm for %d devices skipped: %s", count, exc
                    )

        if block:
            work()
            return None
        thread = threading.Thread(target=work, daemon=True)
        thread.start()
        return thread

    def _prewarm_one(self, count, features, sample_batch, rng):
        import time as _time

        t0 = _time.perf_counter()
        devices = jax.devices()
        if not 0 < count <= len(devices):
            return
        mesh = mesh_lib.create_mesh(devices[:count])
        warm = Trainer(
            model=self.model, optimizer=self.optimizer,
            loss_fn=self.loss_fn, mesh=mesh, use_bf16=self.use_bf16,
            param_sharding_fn=self._param_sharding_fn,
        )
        prev_mesh = mesh_lib.get_current_mesh()
        kwargs = {"train": False} if self._has_train_kwarg else {}

        def make():
            params, variables = split_variables(
                self.model.init(rng, warm._cast(features), **kwargs)
            )
            return TrainState(
                step=jnp.zeros((), jnp.int32),
                params=params,
                opt_state=self.optimizer.init(params),
                model_state=variables,
            )

        # everything tracing under the prewarm mesh sits inside the
        # try/finally: a failure anywhere (eval_shape, sharding, lower)
        # must not leak the small mesh into the caller thread's TLS
        # (block=True runs on the caller's thread)
        mesh_lib.set_thread_mesh(mesh)
        try:
            shapes = jax.eval_shape(make)
            shardings = warm.state_sharding(shapes)
            abstract_state = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(
                    s.shape, s.dtype, sharding=sh
                ),
                shapes, shardings,
            )
            abstract_batch = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    np.asarray(a).shape, np.asarray(a).dtype,
                    sharding=warm._data,
                ),
                sample_batch,
            )
            warm.train_step.aot_compile(abstract_state, abstract_batch)
        finally:
            mesh_lib.set_thread_mesh(prev_mesh)
        logger.info(
            "prewarmed train step for %d-device mesh in %.1fs (persistent"
            " cache populated)", count, _time.perf_counter() - t0,
        )
