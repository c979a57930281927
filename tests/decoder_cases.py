"""The decoder models' conformance suite: what every
`tests/test_<decoder>.py` holds its model to, written once.

A model's file states a `Decoder` (its zoo module and its plain
reference, the cell whose `model_params` template builds it, its tiny
CONFIG under the published key names, what it expects of each case) and
collects the cases with `TestConformance = conformance(DECODER)`; the
cases a model alone has stay in its file and read the same helpers and
the same fixtures (`seeded`, `computed`: imported into the file, built
once a module).  No case's body names a model.

Every program here is ONE compiled program a (model, type, remat form):
`model.init` and the gradient are compiled, not walked a primitive at a
time (`UNFUSED` says with what numerics).

What `remat=True` is held to (`model_zoo/common/decoder.py: remat_block`
keeps the attention core's output and log-sum-exp from the forward and
rebuilds the rest of a block, the lean policy of a device with no room):
the block rebuilt whole, which is the plain `nn.remat` every commit
before ran, no remat at all, and every named product kept beside the
core's (`remat_blocks` given a room that holds them all)."""

import dataclasses
import functools
import json
import os
import threading
import types
import typing

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import datagen, trees
from elasticdl_tpu.common.model_handler import _call_with_params
from elasticdl_tpu.layers.moe import ROUTER_STATE
from elasticdl_tpu.layers.step_metrics import AUX_LOSS, STEP_METRICS

ROOT = os.path.join(os.path.dirname(__file__), "..")
# (a collection a model does not fill costs nothing)
MUTABLE = [AUX_LOSS, STEP_METRICS, ROUTER_STATE]
OTHERS = ["no-remat", "plain-remat", "all-kept"]
# a device's room no model of the tests fills
ALL_THE_ROOM = 1 << 50
# How the gradients' programs are compiled: no operation fused into
# another and bfloat16 rounded wherever the program writes it, which are
# the numerics of the walk a primitive at a time these cases ran before.
# XLA on the CPU fuses a block otherwise inside a remat's computation than
# outside one (a fused multiply-add rounds once where two operations round
# twice) and keeps float32 between two bfloat16 operations it has fused:
# compiled its own way, Kimi's float32 gradients differ in all 84 leaves
# between no remat and the lean policy (to 7e-5 of a leaf) and its
# bfloat16 `A_log` reads 1.13 of the twin's error where walked it read
# 0.6-0.9; compiled this way the remat forms are equal bit for bit in
# both types, as they were walked.
UNFUSED = {
    "xla_disable_hlo_passes": "fusion", "xla_allow_excess_precision": False,
}


def compiled(fn, *args):
    """fn(*args) through ONE program compiled `UNFUSED`, at the matmul
    precision the reference computes in."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn).lower(*args).compile(
            compiler_options=UNFUSED
        )(*args)


@functools.lru_cache(None)
def cell_config(cell):
    """`benchmarks/configs/<cell>.json`, read once (and only read)."""
    with open(os.path.join(
        ROOT, "benchmarks", "configs", f"{cell}.json"
    )) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Kernels:
    """The override of a tiny CONFIG that reaches the kernels' shapes:
    ONE sequence of `length` ids over `config`, and the `*_shapes_ok`
    calls, (function, *arguments) each, that prove the kernels run."""

    config: dict
    length: int
    admitted: tuple


@dataclasses.dataclass(frozen=True)
class Published:
    """The cut model at the published widths: parameters by top-level
    name, their sum (`None` where `also` holds both to the cell's own
    cut), the bytes a parameter takes in the step that put the model over
    the floor, and the model's further asserts,
    `also(model, config, shapes, flat, by_top)`."""

    by_top: typing.Optional[dict]
    total: typing.Optional[int]
    bytes_a_parameter: int = 12
    also: typing.Callable = None


@dataclasses.dataclass(frozen=True)
class Job:
    """The two-task CLI job: the `--model_params` string, by how much the
    loss falls, whether the device has room for every named product (the
    kept gauge then reads 1) and the gauges read at the end,
    `gauges(registry)`."""

    params: str
    gauges: typing.Callable
    falls_by: float = 0.05
    all_the_room: bool = True
    seq_len: int = 32


@dataclasses.dataclass(frozen=True)
class Scopes:
    """The scope names under the model's `prefix` that are catalogued
    (`profiler.DEVICE_SCOPES`) and reach the lowered forward of the model
    built with `remat`; `also(text)` for the rest."""

    prefix: str
    names: tuple
    remat: bool = False
    also: typing.Callable = None


@dataclasses.dataclass(frozen=True)
class Decoder:
    """One decoder model's description: everything the shared cases ask
    of it.  A case whose field is `None` is not collected."""

    zoo: types.ModuleType
    reference: types.ModuleType
    cell: str                     # benchmarks/configs/<cell>.json
    config: dict                  # tiny, under the published key names
    length: int                   # positions of the seeded 8 sequences
    seed: int                     # of their ids
    leaves: int                   # of the parameter tree
    # further asserts of the float32 case, (model, seeded, got)
    float32_also: typing.Callable = None
    loss_limit: float = 1e-4
    # params -> params: seeds moved off what `init` gives (the Trainer's
    # own `init` then starts elsewhere than `seeded`)
    reseed: typing.Callable = None
    kernels: typing.Optional[Kernels] = None
    # the model's types the remat policy is held in
    remat_types: tuple = (False, True)
    no_remat_limit: float = 0.0
    # the (other, bf16) whose two sides are walked, not compiled
    remat_walked: tuple = ()
    # what the twin's namespace holds beside the reference's own
    twin_held: dict = dataclasses.field(default_factory=dict)
    # {name: overrides of `model_of` | a change made with monkeypatch}
    controls: typing.Optional[dict] = None
    # (control, variables) -> variables, (control, got) -> got
    control_variables: typing.Callable = None
    control_leaves: typing.Callable = None
    published: typing.Optional[Published] = None
    # the Trainer case's asserts, (metrics, state, loss, seeded)
    trainer_gauges: typing.Callable = None
    job: typing.Optional[Job] = None
    scopes: typing.Optional[Scopes] = None

    def model_of(self, config, **overrides):
        """The model as the cell builds it: the cell file's own
        `model_params` template formatted over `config`, in float32
        unless told otherwise, the overrides laid over it as a job's
        `--model_params` would carry them."""
        return _call_with_params(self.zoo.custom_model, ";".join([
            cell_config(self.cell)["model_params"].format(**config),
            "bf16=False",
            *(f"{key}={value!r}" for key, value in overrides.items()),
        ]))

    def ids_of(self, rows, length=None, seed=0):
        return np.random.RandomState(seed).randint(
            0, self.config["vocab_size"], (rows, length or self.length)
        ).astype(np.int32)

    def objective(self, model, params, state, ids, room=None):
        """The objective the Trainer builds: the mean of the model's
        per-position losses plus everything sown into AUX_LOSS."""
        out, sown = model.apply(
            {"params": params, **state}, {"input_ids": ids}, mutable=MUTABLE,
            **({} if room is None else {"room": room}),
        )
        return self.zoo.loss(None, out.astype(jnp.float32)) + sum(
            jax.tree.leaves(sown.get(AUX_LOSS, {}))
        )

    def loss_of(self, model, variables, ids):
        state = state_of(variables)
        return float(compiled(
            lambda params: self.objective(model, params, state, ids),
            variables["params"],
        ))

    def loss_and_grads(self, model, variables, ids, room=None, walked=False):
        """(loss, {leaf: gradient}) through one compiled program, or
        `walked` a primitive at a time as these cases ran before they were
        compiled: kept where compiling moves a bit that the walk held."""
        state = state_of(variables)
        grad = jax.value_and_grad(
            lambda params: self.objective(model, params, state, ids, room)
        )
        if walked:
            with jax.default_matmul_precision("highest"):
                loss, grads = grad(variables["params"])
        else:
            loss, grads = compiled(grad, variables["params"])
        return float(loss), {
            k: np.asarray(v, np.float32) for k, v in trees.flat(grads).items()
        }

    def seeded_of(self, config, ids):
        """Seeded weights over `ids`, flat for the reference, and the
        reference's loss and gradients on them."""
        from elasticdl_tpu.worker.trainer import split_variables

        # as the Trainer seeds a model: what `init` sowed is dropped and
        # the compiler drops the forward `init` traced with it
        model = self.model_of(config)
        params, state = jax.jit(
            lambda key, features: split_variables(model.init(key, features))
        )(jax.random.PRNGKey(0), {"input_ids": ids})
        variables = {**params, **state}
        if self.reseed is not None:
            variables["params"] = self.reseed(variables["params"])
        flat = {
            k: np.asarray(v)
            for k, v in trees.flat(variables["params"]).items()
        }
        want_loss, want = self.reference.loss_and_grads(
            flat, {"input_ids": ids}, None, config
        )
        return types.SimpleNamespace(
            ids=ids, variables=variables, flat=flat, want_loss=want_loss,
            want={k: np.asarray(v) for k, v in want.items()},
        )


def state_of(variables):
    """The collections a step carries beside the parameters; AUX_LOSS is
    none of them, as the Trainer drops it."""
    return {
        k: v for k, v in variables.items() if k not in ("params", AUX_LOSS)
    }


@pytest.fixture(scope="module")
def seeded(request):
    decoder = request.module.DECODER
    return decoder.seeded_of(
        decoder.config, decoder.ids_of(8, seed=decoder.seed)
    )


@pytest.fixture(scope="module")
def computed(request, seeded):
    """bf16 -> (loss, gradients) of the model as the cells run it
    (`remat=True`, no room) on the seeded weights: built once a type and
    read by every case that compares with it."""
    decoder = request.module.DECODER
    built = {}

    def of(bf16=False, walked=False):
        if (bf16, walked) not in built:
            built[bf16, walked] = decoder.loss_and_grads(
                decoder.model_of(decoder.config, bf16=bf16),
                seeded.variables, seeded.ids, walked=walked,
            )
        return built[bf16, walked]

    return of


def worst_leaf(got, want):
    assert set(got) == set(want)
    errors = {
        name: np.linalg.norm(got[name] - ref) / np.linalg.norm(ref)
        for name, ref in want.items()
    }
    name = max(errors, key=errors.get)
    return name, errors[name]


def assert_saving_changes_nothing(zoo, monkeypatch, other, grads_of, want,
                                  no_remat_limit=0.0):
    """`grads_of(remat, room)` -> (loss, {leaf: gradient}) of the model
    built with that `remat` and applied with that `room`; `want` the
    same of the model as the cells run it with no room.  The plain
    remat's and the all-kept remat's are equal bit for bit; no remat's
    too, or within `no_remat_limit` of a leaf's norm where the CPU's
    fusions round a block otherwise inside a remat's computation than
    outside one."""
    exact = other != "no-remat"
    if other == "plain-remat":
        monkeypatch.setattr(
            zoo, "remat_blocks",
            lambda block_cls, config, kinds, *rest, **more: (
                [nn.remat(block_cls)] * len(kinds)
            ),
        )
    loss, got = grads_of(
        exact, ALL_THE_ROOM if other == "all-kept" else None
    )
    want_loss, want_grads = want
    assert loss == want_loss and set(got) == set(want_grads)
    for name, leaf in want_grads.items():
        if exact or not no_remat_limit:
            np.testing.assert_array_equal(got[name], leaf, err_msg=name)
        else:
            error = np.linalg.norm(got[name] - leaf)
            assert error <= no_remat_limit * np.linalg.norm(leaf), name


def grad_program_digest(model, batch: int = 2, length: int = 128):
    """sha256 of the program d(mean loss) / d(parameters) lowers to over
    (`batch`, `length`) ids, before any compiler pass and with the
    numbering of its private functions blanked (it follows the count of
    the jaxpr's equations): what a commit's train step is held to where
    nothing of it may move.  (A `checkpoint_name` no policy lists lowers
    to nothing.)"""
    import hashlib
    import re

    features = {"input_ids": jnp.zeros((batch, length), jnp.int32)}
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), features)
    state = {k: v for k, v in shapes.items() if k != "params"}

    def loss(params, state):
        out, _ = model.apply(
            {"params": params, **state}, features, mutable=True
        )
        return out.mean()

    text = re.sub(
        r"(@[A-Za-z_]\w*?)_\d+\b", r"\1",
        jax.jit(jax.grad(loss)).lower(shapes["params"], state).as_text(),
    )
    return hashlib.sha256(text.encode()).hexdigest()


def recorded_workers(monkeypatch):
    """The list every `Worker` built from here on adds itself to."""
    from elasticdl_tpu.worker.worker import Worker

    workers = []
    init = Worker.__init__

    def recording_init(self, *args, **kwargs):
        init(self, *args, **kwargs)
        workers.append(self)

    monkeypatch.setattr(Worker, "__init__", recording_init)
    return workers


def sizes_by_top(flat):
    by_top = {}
    for name, size in flat.items():
        top = name.split("/")[0]
        by_top[top] = by_top.get(top, 0) + size
    return by_top


class Conformance:
    """The nine cases; `conformance` binds them to a description."""

    decoder: Decoder

    def pytest_generate_tests(self, metafunc):
        names = metafunc.fixturenames
        if "other" in names:
            metafunc.parametrize("other", OTHERS)
        if "bf16" in names:
            types_ = self.decoder.remat_types
            metafunc.parametrize("bf16", types_, ids=[
                "bfloat16" if bf16 else "float32" for bf16 in types_
            ])
        if "control" in names:
            metafunc.parametrize("control", sorted(self.decoder.controls))

    def test_float32_matches_reference_leaf_by_leaf(self, seeded, computed):
        """The model as the cells run it, in float32, against the plain
        reference on the same seeded weights: the loss, the tree's leaves
        and every leaf's gradient."""
        d = self.decoder
        loss, got = computed(False)
        assert abs(loss - seeded.want_loss) < (
            d.loss_limit * abs(seeded.want_loss)
        )
        assert len(got) == d.leaves
        name, error = worst_leaf(got, seeded.want)
        assert error < 1e-4, (name, error)
        if d.float32_also is not None:
            d.float32_also(d.model_of(d.config), seeded, got)

    def test_kernels_match_reference_leaf_by_leaf(self):
        """The description's override reaches the kernels' shapes (its
        `admitted` calls prove it): the Pallas kernels, interpreted here,
        inside the whole gradient against the reference."""
        d = self.decoder
        config = dict(d.config, **d.kernels.config)
        for shapes_ok, *shapes in d.kernels.admitted:
            assert shapes_ok(*shapes), (shapes_ok.__name__, shapes)
        seeded = d.seeded_of(
            config, d.ids_of(1, length=d.kernels.length, seed=2)
        )
        loss, got = d.loss_and_grads(
            d.model_of(config), seeded.variables, seeded.ids
        )
        assert abs(loss - seeded.want_loss) < 1e-4 * abs(seeded.want_loss)
        name, error = worst_leaf(got, seeded.want)
        assert error < 2e-4, (name, error)

    def test_the_remat_policy_changes_no_bit(self, seeded, computed,
                                             monkeypatch, other, bf16):
        """`remat=True` against the plain `nn.remat` every commit before
        ran, against no remat at all and against every named product
        kept, bit for bit (no remat's within the description's
        `no_remat_limit` of a leaf where it states one); both sides are
        built the same way, compiled or, where the description says so,
        walked."""
        d = self.decoder
        walked = (other, bf16) in d.remat_walked
        assert_saving_changes_nothing(
            d.zoo, monkeypatch, other,
            lambda remat, room=None: d.loss_and_grads(
                d.model_of(d.config, bf16=bf16, remat=remat),
                seeded.variables, seeded.ids, room, walked,
            ),
            computed(bf16, walked), d.no_remat_limit,
        )

    def test_bfloat16_inside_the_twins_rule(self, seeded, computed):
        """The model computing in bfloat16 is held as the benchmark holds
        a cell that states it: to the reference's own bfloat16 twin, leaf
        by leaf and on the angle (`check_gradient`), where the float8
        control in the step's place fails."""
        from benchmarks.drivers import train

        d = self.decoder
        held = types.SimpleNamespace(**{
            **{k: getattr(d.reference, k) for k in dir(d.reference)
               if not k.startswith("__")},
            "STATED_RATIO": d.reference.TWIN_RATIO, **d.twin_held,
        })
        features = {"input_ids": seeded.ids}
        labels = np.zeros(len(seeded.ids), np.int32)
        _, got = computed(True)
        check = train.check_gradient(
            held, seeded.flat, features, labels, d.config, seeded.want, got
        )
        assert check["ok"], sorted(
            check["shares"].items(), key=lambda kv: -kv[1]
        )[:4]
        _, control = d.reference.loss_and_grads(
            seeded.flat, features, labels, d.config, tower="float8_e4m3fn"
        )
        control = {k: np.asarray(v, np.float32) for k, v in control.items()}
        assert not train.check_gradient(
            held, seeded.flat, features, labels, d.config, seeded.want,
            control,
        )["ok"]

    def test_a_departure_from_the_mathematics_fails_the_comparison(
            self, seeded, monkeypatch, control):
        """The comparison that passes the model fails each of the
        description's controls: the loss moves by over 1e-3 of itself, or
        a leaf's gradient by over 1e-2 of its norm (read only where the
        loss has not moved: the forward alone is a third of the program)."""
        d = self.decoder
        change = d.controls[control]
        overrides = change if isinstance(change, dict) else {}
        if not overrides:
            change(monkeypatch)
        variables = seeded.variables
        if d.control_variables is not None:
            variables = d.control_variables(control, variables)
        model = d.model_of(d.config, **overrides)
        loss = d.loss_of(model, variables, seeded.ids)
        if abs(loss - seeded.want_loss) > 1e-3 * abs(seeded.want_loss):
            return
        loss, got = d.loss_and_grads(model, variables, seeded.ids)
        if d.control_leaves is not None:
            got = d.control_leaves(control, got)
        name, error = worst_leaf(got, seeded.want)
        assert (
            abs(loss - seeded.want_loss) > 1e-3 * abs(seeded.want_loss)
            or error > 1e-2
        ), (control, loss, seeded.want_loss, name, error)

    def test_published_sizes_hold_what_the_configuration_states(self):
        """The parameters of the cut model at the published widths,
        counted from the built model's shapes: the numbers in the
        configuration's `deployment` and its `parameters_held`, and over
        the floor held alone."""
        d = self.decoder
        config = cell_config(d.cell)
        model = _call_with_params(
            d.zoo.custom_model, config["model_params"].format(**config)
        )
        assert model.config.dtype == jnp.bfloat16 and model.config.remat
        shapes = jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0),
            {"input_ids": jnp.zeros((1, 512), jnp.int32)},
        ))
        flat = {
            name: int(np.prod(leaf.shape))
            for name, leaf in trees.flat(shapes["params"]).items()
        }
        by_top = sizes_by_top(flat)
        total = sum(by_top.values())
        if d.published.by_top is not None:
            assert by_top == d.published.by_top
        if d.published.total is not None:
            assert total == d.published.total
        assert total == config.get("parameters_held", total)
        assert f"{total:,}" in config["deployment"]
        assert d.published.bytes_a_parameter * total > 0.25 * 16.9e9
        if d.published.also is not None:
            d.published.also(model, config, shapes, flat, by_top)

    def test_trainer_carries_the_models_gauges(self, seeded):
        """One step through the Trainer on the seeded ids: the loss is
        the reference's, and what the model sows reaches
        `ModelOwner.fetch_loss` under the names the description reads."""
        from elasticdl_tpu.worker.sync import ModelOwner
        from elasticdl_tpu.worker.trainer import Trainer

        d = self.decoder
        trainer = Trainer(
            model=d.model_of(d.config), optimizer=d.zoo.optimizer(1e-3),
            loss_fn=d.zoo.loss,
        )
        batch = {"features": {"input_ids": seeded.ids},
                 "labels": np.zeros(len(seeded.ids), np.int32)}
        state = trainer.init_state(jax.random.PRNGKey(0), batch["features"])
        state, loss = trainer.train_on_batch(state, batch)
        if d.reseed is None:
            assert float(loss) == pytest.approx(seeded.want_loss, rel=1e-3)
        owner = ModelOwner.__new__(ModelOwner)
        owner.state, owner.lock = state, threading.Lock()
        value, metrics = owner.fetch_loss(loss)
        assert value == pytest.approx(float(loss))
        d.trainer_gauges(metrics, state, loss, seeded)

    def test_cli_job_of_two_tasks_with_a_falling_loss(self, tmp_path,
                                                      monkeypatch):
        from elasticdl_tpu.client.main import main as cli_main
        from elasticdl_tpu.common import metrics as metrics_lib
        from elasticdl_tpu.worker import trainer as trainer_lib

        d = self.decoder
        if d.job.all_the_room:
            # a device with room for every named product
            monkeypatch.setattr(
                trainer_lib, "device_room", lambda mesh: ALL_THE_ROOM
            )
        path = str(tmp_path / "train.tfrecord")
        datagen.write_task_file(
            path, 7, {"format": "tokens", "seq_len": d.job.seq_len,
                      "vocab_size": 50}, 64, 2,
        )
        workers = recorded_workers(monkeypatch)
        rc = cli_main([
            "train", "--model_zoo", os.path.join(ROOT, "model_zoo"),
            "--model_def",
            d.zoo.__name__.split(".", 1)[1] + ".custom_model",
            "--model_params", d.job.params,
            "--distribution_strategy", "Local", "--training_data", path,
            "--minibatch_size", "8", "--records_per_task", "64",
            "--num_epochs", "1",
        ])
        assert rc == 0
        losses = [float(x) for x in workers[0].losses]
        assert len(losses) == 16                      # two tasks of 8 steps
        assert np.mean(losses[-4:]) < np.mean(losses[:4]) - d.job.falls_by
        registry = metrics_lib.default_registry()
        if d.job.all_the_room:
            assert registry.value("worker_remat_kept_ratio") == 1.0
        d.job.gauges(registry)

    def test_the_scopes_reach_the_lowered_operations(self):
        """The description's scopes carry the model's prefix into the
        operations' names of the lowered forward, and each is a catalogue
        entry of the profiler's table."""
        from elasticdl_tpu.common import profiler

        d = self.decoder
        model = d.model_of(d.config, remat=d.scopes.remat)
        ids = d.ids_of(1, length=16)
        variables = jax.eval_shape(
            model.init, jax.random.PRNGKey(0), {"input_ids": ids}
        )
        text = jax.jit(
            lambda v, ids: model.apply(
                v, {"input_ids": ids}, mutable=MUTABLE
            )[0]
        ).lower(variables, ids).as_text(debug_info=True)
        for scope in d.scopes.names:
            assert f"{d.scopes.prefix}/{scope}" in profiler.DEVICE_SCOPES
            assert f"{d.scopes.prefix}/{scope}/" in text, scope
        assert "Scope object" not in text
        if d.scopes.also is not None:
            d.scopes.also(text)


# the description's field each case needs: without it the case is not one
# of the model's
NEEDS = {
    "test_kernels_match_reference_leaf_by_leaf": "kernels",
    "test_a_departure_from_the_mathematics_fails_the_comparison": "controls",
    "test_published_sizes_hold_what_the_configuration_states": "published",
    "test_trainer_carries_the_models_gauges": "trainer_gauges",
    "test_cli_job_of_two_tasks_with_a_falling_loss": "job",
    "test_the_scopes_reach_the_lowered_operations": "scopes",
}


def conformance(decoder, **own):
    """The class a model's file collects: the shared cases its
    description has the fields for, and `own` in place of a case the
    model states otherwise."""
    absent = {
        case: None for case, field in NEEDS.items()
        if getattr(decoder, field) is None
    }
    return type("TestConformance", (Conformance,), {
        "decoder": decoder, **absent, **own,
    })
