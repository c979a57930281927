"""What the decoder models' tests hold `remat=True` to
(`model_zoo/common/decoder.py: remat_block` keeps the attention core's
output and log-sum-exp from the forward and rebuilds the rest of a
block, the lean policy of a device with no room): the block rebuilt
whole, which is the plain `nn.remat` every commit before ran, no remat
at all, and every named product kept beside the core's (`remat_blocks`
given a room that holds them all)."""

import flax.linen as nn
import numpy as np

OTHERS = ["no-remat", "plain-remat", "all-kept"]
# a device's room no model of the tests fills
ALL_THE_ROOM = 1 << 50


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

    import jax
    import jax.numpy as jnp

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
