"""What the three decoder models' tests hold `remat=True` to
(`model_zoo/common/decoder.py: remat_block` keeps the attention core's
output and log-sum-exp from the forward and rebuilds the rest of a
block): the block rebuilt whole, which is the plain `nn.remat` every
commit before ran, and no remat at all."""

import flax.linen as nn
import numpy as np

OTHERS = ["no-remat", "plain-remat"]


def assert_saving_changes_nothing(zoo, monkeypatch, other, grads_of, want,
                                  no_remat_limit=0.0):
    """`grads_of(remat)` -> (loss, {leaf: gradient}) of the model built
    with that `remat`; `want` the same of the model as the cells run it.
    The plain remat's is equal bit for bit; no remat's too, or within
    `no_remat_limit` of a leaf's norm where the CPU's fusions round a
    block otherwise inside a remat's computation than outside one."""
    plain = other == "plain-remat"
    if plain:
        monkeypatch.setattr(zoo, "remat_block", nn.remat)
    loss, got = grads_of(plain)
    want_loss, want_grads = want
    assert loss == want_loss and set(got) == set(want_grads)
    for name, leaf in want_grads.items():
        if plain or not no_remat_limit:
            np.testing.assert_array_equal(got[name], leaf, err_msg=name)
        else:
            error = np.linalg.norm(got[name] - leaf)
            assert error <= no_remat_limit * np.linalg.norm(leaf), name
