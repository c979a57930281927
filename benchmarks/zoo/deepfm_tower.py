"""The zoo's DeepFM with the deep tower's widths as a model parameter.

`model_zoo/deepfm/deepfm_functional_api.custom_model` builds `DeepFM`
with its default tower (256, 128) and takes no `mlp_dims`, so a published
tower cannot be asked for from the command line.  This module is that
one argument: the same `DeepFM` class, and every other name (`loss`,
`optimizer`, `feed`, `feed_bulk`, ...) is the zoo module's own.  Listed
in PERF.md for a later PR to fold into the zoo and delete here.
"""

from model_zoo.deepfm import deepfm_functional_api as _zoo


def custom_model(vocab_capacity: int, embed_dim: int, mlp_dims,
                 bf16: bool = False):
    model = _zoo.custom_model(
        vocab_capacity=vocab_capacity, embed_dim=embed_dim, bf16=bf16
    )
    return model.clone(mlp_dims=tuple(int(width) for width in mlp_dims))


def __getattr__(name):
    return getattr(_zoo, name)
