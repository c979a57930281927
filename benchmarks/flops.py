"""Operations the algorithm needs, from shapes alone (never from XLA's
`cost_analysis`).  A multiply-add is 2 operations; backward costs twice
forward; recomputation is not counted."""

from __future__ import annotations


def bert_forward_flops_per_example(config: dict, seq_len: int) -> float:
    """Matrix multiplications of one sequence through the encoder and the
    classifier head: QKV, scores, context, output projection, two MLP
    matmuls a layer.  Embedding lookups, LayerNorm, softmax, GELU and
    bias adds are left out, as the usual MFU accounting does."""
    h = config["hidden_size"]
    layers = config["num_hidden_layers"]
    mlp = config["intermediate_size"]
    length = seq_len
    per_layer = (
        2 * length * h * 3 * h        # qkv projection
        + 2 * length * length * h     # q @ k^T over all heads
        + 2 * length * length * h     # probabilities @ v
        + 2 * length * h * h          # output projection
        + 2 * length * h * mlp * 2    # MLP up and down
    )
    head = 2 * h * config.get("num_labels", 2)
    return float(layers * per_layer + head)


def bert_train_flops_per_example(config: dict, seq_len: int) -> float:
    return 3.0 * bert_forward_flops_per_example(config, seq_len)


def deepfm_forward_flops_per_example(config: dict) -> float:
    """The deep tower's matmuls and the FM reductions of one example."""
    fields = config["num_sparse_fields"]
    dense = config["num_dense_fields"]
    k = config["embed_dim"]
    widths = [dense + fields * k, *config["mlp_dims"], 1]
    mlp = sum(2 * a * b for a, b in zip(widths, widths[1:]))
    fm = 4 * fields * k
    return float(mlp + fm + 2 * dense)


def deepfm_train_flops_per_example(config: dict) -> float:
    return 3.0 * deepfm_forward_flops_per_example(config)


TRAIN_FLOPS = {
    "bert": lambda config, traffic: bert_train_flops_per_example(
        config, traffic["seq_len"]
    ),
    "deepfm": lambda config, traffic: deepfm_train_flops_per_example(config),
}
