"""Operations and bytes the Laguna decoder's train step needs, from the
configuration's shapes alone (never from XLA's `cost_analysis`), by PART,
as `flops_lm.py` counts the MLA decoder's.  A multiply-add is 2
operations; backward costs twice forward; recomputation (remat, the
attention backward's rebuilt logits) is not counted.

All counts are for the configuration AS CUT: the first
`num_hidden_layers` entries of the published per-layer lists, the held
experts and the vocabulary slice the file states.

    full core     a `full_attention` layer's q k^T and p v, THE CAUSAL
                  HALF COUNTED: position t attends t + 1 keys
    window core   a `sliding_attention` layer's, THE BAND COUNTED:
                  position t attends min(t + 1, sliding_window) keys
    bytes         q, o and their gradients once a QUERY head, k, v and
                  their gradients once a K/V head: a group's keys and
                  values are read once, however many query heads share
                  them
"""

from __future__ import annotations

FULL, WINDOW = "full_attention", "sliding_attention"


def layers(config: dict) -> list:
    """[(kind, query heads, routed?)] of the layers the cut model has."""
    return [
        (kind, int(heads), mlp == "sparse") for kind, heads, mlp in zip(
            config["layer_types"], config["num_attention_heads_per_layer"],
            config["mlp_layer_types"],
        )
    ][:config["num_hidden_layers"]]


def keys_per_query(kind: str, config: dict, seq_len: int) -> float:
    """Keys a query attends, averaged over the sequence's positions."""
    if kind == FULL:
        return (seq_len + 1) / 2
    window = min(config["sliding_window"], seq_len)
    return (
        window * (window + 1) / 2 + (seq_len - window) * window
    ) / seq_len


def core_flops_per_token(kind: str, heads: int, config: dict,
                         seq_len: int) -> float:
    """q k^T and p v of one token's row in one layer, forward."""
    return 2.0 * heads * 2 * config["head_dim"] * keys_per_query(
        kind, config, seq_len
    )


def attn_proj_flops_per_token(heads: int, config: dict) -> float:
    """q, k, v, the gate and o of one layer."""
    d, dim = config["hidden_size"], config["head_dim"]
    kv = config["num_key_value_heads"]
    return 2.0 * d * (heads * dim + 2 * kv * dim + heads + heads * dim)


def swiglu_flops_per_token(hidden: int, width: int) -> float:
    return 2.0 * 3 * hidden * width


def held_share(config: dict) -> float:
    """Routing slots that land on a held expert under balanced load."""
    return config["held_experts"][1] / config["num_experts_published"]


def forward_flops_per_token(config: dict, seq_len: int,
                            routed_here: float = None) -> dict:
    """{part: matmul operations of one token, forward, over the whole
    cut model}.  `routed_here` is the share of the tokens x top_k slots
    that chose a held expert (`held_share` when not measured)."""
    if routed_here is None:
        routed_here = held_share(config)
    d = config["hidden_size"]
    expert = swiglu_flops_per_token(d, config["moe_intermediate_size"])
    parts = dict.fromkeys((
        "attn_proj", "full_core", "window_core", "dense_ffn", "moe_router",
        "moe_shared", "moe_experts",
    ), 0.0)
    for kind, heads, routed in layers(config):
        parts["attn_proj"] += attn_proj_flops_per_token(heads, config)
        core = "window_core" if kind == WINDOW else "full_core"
        parts[core] += core_flops_per_token(kind, heads, config, seq_len)
        if routed:
            parts["moe_router"] += 2.0 * d * config["num_experts_published"]
            parts["moe_shared"] += swiglu_flops_per_token(
                d, config["shared_expert_intermediate_size"]
            )
            parts["moe_experts"] += (
                expert * config["num_experts_per_tok"] * routed_here
            )
        else:
            parts["dense_ffn"] += swiglu_flops_per_token(
                d, config["intermediate_size"]
            )
    parts["head"] = 2.0 * d * config["vocab_size"]
    return parts


def train_flops_per_token(config: dict, seq_len: int,
                          routed_here: float = None) -> float:
    return 3.0 * sum(
        forward_flops_per_token(config, seq_len, routed_here).values()
    )


def core_train_flops_per_step(config: dict, traffic: dict,
                              kind: str) -> float:
    """Every attention core of `kind` of a step, forward (q k^T, p v)
    plus backward (dV, dP, dQ, dK: four products of the same size, twice
    the forward); the backward's rebuilt logits are recomputation."""
    tokens = traffic["minibatch_size"] * traffic["seq_len"]
    part = "window_core" if kind == WINDOW else "full_core"
    return 3.0 * tokens * forward_flops_per_token(
        config, traffic["seq_len"]
    )[part]


def core_train_bytes_per_step(config: dict, traffic: dict,
                              kind: str) -> float:
    """The least HBM traffic of the attention cores of `kind` of a step
    in the stated 2-byte type: forward reads q, k, v and writes o;
    backward reads q, k, v, o, dO and writes dQ, dK, dV; k, v, dK and dV
    are Hkv heads wide (log-sum-exp and delta are 1/128 of a row and left
    out)."""
    dim, kv = config["head_dim"], config["num_key_value_heads"]
    tokens = traffic["minibatch_size"] * traffic["seq_len"]
    total = 0.0
    for layer_kind, heads, _ in layers(config):
        if layer_kind == kind:
            forward = 2 * heads + 2 * kv
            backward = (3 * heads + 2 * kv) + (heads + 2 * kv)
            total += 2.0 * dim * (forward + backward) * tokens
    return total
