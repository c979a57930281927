"""Operations and bytes the LFM2 decoder's train step needs, from the
configuration's shapes alone (never from XLA's `cost_analysis`), by PART,
as `flops_laguna.py` counts the Laguna decoder's.  A multiply-add is 2
operations; backward costs twice forward; recomputation (remat, the
attention backward's rebuilt logits, the conv backward's rebuilt z) is
not counted.

All counts are for the configuration AS CUT: the published layers in
`layers_held`, the held experts and the vocabulary slice the file states.

    short conv    the pass between the conv operator's two projections,
                  y = C * conv_K(B * u), whatever implements it.  Forward
                  reads three streams of tokens x d and writes one;
                  backward reads four (B, C, u and dy) and writes three
                  (dB, dC, du); the K x d taps and their gradient are
                  nothing beside them.  An element costs 2K + 1
                  operations forward (the gate B * u, K multiplies and
                  K - 1 adds, the gate C *) and 4K + 3 backward (dz, the
                  K multiplies and K - 1 adds of dx, the K multiplies and
                  K adds of dw, dB, du and dC); z rebuilt for dC is
                  recomputation.  Memory bounds it a hundred times over.
    full core     a `full_attention` layer's q k^T and p v, THE CAUSAL
                  HALF COUNTED: position t attends t + 1 keys
    bytes (core)  q, o and their gradients once a QUERY head, k, v and
                  their gradients once a K/V head
"""

from __future__ import annotations

CONV, FULL = "conv", "full_attention"
BYTES = 2          # the stated type, bfloat16


def layers(config: dict) -> list:
    """[(kind, routed?)] of the layers the cut model has."""
    return [
        (config["layer_types"][i],
         i >= config["num_dense_layers_published"])
        for i in config["layers_held"]
    ]


def count(config: dict, kind: str) -> int:
    return sum(1 for k, _ in layers(config) if k == kind)


def tokens_per_step(traffic: dict) -> int:
    return traffic["minibatch_size"] * traffic["seq_len"]


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
    d, heads = config["hidden_size"], config["num_attention_heads"]
    dim, kv = config["head_dim"], config["num_key_value_heads"]
    parts = dict.fromkeys((
        "conv_proj", "attn_proj", "full_core", "dense_ffn", "moe_router",
        "moe_experts",
    ), 0.0)
    for kind, routed in layers(config):
        if kind == CONV:
            parts["conv_proj"] += 2.0 * d * (3 * d + d)
        else:
            parts["attn_proj"] += 2.0 * d * dim * (2 * heads + 2 * kv)
            parts["full_core"] += 2.0 * heads * 2 * dim * (seq_len + 1) / 2
        if routed:
            parts["moe_router"] += 2.0 * d * config["num_experts_published"]
            parts["moe_experts"] += swiglu_flops_per_token(
                d, config["moe_intermediate_size"]
            ) * config["num_experts_per_tok"] * routed_here
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


def core_train_flops_per_step(config: dict, traffic: dict) -> float:
    """Every attention core of a step, forward (q k^T, p v) plus backward
    (dV, dP, dQ, dK: twice the forward); the backward's rebuilt logits
    are recomputation."""
    return 3.0 * tokens_per_step(traffic) * forward_flops_per_token(
        config, traffic["seq_len"]
    )["full_core"]


def core_train_bytes_per_step(config: dict, traffic: dict) -> float:
    """The least HBM traffic of the attention cores of a step: forward
    reads q, k, v and writes o; backward reads q, k, v, o, dO and writes
    dQ, dK, dV; k, v, dK and dV are Hkv heads wide."""
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    forward = 2 * heads + 2 * kv
    backward = (3 * heads + 2 * kv) + (heads + 2 * kv)
    return (
        float(BYTES) * config["head_dim"] * (forward + backward)
        * tokens_per_step(traffic) * count(config, FULL)
    )


def short_conv_train_flops_per_step(config: dict, traffic: dict) -> float:
    taps = config["conv_L_cache"]
    per_element = (2 * taps + 1) + (4 * taps + 3)
    return (
        float(per_element) * tokens_per_step(traffic)
        * config["hidden_size"] * count(config, CONV)
    )


def short_conv_train_bytes_per_step(config: dict, traffic: dict) -> float:
    """Four streams of tokens x d forward, seven backward."""
    return (
        float(BYTES) * (4 + 7) * tokens_per_step(traffic)
        * config["hidden_size"] * count(config, CONV)
    )
