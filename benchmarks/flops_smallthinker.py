"""Operations and bytes the SmallThinker decoder's train step needs, from
the configuration's shapes alone (never from XLA's `cost_analysis`), by
PART, as `flops_laguna.py` counts the Laguna decoder's.  A multiply-add is
2 operations; backward costs twice forward; recomputation (remat, the
attention backward's rebuilt logits, the walk's rebuilt forward) is not
counted.

All counts are for the configuration AS CUT: the published layers in
`layers_held`, the held experts and the vocabulary slice the file states.

    gqa core      a full layer's q k^T and p v at 28 heads of 128 over 4
                  K/V heads, THE CAUSAL HALF COUNTED: position t attends
                  t + 1 keys (134,225,920 pairs a head at 16,384)
    window core   a band layer's, THE BAND COUNTED: position t attends
                  min(t + 1, sliding_window_size) keys (58,722,304 pairs a
                  head at 16,384 under a band of 4,096)
    bytes         q, o and their gradients once a QUERY head, k, v and
                  their gradients once a K/V head: a group's keys and
                  values are read once, however many query heads share
                  them (seven here)
    moe experts   the grouped ReGLU products over the rows ACTUALLY routed
                  here: three products an expert (gate, up, down) and
                  their transposes, 6 d w operations a row forward
"""

from __future__ import annotations

BYTES = 2          # the stated type, bfloat16
FLOAT32 = 4


def layers(config: dict) -> list:
    """True a band layer (a window and rotary), False a full one, of the
    layers the cut model has."""
    return [
        bool(config["sliding_window_layout"][i])
        for i in config["layers_held"]
    ]


def count(config: dict, banded: bool) -> int:
    return sum(1 for kind in layers(config) if kind == banded)


def tokens_per_step(traffic: dict) -> int:
    return traffic["minibatch_size"] * traffic["seq_len"]


def pairs_per_head(banded: bool, config: dict, seq_len: int) -> int:
    """(query, key) pairs one head scores over one sequence."""
    if not banded:
        return seq_len * (seq_len + 1) // 2
    window = min(config["sliding_window_size"], seq_len)
    return window * (window + 1) // 2 + (seq_len - window) * window


def core_flops_per_token(banded: bool, config: dict, seq_len: int) -> float:
    """q k^T and p v of one token's row in one layer, forward."""
    return (
        2.0 * config["num_attention_heads"] * 2 * config["head_dim"]
        * pairs_per_head(banded, config, seq_len) / seq_len
    )


def attn_proj_flops_per_token(config: dict) -> float:
    """q, k, v and o of one layer."""
    d, dim = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return 2.0 * d * dim * (2 * heads + 2 * kv)


def reglu_flops_per_token(hidden: int, width: int) -> float:
    return 2.0 * 3 * hidden * width


def held_share(config: dict) -> float:
    """Routing slots that land on a held expert under balanced load."""
    return (
        config["held_experts"][1]
        / config["moe_num_primary_experts_published"]
    )


def forward_flops_per_token(config: dict, seq_len: int,
                            routed_here: float = None) -> dict:
    """{part: matmul operations of one token, forward, over the whole
    cut model}.  `routed_here` is the share of the tokens x top_k slots
    that chose a held expert (`held_share` when not measured)."""
    if routed_here is None:
        routed_here = held_share(config)
    d = config["hidden_size"]
    parts = dict.fromkeys((
        "attn_proj", "gqa_core", "window_core", "moe_router", "moe_experts",
    ), 0.0)
    for banded in layers(config):
        parts["attn_proj"] += attn_proj_flops_per_token(config)
        parts["window_core" if banded else "gqa_core"] += (
            core_flops_per_token(banded, config, seq_len)
        )
        parts["moe_router"] += (
            2.0 * d * config["moe_num_primary_experts_published"]
        )
        parts["moe_experts"] += (
            reglu_flops_per_token(d, config["moe_ffn_hidden_size"])
            * config["moe_num_active_primary_experts"] * routed_here
        )
    parts["head"] = 2.0 * d * config["vocab_size"]
    return parts


def train_flops_per_token(config: dict, seq_len: int,
                          routed_here: float = None) -> float:
    return 3.0 * sum(
        forward_flops_per_token(config, seq_len, routed_here).values()
    )


def core_train_flops_per_step(config: dict, traffic: dict,
                              banded: bool) -> float:
    """Every attention core of one kind of a step, forward (q k^T, p v)
    plus backward (dV, dP, dQ, dK: four products of the same size, twice
    the forward); the backward's rebuilt logits are recomputation."""
    part = "window_core" if banded else "gqa_core"
    return 3.0 * tokens_per_step(traffic) * forward_flops_per_token(
        config, traffic["seq_len"]
    )[part]


def core_train_bytes_per_step(config: dict, traffic: dict,
                              banded: bool) -> float:
    """The least HBM traffic of the attention cores of one kind of a step
    in the stated 2-byte type: forward reads q, k, v and writes o;
    backward reads q, k, v, o, dO and writes dQ, dK, dV; k, v, dK and dV
    are Hkv heads wide (log-sum-exp and delta are 1/128 of a row and left
    out)."""
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    forward = 2 * heads + 2 * kv
    backward = (3 * heads + 2 * kv) + (heads + 2 * kv)
    return (
        float(BYTES) * config["head_dim"] * (forward + backward)
        * tokens_per_step(traffic) * count(config, banded)
    )


def moe_experts_train_flops_per_step(config: dict, traffic: dict,
                                     routed_here: float) -> float:
    """The grouped products over the rows ACTUALLY routed here."""
    return 3.0 * tokens_per_step(traffic) * forward_flops_per_token(
        config, traffic["seq_len"], routed_here
    )["moe_experts"]


def moe_experts_train_bytes_per_step(config: dict, traffic: dict,
                                     routed_here: float) -> float:
    """Least HBM traffic of the grouped products: each held expert's
    three matrices once forward and once for each of the backward's two
    uses (2-byte reads; the float32 gradient written once), and the routed
    rows in and out at 2 bytes: the rows, the gate | up product, the
    activation and the output, forward and for each of the backward's two
    uses."""
    d, width = config["hidden_size"], config["moe_ffn_hidden_size"]
    rows = (
        tokens_per_step(traffic) * config["moe_num_active_primary_experts"]
        * routed_here
    )
    weights = config["held_experts"][1] * 3 * d * width * (3 * BYTES + FLOAT32)
    activations = rows * BYTES * 3 * (d + 2 * width + width + d)
    return len(layers(config)) * float(weights + activations)
