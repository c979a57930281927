"""Operations and bytes the Kimi-Linear decoder's train step needs, from
the configuration's shapes alone (never from XLA's `cost_analysis`), by
PART, as `flops_lfm2.py` counts the LFM2 decoder's.  A multiply-add is 2
operations; backward costs twice forward; recomputation (remat, the
attention backward's rebuilt logits, the scan backward's rebuilt chunk,
the conv backward's rebuilt z) is not counted.

All counts are for the configuration AS CUT: the published layers in
`layers_held`, the held experts and the vocabulary slice the file states.

    kda core     the gated delta rule's RECURRENCE, whatever implements
                 it: a token and head decays the (dk, dv) state (dk dv),
                 reads k^T S (2 dk dv), writes the rank-one update (2 dk
                 dv and 2 dv) and reads S^T q (2 dk dv).  Its least
                 traffic is q, k, v, o and their gradients once in the
                 stated type and g, beta and theirs once in float32; the
                 boundary states and everything a chunked form rebuilds
                 are the implementation's, not the mathematics', so a
                 chunk size, a padded column or a rebuilt state lowers a
                 share of this roofline and nothing lifts it over 100%.
    short conv   y = silu(conv_K(u)) over the fused q | k | v columns:
                 forward reads u and writes y, backward reads u and dy
                 and writes du; the K x 3W taps are nothing beside them.
    mla core     q k^T and p v at the PUBLISHED key width (128 + 64) and
                 value width (128), THE CAUSAL HALF COUNTED, so columns
                 padded inside the op show as a lower share.
"""

from __future__ import annotations

from benchmarks.flops_lm import (
    mla_core_flops_per_token,
    swiglu_flops_per_token,
)

KDA, MLA = "kda", "mla"
BYTES = 2          # the stated type, bfloat16
FLOAT32 = 4


def layers(config: dict) -> list:
    """[(kind, routed?)] of the layers the cut model has."""
    kda = set(config["linear_attn_config"]["kda_layers"])
    return [
        (KDA if i + 1 in kda else MLA, i >= config["first_k_dense_replace"])
        for i in config["layers_held"]
    ]


def count(config: dict, kind: str) -> int:
    return sum(1 for k, _ in layers(config) if k == kind)


def tokens_per_step(traffic: dict) -> int:
    return traffic["minibatch_size"] * traffic["seq_len"]


def kda_sizes(config: dict):
    """(heads, head width, columns) of a KDA layer."""
    linear = config["linear_attn_config"]
    return (
        linear["num_heads"], linear["head_dim"],
        linear["num_heads"] * linear["head_dim"],
    )


def held_share(config: dict) -> float:
    """Routing slots that land on a held expert under balanced load."""
    return config["held_experts"][1] / config["num_experts_published"]


def kda_proj_flops_per_token(config: dict) -> float:
    """q | k | v, the output projection, the two low-rank gates, beta."""
    d = config["hidden_size"]
    heads, dim, width = kda_sizes(config)
    return 2.0 * (
        d * 3 * width + width * d + 2 * (d * dim + dim * width) + d * heads
    )


def kda_core_flops_per_token(config: dict) -> float:
    """The recurrence of one token in one layer, forward."""
    heads, dim, _ = kda_sizes(config)
    return float(heads) * (7 * dim * dim + 2 * dim)


def mla_proj_flops_per_token(config: dict) -> float:
    """No low-rank query: q, kv_a, kv_b and the output projection."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return 2.0 * (
        d * heads * qk
        + d * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
        + config["kv_lora_rank"] * heads
        * (config["qk_nope_head_dim"] + config["v_head_dim"])
        + heads * config["v_head_dim"] * d
    )


def forward_flops_per_token(config: dict, seq_len: int,
                            routed_here: float = None) -> dict:
    """{part: operations of one token, forward, over the whole cut
    model}.  `routed_here` is the share of the tokens x top_k slots that
    chose a held expert (`held_share` when not measured)."""
    if routed_here is None:
        routed_here = held_share(config)
    d = config["hidden_size"]
    parts = dict.fromkeys((
        "kda_proj", "kda_core", "mla_proj", "mla_core", "dense_ffn",
        "moe_router", "moe_shared", "moe_experts",
    ), 0.0)
    for kind, routed in layers(config):
        if kind == KDA:
            parts["kda_proj"] += kda_proj_flops_per_token(config)
            parts["kda_core"] += kda_core_flops_per_token(config)
        else:
            parts["mla_proj"] += mla_proj_flops_per_token(config)
            parts["mla_core"] += mla_core_flops_per_token(config, seq_len)
        if routed:
            expert = swiglu_flops_per_token(
                d, config["moe_intermediate_size"]
            )
            parts["moe_router"] += 2.0 * d * config["num_experts_published"]
            parts["moe_shared"] += config["num_shared_experts"] * expert
            parts["moe_experts"] += (
                expert * config["num_experts_per_token"] * routed_here
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


def kda_core_train_flops_per_step(config: dict, traffic: dict) -> float:
    return 3.0 * tokens_per_step(traffic) * forward_flops_per_token(
        config, traffic["seq_len"]
    )["kda_core"]


def kda_core_train_bytes_per_step(config: dict, traffic: dict) -> float:
    """q, k, v, o and their gradients once (2 bytes), g and its gradient
    once and beta and its gradient once (float32)."""
    heads, _, width = kda_sizes(config)
    per_token = 8 * width * BYTES + 2 * width * FLOAT32 + 2 * heads * FLOAT32
    return float(per_token) * tokens_per_step(traffic) * count(config, KDA)


def short_conv_train_flops_per_step(config: dict, traffic: dict) -> float:
    """An element costs 2K - 1 operations and silu's 4 forward, the K
    multiplies and K - 1 adds of du, the K multiplies and K adds of dw
    and silu's slope (6) backward."""
    taps = config["linear_attn_config"]["short_conv_kernel_size"]
    per_element = (2 * taps - 1 + 4) + (4 * taps - 1 + 6)
    return (
        float(per_element) * tokens_per_step(traffic)
        * 3 * kda_sizes(config)[2] * count(config, KDA)
    )


def short_conv_train_bytes_per_step(config: dict, traffic: dict) -> float:
    """Two streams of tokens x 3W forward (u, y), three backward (u, dy,
    du)."""
    return (
        float(BYTES) * (2 + 3) * tokens_per_step(traffic)
        * 3 * kda_sizes(config)[2] * count(config, KDA)
    )


def mla_core_train_flops_per_step(config: dict, traffic: dict) -> float:
    """Every attention core of a step, forward (q k^T, p v) plus backward
    (dV, dP, dQ, dK: twice the forward)."""
    return 3.0 * tokens_per_step(traffic) * forward_flops_per_token(
        config, traffic["seq_len"]
    )["mla_core"]


def mla_core_train_bytes_per_step(config: dict, traffic: dict) -> float:
    """Forward reads q, k, v and writes o; backward reads q, k, v, o, dO
    and writes dQ, dK, dV, each at its published width."""
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    v = config["v_head_dim"]
    forward = 2 * qk + 2 * v
    backward = (2 * qk + 3 * v) + (2 * qk + v)
    return (
        float(BYTES) * config["num_attention_heads"] * (forward + backward)
        * tokens_per_step(traffic) * count(config, MLA)
    )
