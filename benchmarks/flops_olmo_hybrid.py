"""Operations and bytes the Olmo-Hybrid decoder's train step needs, from
the configuration's shapes alone (never from XLA's `cost_analysis`), by
PART, as `flops_qwen3_next.py` counts the Qwen3-Next decoder's.  A
multiply-add is 2 operations; backward costs twice forward; recomputation
(remat, the attention backward's rebuilt logits, the scan backward's
rebuilt chunk, the conv backward's rebuilt z) is not counted.

All counts are for the configuration AS CUT: the published layers in
`layers_held`, the HEADS held (the file's head counts are the held ones)
and the vocabulary slice, at the PUBLISHED head widths (96 in keys, 192 in
values, 128 in attention): never the widths an implementation pads a head
to, so that a share reads the same work whatever implements it.

    gdn core     the SCALAR-DECAY CHUNKED mathematics at the op's chunk C
                 (`ops/gdn.py: CHUNK`, 64), whatever implements it.  Once
                 a KEY head and token: Q K^T and K K^T (2 C dk each).
                 Once a VALUE head and token: the state's read K S (2 dk
                 dv), the substitution's triangular products (C dv), the
                 outputs Q S and P U (2 dk dv and 2 C dv) and the state's
                 write K^T U (2 dk dv).  Its least traffic is q and k and
                 their gradients once a KEY head (dk wide) and v, o and
                 theirs once a value head (dv wide) in the stated type, g,
                 beta and theirs once in float32; the boundary states,
                 padded columns and everything the backward rebuilds are
                 the implementation's, so they lower a share of this
                 roofline and nothing lifts it over 100%.
    short conv   y = silu(conv_K(u)) over the held q | k | v columns:
                 forward reads u and writes y, backward reads u and dy
                 and writes du; the K x W taps are nothing beside them.
    gqa core     q k^T and p v of the attention layer at the held heads
                 of 128 (one K/V head a query head), THE CAUSAL HALF
                 COUNTED; the backward's four products (dV, dP, dQ, dK)
                 are twice the forward.
    dense        the six projections and W_o of a delta-rule layer, the
                 four of attention, gate | up and down of every MLP, the
                 untied head over the vocabulary slice.

At the cell's sizes (ten heads held, 8,192 tokens): a delta-rule layer's
projections 0.484e12 forward, its core 0.014e12, an attention layer's
projections 0.322e12 and core 0.172e12, an MLP 2.078e12, the head
0.789e12.
"""

from __future__ import annotations

BYTES = 2          # the stated type, bfloat16
FLOAT32 = 4
# `elasticdl_tpu/ops/gdn.py: CHUNK`: the benchmark counts from shapes and
# imports nothing of the program.
GDN_CHUNK = 64

LINEAR = "linear_attention"


def layers(config: dict) -> list:
    """The published type of each layer the cut model has."""
    return [config["layer_types"][i] for i in config["layers_held"]]


def count(config: dict, linear: bool) -> int:
    return sum(1 for kind in layers(config) if (kind == LINEAR) == linear)


def tokens_per_step(traffic: dict) -> int:
    return traffic["minibatch_size"] * traffic["seq_len"]


def gdn_sizes(config: dict):
    """(key heads, value heads, key width, value width) of a delta-rule
    layer, the heads those HELD."""
    return (
        config["linear_num_key_heads"], config["linear_num_value_heads"],
        config["linear_key_head_dim"], config["linear_value_head_dim"],
    )


def conv_columns(config: dict) -> int:
    """q | k | v: what the conv passes over."""
    key_heads, value_heads, dk, dv = gdn_sizes(config)
    return 2 * key_heads * dk + value_heads * dv


def gdn_proj_flops_per_token(config: dict) -> float:
    """q, k, v, z, a, b and the output projection."""
    d = config["hidden_size"]
    _, value_heads, _, dv = gdn_sizes(config)
    values = value_heads * dv
    return 2.0 * (
        d * (conv_columns(config) + values) + d * 2 * value_heads + values * d
    )


def gdn_core_flops_per_token(config: dict) -> float:
    """The chunked form of one token in one layer, forward."""
    key_heads, value_heads, dk, dv = gdn_sizes(config)
    chunk = GDN_CHUNK
    return (
        float(key_heads) * 2 * (2 * chunk * dk)
        + float(value_heads) * (3 * 2 * dk * dv + 3 * chunk * dv)
    )


def attn_proj_flops_per_token(config: dict) -> float:
    """q, k, v and the output projection of the held heads."""
    d, dim = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return 2.0 * d * dim * (2 * heads + 2 * kv)


def attn_core_flops_per_token(config: dict, seq_len: int) -> float:
    """q k^T and p v of one token's row in one layer, forward, the causal
    half: position t attends t + 1 keys, (L + 1) / 2 on average."""
    return (
        2.0 * config["num_attention_heads"] * 2 * config["head_dim"]
        * (seq_len + 1) / 2
    )


def dense_ffn_flops_per_token(config: dict) -> float:
    return 2.0 * 3 * config["hidden_size"] * config["intermediate_size"]


def forward_flops_per_token(config: dict, seq_len: int) -> dict:
    """{part: operations of one token, forward, over the whole cut
    model}."""
    parts = dict.fromkeys(
        ("gdn_proj", "gdn_core", "attn_proj", "attn_core", "dense_ffn"), 0.0
    )
    for kind in layers(config):
        if kind == LINEAR:
            parts["gdn_proj"] += gdn_proj_flops_per_token(config)
            parts["gdn_core"] += gdn_core_flops_per_token(config)
        else:
            parts["attn_proj"] += attn_proj_flops_per_token(config)
            parts["attn_core"] += attn_core_flops_per_token(config, seq_len)
        parts["dense_ffn"] += dense_ffn_flops_per_token(config)
    parts["head"] = 2.0 * config["hidden_size"] * config["vocab_size"]
    return parts


def train_flops_per_token(config: dict, seq_len: int) -> float:
    return 3.0 * sum(forward_flops_per_token(config, seq_len).values())


def gdn_core_train_flops_per_step(config: dict, traffic: dict) -> float:
    return 3.0 * tokens_per_step(traffic) * forward_flops_per_token(
        config, traffic["seq_len"]
    )["gdn_core"]


def gdn_core_train_bytes_per_step(config: dict, traffic: dict) -> float:
    """q, k and their gradients once a KEY head, v, o and theirs once a
    value head (2 bytes), g, beta and their gradients once (float32)."""
    key_heads, value_heads, dk, dv = gdn_sizes(config)
    per_token = (
        4 * key_heads * dk * BYTES + 4 * value_heads * dv * BYTES
        + 4 * value_heads * FLOAT32
    )
    return float(per_token) * tokens_per_step(traffic) * count(config, True)


def short_conv_train_flops_per_step(config: dict, traffic: dict) -> float:
    """An element costs 2K - 1 operations and silu's 4 forward, the K
    multiplies and K - 1 adds of du, the K multiplies and K adds of dw
    and silu's slope (6) backward."""
    taps = config["linear_conv_kernel_dim"]
    per_element = (2 * taps - 1 + 4) + (4 * taps - 1 + 6)
    return (
        float(per_element) * tokens_per_step(traffic)
        * conv_columns(config) * count(config, True)
    )


def short_conv_train_bytes_per_step(config: dict, traffic: dict) -> float:
    """Two streams of tokens x columns forward (u, y), three backward (u,
    dy, du)."""
    return (
        float(BYTES) * (2 + 3) * tokens_per_step(traffic)
        * conv_columns(config) * count(config, True)
    )


def gqa_core_train_flops_per_step(config: dict, traffic: dict) -> float:
    """Every attention core of a step, forward (q k^T, p v) plus backward
    (dV, dP, dQ, dK: twice the forward)."""
    return 3.0 * tokens_per_step(traffic) * forward_flops_per_token(
        config, traffic["seq_len"]
    )["attn_core"]


def gqa_core_train_bytes_per_step(config: dict, traffic: dict) -> float:
    """Forward reads q, k, v and writes o; backward reads q, k, v, o, dO
    and writes dQ, dK, dV; k, v, dK and dV are Hkv heads wide."""
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    forward = 2 * heads + 2 * kv
    backward = (3 * heads + 2 * kv) + (heads + 2 * kv)
    return (
        float(BYTES) * config["head_dim"] * (forward + backward)
        * tokens_per_step(traffic) * count(config, False)
    )
