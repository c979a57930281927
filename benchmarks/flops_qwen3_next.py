"""Operations and bytes the Qwen3-Next decoder's train step needs, from the
configuration's shapes alone (never from XLA's `cost_analysis`), by PART,
as `flops_kimi.py` counts the Kimi-Linear decoder's.  A multiply-add is 2
operations; backward costs twice forward; recomputation (remat, the
attention backward's rebuilt logits, the scan backward's rebuilt chunk, the
conv backward's rebuilt z, the walk's rebuilt forward) is not counted.

All counts are for the configuration AS CUT: the published layers in
`layers_held`, the held experts and the vocabulary slice the file states.

    gdn core     the SCALAR-DECAY CHUNKED mathematics at the op's chunk C
                 (`ops/gdn.py: CHUNK`, 64), whatever implements it.  Once a
                 KEY head and token: Q K^T and K K^T (2 C dk each).  Once
                 a VALUE head and token: the state's read K S (2 dk dv),
                 the substitution's triangular products (C dv), the
                 outputs Q S and P U (2 dk dv and 2 C dv) and the state's
                 write K^T U (2 dk dv); the decays, masks and scalings are
                 nothing beside them.  Its least traffic is q and k and
                 their gradients once a KEY head and v, o and theirs once
                 a value head in the stated type, g, beta and theirs once
                 in float32; the boundary states and everything the
                 backward rebuilds are the implementation's, so a rebuilt
                 chunk or a padded row lowers a share of this roofline
                 and nothing lifts it over 100%.
    short conv   y = silu(conv_K(u)) over the q | k | v columns (8,192):
                 forward reads u and writes y, backward reads u and dy
                 and writes du; the K x W taps are nothing beside them.
    gqa core     q k^T and p v of the attention layer at 16 heads of 256
                 over 2 K/V heads, THE CAUSAL HALF COUNTED; the backward's
                 four products (dV, dP, dQ, dK) are twice the forward, its
                 rebuilt logits are recomputation and are not counted.
    moe experts  the grouped products over the rows ACTUALLY routed here:
                 three products an expert (gate, up, down) and their
                 transposes, 6 d w operations a row forward.
"""

from __future__ import annotations

BYTES = 2          # the stated type, bfloat16
FLOAT32 = 4
# `elasticdl_tpu/ops/gdn.py: CHUNK`: the benchmark counts from shapes and
# imports nothing of the program.
GDN_CHUNK = 64


def layers(config: dict) -> list:
    """True a delta-rule layer, False an attention layer, of the layers
    the cut model has."""
    every = config["full_attention_interval"]
    return [(i + 1) % every != 0 for i in config["layers_held"]]


def count(config: dict, is_gdn: bool) -> int:
    return sum(1 for kind in layers(config) if kind == is_gdn)


def tokens_per_step(traffic: dict) -> int:
    return traffic["minibatch_size"] * traffic["seq_len"]


def gdn_sizes(config: dict):
    """(key heads, value heads, key width, value width) of a delta-rule
    layer."""
    return (
        config["linear_num_key_heads"], config["linear_num_value_heads"],
        config["linear_key_head_dim"], config["linear_value_head_dim"],
    )


def conv_columns(config: dict) -> int:
    """q | k | v: what the conv passes over."""
    key_heads, value_heads, dk, dv = gdn_sizes(config)
    return 2 * key_heads * dk + value_heads * dv


def held_share(config: dict) -> float:
    """Routing slots that land on a held expert under balanced load."""
    return config["held_experts"][1] / config["num_experts_published"]


def gdn_proj_flops_per_token(config: dict) -> float:
    """q | k | v | z, b | a, and the output projection."""
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


def attn_core_flops_per_token(config: dict, seq_len: int) -> float:
    """q k^T and p v of one token's row in one layer, forward, the causal
    half: position t attends t + 1 keys, (L + 1) / 2 on average."""
    return (
        2.0 * config["num_attention_heads"] * 2 * config["head_dim"]
        * (seq_len + 1) / 2
    )


def swiglu_flops_per_token(hidden: int, width: int) -> float:
    return 2.0 * 3 * hidden * width


def forward_flops_per_token(config: dict, seq_len: int,
                            routed_here: float = None) -> dict:
    """{part: operations of one token, forward, over the whole cut
    model}.  `routed_here` is the share of the tokens x top_k slots that
    chose a held expert (`held_share` when not measured)."""
    if routed_here is None:
        routed_here = held_share(config)
    d, heads = config["hidden_size"], config["num_attention_heads"]
    dim, kv = config["head_dim"], config["num_key_value_heads"]
    parts = dict.fromkeys((
        "gdn_proj", "gdn_core", "attn_proj", "attn_core", "moe_router",
        "moe_shared", "moe_experts",
    ), 0.0)
    for is_gdn in layers(config):
        if is_gdn:
            parts["gdn_proj"] += gdn_proj_flops_per_token(config)
            parts["gdn_core"] += gdn_core_flops_per_token(config)
        else:
            # q | gate, k, v and the output projection
            parts["attn_proj"] += 2.0 * d * dim * (3 * heads + 2 * kv)
            parts["attn_core"] += attn_core_flops_per_token(config, seq_len)
        parts["moe_router"] += 2.0 * d * config["num_experts_published"]
        parts["moe_shared"] += swiglu_flops_per_token(
            d, config["shared_expert_intermediate_size"]
        ) + 2.0 * d
        parts["moe_experts"] += (
            swiglu_flops_per_token(d, config["moe_intermediate_size"])
            * config["num_experts_per_tok"] * routed_here
        )
    parts["head"] = 2.0 * d * config["vocab_size"]
    return parts


def train_flops_per_token(config: dict, seq_len: int,
                          routed_here: float = None) -> float:
    return 3.0 * sum(
        forward_flops_per_token(config, seq_len, routed_here).values()
    )


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
    """Two streams of tokens x 8,192 forward (u, y), three backward (u,
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
    d, width = config["hidden_size"], config["moe_intermediate_size"]
    rows = (
        tokens_per_step(traffic) * config["num_experts_per_tok"] * routed_here
    )
    weights = config["held_experts"][1] * 3 * d * width * (3 * BYTES + FLOAT32)
    activations = rows * BYTES * 3 * (d + 2 * width + width + d)
    return len(layers(config)) * float(weights + activations)
